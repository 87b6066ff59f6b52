"""MoE correctness: the dropless held-expert layer vs the dense loop
oracle, skewed routing, gate normalization, aux loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import moe

CFG = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                  pattern=("moe",), n_experts=8, top_k=2, d_expert=48)


class TestDispatch:
    def test_matches_dense_oracle(self):
        p = moe.moe_init(jax.random.PRNGKey(0), CFG)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
        out, aux = moe.moe_apply(p, x, CFG)
        ref = moe.moe_apply_reference(p, x, CFG)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)
        assert float(aux) > 0

    def test_with_shared_expert(self):
        cfg = CFG.replace(n_shared_experts=1)
        p = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
        out, _ = moe.moe_apply(p, x, cfg)
        ref = moe.moe_apply_reference(p, x, cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    def test_skewed_routing_drops_no_token(self):
        """Every token routed to the same two experts: all of them are
        computed (a capacity buffer would have dropped most), so the
        layer still equals the dense oracle."""
        p = moe.moe_init(jax.random.PRNGKey(0), CFG)
        p["w_router"] = jnp.zeros_like(p["w_router"]).at[:, :2].set(10.0)
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32)))
        _, ids, _ = moe.route(p, x.reshape(-1, 32), CFG)
        assert set(np.unique(np.asarray(ids)).tolist()) == {0, 1}
        out, _ = moe.moe_apply(p, x, CFG)
        ref = moe.moe_apply_reference(p, x, CFG)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    def test_gates_normalized(self):
        p = moe.moe_init(jax.random.PRNGKey(0), CFG)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 32))
        logits = x.reshape(-1, 32).astype(jnp.float32) @ p["w_router"]
        gv, _ = jax.lax.top_k(jax.nn.softmax(logits, -1), CFG.top_k)
        gv = gv / jnp.sum(gv, axis=-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(jnp.sum(gv, -1)), 1.0,
                                   atol=1e-6)

    def test_grad_flows_through_dispatch(self):
        p = moe.moe_init(jax.random.PRNGKey(0), CFG)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))

        def loss(pp):
            out, aux = moe.moe_apply(pp, x, CFG)
            return jnp.sum(out.astype(jnp.float32) ** 2) + aux

        g = jax.grad(loss)(p)
        for leaf in jax.tree.leaves(g):
            assert bool(jnp.all(jnp.isfinite(leaf)))
        # expert weights actually receive gradient
        assert float(jnp.linalg.norm(g["we_g"])) > 0

    def test_aux_loss_balanced_lower_than_collapsed(self):
        """Uniform routing should have lower aux loss than collapsed."""
        p = moe.moe_init(jax.random.PRNGKey(0), CFG)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 32))
        _, aux_normal = moe.moe_apply(p, x, CFG)
        # collapse routing: all mass on expert 0 regardless of input
        p2 = dict(p)
        p2["w_router"] = jnp.zeros_like(p["w_router"]).at[:, 0].set(10.0)
        _, aux_collapsed = moe.moe_apply(p2, x, CFG)
        assert float(aux_collapsed) > float(aux_normal)
