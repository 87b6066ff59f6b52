"""§Perf hillclimb switches: correctness parity with the baselines.

The optimized paths must be numerically equivalent — the §Perf wins come
from communication/memory scheduling, not changed math."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.models.blocks import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_mtp_share_trunk_identical_loss():
    """The MTP head reads the main stream's hidden states from the loss's
    one pass through the trunk: its term is the MTP head over the trunk
    re-run here, layer by layer."""
    cfg = get_config("deepseek-v3-671b", smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": toks}
    loss, m = model.loss_fn(params, batch)

    x = model._embed(params, toks)
    dense = cfg.replace(pattern=(cfg.prefix_kind,))
    for i in range(cfg.first_dense):
        x, _ = REGISTRY[cfg.prefix_kind].apply(
            jax.tree.map(lambda a: a[i], params["prefix"]), x, dense)
    for g in range(cfg.n_groups):
        gp = jax.tree.map(lambda a: a[g], params["groups"])
        for j, kind in enumerate(cfg.pattern):
            x, _ = REGISTRY[kind].apply(gp[f"b{j}"], x, cfg)
    rerun = {}
    model._mtp_loss(params, batch, rerun, x)
    np.testing.assert_allclose(float(m["mtp"]), float(rerun["mtp"]),
                               rtol=1e-5)
    np.testing.assert_allclose(
        float(loss), float(m["xent"] + m["aux"] + 0.3 * m["mtp"]),
        rtol=1e-5)


def test_ssd_shard_map_matches_gspmd():
    """Run the mamba2 smoke forward with and without shard_map SSD on an
    8-device subprocess mesh; outputs must match."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding
        from repro.launch.mesh import make_mesh
        from repro.models import build

        cfg = get_config("mamba2-1.3b", smoke=True).replace(
            ssm_headdim=16, d_model=64)
        model0 = build(cfg)
        model1 = build(cfg.replace(ssd_shard_map=True))
        params = model0.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab)
        mesh = make_mesh((4, 2), ("data", "model"))
        with sharding.use_mesh(mesh, {}):
            l0 = jax.jit(lambda p, t: model0.forward(p, t)[0])(params, toks)
            l1 = jax.jit(lambda p, t: model1.forward(p, t)[0])(params, toks)
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   atol=2e-4, rtol=2e-3)
        # gradients too
        def loss(m):
            def f(p):
                lg, _ = m.forward(p, toks)
                return jnp.sum(lg.astype(jnp.float32) ** 2)
            return f
        with sharding.use_mesh(mesh, {}):
            g0 = jax.jit(jax.grad(loss(model0)))(params)
            g1 = jax.jit(jax.grad(loss(model1)))(params)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=3e-3, rtol=3e-2)
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_q8_moments_smoke_training():
    """Full train step with int8 moments on a smoke config: loss drops."""
    from repro.data import SyntheticLMData
    from repro.train.train_step import init_state, make_train_step
    cfg = get_config("minicpm-2b", smoke=True)
    model = build(cfg)
    state = init_state(model, jax.random.PRNGKey(0), moment_dtype="int8")
    data = SyntheticLMData(cfg, batch=8, seq_len=32)
    step = jax.jit(make_train_step(model, lr=3e-3, q8_moments=True))
    losses = []
    for i in range(20):
        b = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    assert sum(losses[-5:]) / 5 < sum(losses[:5]) / 5 - 0.1
    # moments really are int8
    leaf = jax.tree.leaves(state["opt"]["mu"])[0]
    assert leaf.dtype == jnp.int8
