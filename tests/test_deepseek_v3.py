"""DeepSeek-V3's mechanisms on the CPU at small sizes, each against a
plain statement of it: noaux_tc routing against a loop over tokens, YaRN
against its formulas, the absorbed latent decode against the expanded
form, attention in blocks against one block, the held-expert shares
against the uncut layer, and the counters that say which form and which
prefill path a program traced."""
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build, mla, moe
from repro.models.layers import rope_freqs, yarn_mscale
from repro.obs import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = get_config("deepseek-v3-671b", smoke=True)


def router_inputs(cfg, t=64, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = moe.moe_init(k1, cfg)
    p["e_score_correction_bias"] = 0.3 * jax.random.normal(
        k2, (cfg.router_width,))
    return p, jax.random.normal(k3, (t, cfg.d_model))


def route_one(p, x, cfg):
    """noaux_tc for one token, as the release's gate states it."""
    scores = 1 / (1 + np.exp(-(x @ np.asarray(p["w_router"]))))
    choice = scores + np.asarray(p["e_score_correction_bias"])
    per = cfg.router_width // cfg.n_group
    group_score = [sorted(choice[g * per:(g + 1) * per])[-2:]
                   for g in range(cfg.n_group)]
    best = sorted(range(cfg.n_group), key=lambda g: -sum(group_score[g]))
    kept = set(best[:cfg.topk_group])
    masked = [choice[e] if e // per in kept else 0.0
              for e in range(cfg.router_width)]
    ids = sorted(range(cfg.router_width), key=lambda e: -masked[e])
    ids = ids[:cfg.top_k]
    gates = scores[ids] / (scores[ids].sum() + 1e-20)
    return ids, gates * cfg.routed_scaling_factor


@pytest.mark.parametrize("n_group,topk_group", [(4, 2), (8, 4), (1, 1)])
def test_noaux_tc_matches_a_loop_over_tokens(n_group, topk_group):
    cfg = SMOKE.replace(n_experts=16, top_k=4, n_group=n_group,
                        topk_group=topk_group)
    p, x = router_inputs(cfg)
    gates, ids, _ = moe.route(p, x, cfg)
    xs = np.asarray(x, np.float64)
    for t in range(x.shape[0]):
        want_ids, want_gates = route_one(p, xs[t], cfg)
        order = np.argsort(np.asarray(ids[t]))
        assert sorted(want_ids) == np.asarray(ids[t])[order].tolist()
        got = dict(zip(np.asarray(ids[t]).tolist(), np.asarray(gates[t])))
        np.testing.assert_allclose([got[e] for e in want_ids], want_gates,
                                   rtol=1e-5)
        if n_group > 1:     # every choice lies in one of the kept groups
            per = cfg.router_width // n_group
            assert len({int(e) // per for e in ids[t]}) <= topk_group


def test_yarn_frequencies_and_scale_follow_the_formulas():
    d, base, factor, orig = 64, 10000.0, 40.0, 4096
    got = np.asarray(rope_freqs(d, base, (factor, orig)))
    extra = 1.0 / base ** (np.arange(0, d, 2) / d)

    def corr_dim(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = math.floor(corr_dim(32)), math.ceil(corr_dim(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    want = extra / factor * ramp + extra * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:low], extra[:low], rtol=1e-6)
    np.testing.assert_allclose(got[high:], extra[high:] / factor, rtol=1e-6)
    assert yarn_mscale(40.0) == pytest.approx(0.1 * math.log(40) + 1)
    cfg = get_config("deepseek-v3-671b")
    assert mla.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert (0.1 * math.log(40) + 1) ** 2 == pytest.approx(1.874, abs=1e-3)


def mla_case(seed=0, b=2, s=9):
    cfg = SMOKE
    kp, kx, kn = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = mla.mla_init(kp, cfg)
    p["q_norm"] = 1 + 0.1 * jax.random.normal(kn, p["q_norm"].shape)
    x = jax.random.normal(kx, (b, s, cfg.d_model))
    return cfg, p, x


def test_absorbed_decode_equals_the_expanded_form():
    """The prompt's first S-1 positions prefilled (expanded), the last one
    decoded (absorbed) from the latent cache: its output equals the
    expanded form's at that position, and its cache entry the latents the
    expanded form writes."""
    cfg, p, x = mla_case()
    s = x.shape[1]
    full = mla.mla_apply(p, x, cfg)
    cache = mla.init_mla_cache(cfg, x.shape[0], s + 3)
    _, cache = mla.mla_prefill(p, x[:, :-1], cache, cfg)
    out, cache = mla.mla_decode(p, x[:, -1:], cache, jnp.int32(s - 1), cfg)
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, -1]), atol=1e-5,
                               rtol=1e-5)
    _, whole = mla.mla_prefill(p, x, mla.init_mla_cache(cfg, x.shape[0],
                                                        s + 3), cfg)
    for k in ("latent", "k_rope"):
        np.testing.assert_allclose(np.asarray(cache[k]),
                                   np.asarray(whole[k]), atol=1e-6)


def test_attention_in_blocks_equals_one_block(monkeypatch):
    """Scores held to one small block pair at a time (with the sequence
    padded to whole key blocks, some skipped after the diagonal) give
    what one block of all scores gives."""
    cfg, p, x = mla_case(seed=1, s=300)
    one = mla.mla_apply(p, x, cfg)
    monkeypatch.setattr(mla, "SCORE_BLOCK_BYTES", 2 * 4 * 64 * 128 * 4)
    monkeypatch.setattr(mla, "QUERY_BLOCK", 64)
    # 64 queries against 128 keys; 300 positions padded to 384
    assert mla._score_blocks(2, 4, 300) == (64, 128)
    blocked = mla.mla_apply(p, x, cfg)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(one),
                               atol=2e-5, rtol=2e-5)


def test_held_shares_sum_to_the_uncut_layer():
    """Four layers each holding 2 of 8 experts, the shared expert taken
    once: their parts add up to the uncut layer's reference."""
    cfg = SMOKE.replace(n_group=1, topk_group=1)
    p, _ = router_inputs(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))
    want = moe.moe_apply_reference(p, x, cfg)
    shared = moe._shared(p, x.reshape(-1, cfg.d_model), jnp.float32)
    total = shared.reshape(x.shape)
    for lo in range(0, 8, 2):
        share = cfg.replace(n_experts=2, n_routed_experts=8, expert_lo=lo)
        ps = dict(p, **{k: p[k][lo:lo + 2] for k in ("we_g", "we_u",
                                                     "we_d")})
        out, _ = moe.moe_apply(ps, x, share)
        total = total + out - shared.reshape(x.shape)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_chunks_of_sorted_pairs_equal_one_chunk(monkeypatch):
    """The held pairs run through the ragged products in as many chunks as
    they fill: with chunks of 512 rows, 3 x 160 tokens' pairs (about 480
    held of 960) take one or two chunks and give what one chunk gives,
    and the dense oracle's result."""
    cfg = SMOKE.replace(n_experts=4, n_routed_experts=8, expert_lo=4)
    p, _ = router_inputs(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 160, cfg.d_model))
    one, _ = moe.moe_apply(p, x, cfg)
    monkeypatch.setattr(moe, "CHUNK_ROWS", 512)
    chunked, _ = moe.moe_apply(p, x, cfg)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(one),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(one), np.asarray(
        moe.moe_apply_reference(p, x, cfg)), atol=2e-5, rtol=2e-4)


def counts(family, label):
    series = metrics.snapshot()[family]["series"]
    return {s["labels"][label]: s["value"] for s in series}


def test_counters_name_the_form_and_the_path():
    """prefill traces the sequence path and the expanded form (once per
    layer scan: the dense prefix and the expert layers); decode_step the
    absorbed form, as often."""
    model = build(SMOKE)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    forms0 = counts("repro_jax_mla_lowerings_total", "form")
    paths0 = counts("repro_jax_prefill_lowerings_total", "path")
    jax.eval_shape(model.prefill, params,
                   jax.ShapeDtypeStruct((2, 8), jnp.int32), cache)
    forms1 = counts("repro_jax_mla_lowerings_total", "form")
    paths1 = counts("repro_jax_prefill_lowerings_total", "path")
    assert paths1["sequence"] == paths0["sequence"] + 1
    assert paths1["token"] == paths0["token"]
    assert forms1 == {"expanded": forms0["expanded"] + 2,
                      "absorbed": forms0["absorbed"]}
    jax.eval_shape(model.decode_step, params, cache,
                   jax.ShapeDtypeStruct((2, 1), jnp.int32),
                   jax.ShapeDtypeStruct((), jnp.int32))
    forms2 = counts("repro_jax_mla_lowerings_total", "form")
    assert forms2 == {"expanded": forms1["expanded"],
                      "absorbed": forms1["absorbed"] + 2}


def test_experts_split_over_a_mesh_equal_one_device():
    """Under a (data=2, model=2) mesh each model rank computes its own
    half of the held experts (shard_map) and the parts are summed: the
    layer equals the one-device layer, with few tokens and with many."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding
        from repro.launch.mesh import make_mesh
        from repro.models import moe
        cfg = get_config("deepseek-v3-671b", smoke=True)
        p = moe.moe_init(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh((2, 2), ("data", "model"))
        # 16 tokens a data rank (each expert over them), then 128 (sorted)
        for seq in (8, 64):
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  (4, seq, cfg.d_model))
            want, _ = moe.moe_apply(p, x, cfg)
            with sharding.use_mesh(mesh, {}):
                got, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(p, x)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, rtol=1e-5)
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
