"""LanguageModel.prefill's two paths: one sequence-parallel pass for models
made of attention, latent-attention, expert and SSM blocks, and
``_prefill_tokens``, ``decode_step`` over the prompt one token at a time,
which every other model keeps.  From the same prompt and cache both must
give the same last-token logits and the same cache, and decoding on from
either must agree.  For latent attention this also holds the expanded
form (the pass) to the absorbed one (decode); for the SSM the chunked
scan from the cache's state to the recurrence one token at a time."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, memory_len
from repro.configs.base import ModelConfig
from repro.kernels.ssd import ref as ssd_ref
from repro.kernels.ssd import ssd_scan
from repro.models import build, ssm
from repro.models.layers import dtype_of, rmsnorm
from repro.obs import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = ModelConfig(name="tiny-prefill", family="dense", n_layers=2,
                   d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=128)
LOCAL = BASE.replace(pattern=("local_attn",), window=7)   # an 8-slot ring
# DeepSeek-V3's blocks: MLA prefix layer, then MLA + held experts (4 of
# 8, from id 2, noaux_tc over 4 groups) with a shared expert
DSV3 = get_config("deepseek-v3-671b", smoke=True).replace(
    n_experts=4, n_routed_experts=8, expert_lo=2)
MAMBA2 = get_config("mamba2-1.3b", smoke=True)            # chunk 16, conv 4
DECODE_STEPS = 4

# config, prompt length, cache length
CASES = {
    "attn": (BASE, 12, 16),
    "local_attn-shorter-than-ring": (LOCAL, 5, 20),
    "local_attn-equal-to-ring": (LOCAL, 8, 20),
    "local_attn-ring-wraps": (LOCAL, 13, 20),
    "first_dense-prefix": (BASE.replace(n_layers=3, first_dense=1), 12, 16),
    "mla-moe-held-share": (DSV3, 12, 16),
    "gqa-moe": (get_config("qwen3-moe-235b-a22b", smoke=True), 12, 16),
    "ssm-shorter-than-conv": (MAMBA2, 2, 8),
    "ssm-one-chunk": (MAMBA2, 16, 24),
    "ssm-ragged-tail": (MAMBA2, 40, 48),
    "ssm-four-chunks": (MAMBA2, 64, 72),
}
# float32 agrees to rounding; bfloat16 to test_bf16_prefill_matches_forward's
# share of the largest logit
TOLERANCE = {"float32": 1e-5, "bfloat16": 5e-2}


def assert_close(got, want, rel):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1.0)
    err = float(np.max(np.abs(got - want)))
    assert err <= rel * scale, (err, scale)


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("case", sorted(CASES))
def test_sequence_prefill_matches_token_loop(case, dtype):
    cfg, s, max_len = CASES[case]
    cfg = cfg.replace(dtype=dtype, param_dtype=dtype)
    model = build(cfg)
    k_par, k_tok, k_cache = jax.random.split(jax.random.PRNGKey(0), 3)
    params = model.init(k_par)
    tokens = jax.random.randint(k_tok, (2, s), 0, cfg.vocab)
    # noise in every slot: those the prompt does not reach come back as
    # they were passed in
    leaves, tree = jax.tree.flatten(model.init_cache(2, max_len))
    keys = jax.random.split(k_cache, len(leaves))
    cache = jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape).astype(a.dtype)
        for k, a in zip(keys, leaves)])

    logits, seq_cache = jax.jit(model.prefill)(params, tokens, cache)
    want, tok_cache = jax.jit(model._prefill_tokens)(params, tokens, cache)
    assert logits.shape == (2, cfg.vocab)
    assert logits.dtype == want.dtype == jnp.dtype(dtype)
    rel = TOLERANCE[dtype]
    assert_close(logits, want, rel)
    assert jax.tree.structure(seq_cache) == jax.tree.structure(tok_cache)
    for a, b in zip(jax.tree.leaves(seq_cache), jax.tree.leaves(tok_cache)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert_close(a, b, rel)

    step = jax.jit(model.decode_step)
    for i in range(DECODE_STEPS):
        nxt = jnp.argmax(want, axis=-1).astype(jnp.int32)[:, None]
        logits, seq_cache = step(params, seq_cache, nxt, jnp.int32(s + i))
        want, tok_cache = step(params, tok_cache, nxt, jnp.int32(s + i))
        assert_close(logits, want, rel)


def test_prompt_longer_than_a_full_cache_is_refused():
    model = build(BASE)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, 8))
    tokens = jax.ShapeDtypeStruct((2, 9), jnp.int32)
    with pytest.raises(ValueError, match="does not fit"):
        jax.eval_shape(model.prefill, params, tokens, cache)


def prefill_lowerings():
    series = metrics.snapshot()["repro_jax_prefill_lowerings_total"]
    return {s["labels"]["path"]: s["value"] for s in series["series"]}


@pytest.mark.parametrize("arch, path", [
    ("minicpm-2b", "sequence"),             # attn
    ("h2o-danube-1.8b", "sequence"),        # local_attn
    ("mamba2-1.3b", "sequence"),            # ssm
    ("recurrentgemma-9b", "token"),         # rglru (and local_attn)
    ("qwen3-moe-235b-a22b", "sequence"),    # moe (GQA)
    ("deepseek-v3-671b", "sequence"),       # mla prefix, moe (MLA)
    ("llama-3.2-vision-90b", "token"),      # cross_attn over image memory
])
def test_prefill_path_follows_the_block_kinds(arch, path):
    """Tracing prefill counts one lowering under the path its blocks
    allow, and none under the other."""
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, 16))
    tokens = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    mlen = memory_len(cfg, 8)
    memory = (None if mlen is None else
              jax.ShapeDtypeStruct((2, mlen, cfg.d_model), jnp.float32))
    before = prefill_lowerings()
    logits, _ = jax.eval_shape(
        lambda p, t, c, m: model.prefill(p, t, c, memory_embeds=m), params,
        tokens, cache, memory)
    after = prefill_lowerings()
    assert logits.shape == (2, cfg.vocab)
    other = {"sequence": "token", "token": "sequence"}[path]
    assert after[path] == before[path] + 1
    assert after[other] == before[other]


# --------------------------------------------------------------------------
# the SSM prefill's pieces: the chunked SSD from a state, and ssm_apply
# --------------------------------------------------------------------------

def ssd_inputs(s, h=4, p=8, n=16, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (b, s, h, p), jnp.float32),
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))),
            0.5 * jax.random.normal(ks[2], (h,)),
            jax.random.normal(ks[3], (b, s, n)),
            jax.random.normal(ks[4], (b, s, n)),
            jax.random.normal(ks[5], (b, h, n, p), jnp.float32))


@pytest.mark.parametrize("unroll_heads", [False, True])
@pytest.mark.parametrize("s", [16, 48, 40], ids=["one-chunk", "three-chunks",
                                                "padded-tail"])
def test_chunked_ssd_from_a_state_matches_the_recurrence(s, unroll_heads):
    """The chunked SSD started from a nonzero state gives the exact
    per-timestep recurrence's outputs and final state, in float32, at one
    and three chunks of 16 and with a tail padded with dt = 0."""
    x, dt, a_log, b, c, h0 = ssd_inputs(s)
    y, h_last = jax.jit(lambda h0, *a: ssd_ref.ssd_chunked_from(
        h0, *a, chunk=16, head_blocks=2, unroll_heads=unroll_heads))(
        h0, x, dt, a_log, b, c)
    want_y, want_h = jax.jit(ssd_ref.ssd_scan_ref)(x, dt, a_log, b, c, h0)
    assert y.shape == x.shape and h_last.shape == h0.shape
    assert h_last.dtype == jnp.float32
    assert_close(y, want_y, 1e-5)
    assert_close(h_last, want_h, 1e-5)


def ssm_apply_before(p, x, cfg):
    """``ssm_apply`` as it was before the prefill shared its code (the
    single-device path)."""
    dt_ = dtype_of(cfg)
    di, n, h, _ = ssm._dims(cfg)
    b, s, _ = x.shape
    z = x @ p["w_z"].astype(dt_)
    xs = x @ p["w_xs"].astype(dt_)
    bmat = x @ p["w_b"].astype(dt_)
    cmat = x @ p["w_c"].astype(dt_)
    dt_raw = x @ p["w_dtp"].astype(dt_)

    (wx, bx), (wb, bb), (wc, bc) = ssm._conv_split(p, cfg)

    def conv(v, w, bias):
        pad = jnp.pad(v, ((0, 0), (cfg.conv_width - 1, 0), (0, 0)))
        out = sum(pad[:, j:j + s, :] * w[j][None, None, :]
                  for j in range(cfg.conv_width))
        return jax.nn.silu(out + bias[None, None, :])

    xs = conv(xs, wx.astype(dt_), bx.astype(dt_))
    bmat = conv(bmat, wb.astype(dt_), bb.astype(dt_))
    cmat = conv(cmat, wc.astype(dt_), bc.astype(dt_))
    xh = xs.reshape(b, s, h, cfg.ssm_headdim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    y = ssd_scan(xh.astype(jnp.float32), dt, p["a_log"],
                 bmat.astype(jnp.float32), cmat.astype(jnp.float32),
                 chunk=cfg.ssm_chunk, use_kernel=False,
                 unroll_heads=cfg.attn_chunk_unroll, head_blocks=h)
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    y = y.reshape(b, s, di).astype(dt_)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    return y @ p["w_out"].astype(dt_)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("s", [48, 40], ids=["chunked", "ragged"])
def test_ssm_apply_is_unchanged_by_the_shared_code(s, dtype):
    """The training and ``forward`` path computes what it computed before
    ``ssm_prefill`` shared its code: bit for bit, from the same program."""
    cfg = MAMBA2.replace(dtype=dtype, param_dtype=dtype)
    k_par, k_x = jax.random.split(jax.random.PRNGKey(1))
    p = ssm.ssm_init(k_par, cfg)
    x = jax.random.normal(k_x, (2, s, cfg.d_model)).astype(dtype)
    got = jax.jit(lambda p, x: ssm.ssm_apply(p, x, cfg))(p, x)
    want = jax.jit(lambda p, x: ssm_apply_before(p, x, cfg))(p, x)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert (str(jax.make_jaxpr(lambda p, x: ssm.ssm_apply(p, x, cfg))(p, x))
            == str(jax.make_jaxpr(
                lambda p, x: ssm_apply_before(p, x, cfg))(p, x)))


def test_ssm_prefill_over_a_mesh_equals_one_device():
    """Under a (data=2, model=2) mesh, mamba2's prefill (the chunked SSD
    from the cache's state, heads split over "model") and the decode steps
    after it give one device's logits, in float32, across a ragged tail."""
    code = textwrap.dedent("""
        import jax
        jax.config.update("jax_num_cpu_devices", 4)
        import numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding
        from repro.launch.mesh import make_mesh
        from repro.models import build
        from repro.obs import metrics
        from repro.train.serve_step import greedy_generate
        cfg = get_config("mamba2-1.3b", smoke=True).replace(
            dtype="float32", param_dtype="float32")
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        prompt = jax.random.randint(jax.random.PRNGKey(1), (4, 40), 0,
                                    cfg.vocab)
        single = greedy_generate(model, params, prompt, max_new=4)
        mesh = make_mesh((2, 2), ("data", "model"))
        with sharding.use_mesh(mesh):
            params_d = jax.device_put(
                params, sharding.param_shardings(mesh, params))
            prompt_d = jax.device_put(prompt, sharding.tree_shardings(
                mesh, sharding.batch_specs_tree(prompt, mesh=mesh)))
            sharded = greedy_generate(model, params_d, prompt_d, max_new=4)
        want = np.asarray(single.logits)
        err = np.max(np.abs(np.asarray(sharded.logits) - want))
        assert err <= 1e-5 * max(np.max(np.abs(want)), 1.0), err
        assert (np.asarray(sharded.tokens) == np.asarray(single.tokens)).all()
        series = metrics.snapshot()["repro_jax_prefill_lowerings_total"]
        paths = {s["labels"]["path"]: s["value"] for s in series["series"]}
        assert paths.get("token", 0) == 0 and paths["sequence"] >= 2, paths
        print("OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_ssm_serving_loads_no_pallas():
    """Tracing mamba2's prefill and decode step imports no Pallas: neither
    runs a kernel, and the import costs seconds of a server's start."""
    code = textwrap.dedent("""
        import sys
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build
        model = build(get_config("mamba2-1.3b", smoke=True))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        cache = model.init_cache(2, 24, abstract=True)
        jax.eval_shape(model.prefill, params,
                       jax.ShapeDtypeStruct((2, 20), jnp.int32), cache)
        jax.eval_shape(model.decode_step, params, cache,
                       jax.ShapeDtypeStruct((2, 1), jnp.int32),
                       jax.ShapeDtypeStruct((), jnp.int32))
        print("pallas", "jax.experimental.pallas" in sys.modules)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-2:] == ["pallas", "False"], out.stdout


@pytest.mark.parametrize("shard, b, nc, chunk, blocks", [
    (1, 12, 1, 128, 1),      # mamba2's cell: all 64 heads in one block
    (1, 4, 16, 256, 16),     # 4,096-token prompts: 4 heads a block
    (4, 4, 16, 256, 16),     # ... a multiple of the sharded slice
    (1, 64, 64, 256, 64),    # tiles past the budget: one head a block
])
def test_prefill_head_blocks_follow_the_tile_bytes(shard, b, nc, chunk,
                                                  blocks):
    assert ssm._prefill_head_blocks(64, shard, b, nc, chunk) == blocks
