"""LanguageModel.prefill's two paths: one sequence-parallel pass for models
made of attention, latent-attention and expert blocks, and
``_prefill_tokens``, ``decode_step`` over the prompt one token at a time,
which every other model keeps.  From the same prompt and cache both must
give the same last-token logits and the same cache, and decoding on from
either must agree.  For latent attention this also holds the expanded
form (the pass) to the absorbed one (decode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, memory_len
from repro.configs.base import ModelConfig
from repro.models import build
from repro.obs import metrics

BASE = ModelConfig(name="tiny-prefill", family="dense", n_layers=2,
                   d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=128)
LOCAL = BASE.replace(pattern=("local_attn",), window=7)   # an 8-slot ring
# DeepSeek-V3's blocks: MLA prefix layer, then MLA + held experts (4 of
# 8, from id 2, noaux_tc over 4 groups) with a shared expert
DSV3 = get_config("deepseek-v3-671b", smoke=True).replace(
    n_experts=4, n_routed_experts=8, expert_lo=2)
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
    ("mamba2-1.3b", "token"),               # ssm
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
