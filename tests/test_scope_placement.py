"""Where the compiled programs put their ops: each op's ``op_name``
bucketed by ``benchmarks/chip/scopes.py``, as the chip benchmark's
per-layer device times bucket them (``profile_scopes.py``).

For a smoke config of each block kind, the sequence prefill (or
``forward``, where the kind prefills one token at a time) and the decode
step are compiled on the CPU, and:

* every dot_general falls under a block scope, ``embed`` or ``head``:
  never under bare ``layers`` or outside every scope;
* the dot_generals fall under exactly the kind's mixer and FFN scopes
  (and ``head``): each mixer's projections under its own scope;
* a call that fills the cache writes it under ``cache``, and every
  dynamic_update_slice inside a block is a cache write.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import scopes
from repro.configs import get_config, memory_len
from repro.models import build

BLOCK_SCOPES = {"attn", "mlp", "ssm", "moe", "rglru", "cross"}
B, S = 2, 8

#: arch -> (the call that takes the prompt, the scopes its dot_generals
#: fall under)
KINDS = {
    "h2o-danube-1.8b": ("prefill", {"attn", "mlp", "head"}),
    "deepseek-v3-671b": ("prefill", {"attn", "mlp", "moe", "head"}),
    "mamba2-1.3b": ("prefill", {"ssm", "head"}),
    "recurrentgemma-9b": ("forward", {"rglru", "attn", "mlp", "head"}),
    "whisper-tiny": ("forward", {"attn", "cross", "mlp", "head"}),
}


@functools.lru_cache(maxsize=None)
def compiled(arch, call):
    """Instruction -> op_name of the call's compiled program."""
    cfg = get_config(arch, smoke=True)
    model = build(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(B, S + 4))
    mlen = memory_len(cfg, S)
    mem = (None if mlen is None
           else jax.ShapeDtypeStruct((B, mlen, cfg.d_model), jnp.float32))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if call == "decode_step":
        fn = lambda p, c, t, pos, m: model.decode_step(  # noqa: E731
            p, c, t, pos, memory_embeds=m)
        args = (params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32), mem)
    elif call == "prefill":
        assert mem is None
        fn, args = model.prefill, (params, tokens, cache)
    else:
        fn = lambda p, t, m: model.forward(  # noqa: E731
            p, t, memory_embeds=m)
        args = (params, tokens, mem)
    return scopes.op_names(jax.jit(fn).lower(*args).compile().as_text())


def ops_of(arch, call, primitive):
    """The op_names of the instructions that carry ``primitive``'s."""
    return [op for op in compiled(arch, call).values()
            if op and op.split("/")[-1] == primitive]


CASES = [(arch, call) for arch, (first, _) in KINDS.items()
         for call in (first, "decode_step")]


@pytest.mark.parametrize("arch, call", CASES)
def test_every_dot_in_a_block_embed_or_head(arch, call):
    dots = ops_of(arch, call, "dot_general")
    assert dots
    outside = [op for op in dots if scopes.bucket(op)
               not in BLOCK_SCOPES | {"embed", "head"}]
    assert not outside, outside


@pytest.mark.parametrize("arch, call", CASES)
def test_projections_under_their_own_scopes(arch, call):
    want = KINDS[arch][1]
    got = {scopes.bucket(op) for op in ops_of(arch, call, "dot_general")}
    assert got == want


@pytest.mark.parametrize("arch, call", [c for c in CASES
                                        if c[1] != "forward"])
def test_cache_writes_under_cache(arch, call):
    buckets = [scopes.bucket(op) for op in compiled(arch, call).values()
               if op]
    assert "cache" in buckets
    in_blocks = [op for op in ops_of(arch, call, "dynamic_update_slice")
                 if BLOCK_SCOPES & set(op.split("/"))]
    assert all(scopes.bucket(op) == "cache" for op in in_blocks), in_blocks
