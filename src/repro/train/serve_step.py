"""Serving: prefill + batched one-token decode steps (the functions the
decode_32k / long_500k dry-run cells lower), plus a simple batched
request loop for the serving example."""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..distributed import sharding
from ..launch import compile_cache
from ..models import LanguageModel
from ..obs import metrics


def make_prefill(model: LanguageModel) -> Callable:
    """prefill(params, tokens[, memory_embeds]) -> last-token logits.

    Lowered for the prefill_* dry-run cells: the full forward, with no
    cache.  Serving fills its cache with ``LanguageModel.prefill``, which
    for attention models is the same sequence-parallel pass plus one
    store of each layer's keys and values."""

    def prefill(params, tokens, memory_embeds=None):
        logits, _ = model.forward(params, tokens,
                                  memory_embeds=memory_embeds)
        return logits[:, -1, :]

    return prefill


def make_serve_step(model: LanguageModel) -> Callable:
    """serve_step(params, cache, tokens (B,1), pos) -> (logits, cache).
    One new token against a KV cache of seq_len (decode cells)."""

    def serve_step(params, cache, tokens, pos, memory_embeds=None):
        return model.decode_step(params, cache, tokens, pos,
                                 memory_embeds=memory_embeds)

    return serve_step


class Generation(NamedTuple):
    tokens: jax.Array     # (B, max_new) int32
    logits: jax.Array     # (B, max_new, V); tokens[:, i] = argmax logits[:, i]
    # lowerings once the decode step ran; None with the metrics switched off
    compiles_after_first_step: Optional[int]


def greedy_generate(model: LanguageModel, params, prompt, *, max_new: int,
                    max_len: Optional[int] = None,
                    memory_embeds=None) -> Generation:
    """Batched greedy decoding driver (example/serving path).

    Under ``sharding.use_mesh`` the cache is placed by
    ``cache_specs_tree`` and kept there: the prefill's cache is put back
    in that layout and every decode step returns it in it, so the loop's
    input layouts never change.  Every jitted lowering after the first
    decode step is counted (``repro_jax_lowerings_total``): a steady
    decode loop compiles nothing."""
    b, s = prompt.shape
    max_len = max_len or (s + max_new)
    cache = model.init_cache(b, max_len)
    mesh = sharding.active_mesh()
    if mesh is not None:
        layout = sharding.tree_shardings(
            mesh, sharding.cache_specs_tree(cache, mesh=mesh))
        cache = jax.device_put(cache, layout)
    # prefill fills the cache through position s-1 and returns the
    # last-token logits
    logits, cache = model.prefill(params, prompt, cache,
                                  memory_embeds=memory_embeds)
    if mesh is None:
        step = jax.jit(model.decode_step)
    else:
        cache = jax.device_put(cache, layout)
        step = jax.jit(model.decode_step,
                       out_shardings=(logits.sharding, layout))
    compile_cache.count_compiles()

    toks, outs, before = [], [], None
    for i in range(max_new):
        if i == 1:      # the first decode step has compiled
            before = compile_cache.lowerings()
        outs.append(logits)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        toks.append(nxt)
        if i + 1 < max_new:
            logits, cache = step(params, cache, nxt, jnp.int32(s + i),
                                 memory_embeds=memory_embeds)
    compiles = (None if not metrics.enabled() else 0 if before is None
                else int(compile_cache.lowerings() - before))
    return Generation(jnp.concatenate(toks, axis=1), jnp.stack(outs, axis=1),
                      compiles)
