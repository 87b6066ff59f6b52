"""Serving: prefill + batched one-token decode steps (the functions the
decode_32k / long_500k dry-run cells lower), plus a simple batched
request loop for the serving example."""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..distributed import sharding
from ..models import LanguageModel


def make_prefill(model: LanguageModel) -> Callable:
    """prefill(params, tokens[, memory_embeds]) -> last-token logits.

    Lowered for the prefill_* cells: the dominant prefill compute is the
    full forward; per-layer cache population adds stores the roofline
    memory term already covers (DESIGN.md §4)."""

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
    compiles_after_first_step: int   # lowerings once the decode step ran


_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def greedy_generate(model: LanguageModel, params, prompt, *, max_new: int,
                    max_len: Optional[int] = None,
                    memory_embeds=None) -> Generation:
    """Batched greedy decoding driver (example/serving path).

    Under ``sharding.use_mesh`` the cache is placed by
    ``cache_specs_tree``.  Every jitted lowering after the first decode
    step is counted: a steady decode loop compiles nothing."""
    b, s = prompt.shape
    max_len = max_len or (s + max_new)
    cache = model.init_cache(b, max_len)
    mesh = sharding.active_mesh()
    if mesh is not None:
        cache = jax.device_put(cache, sharding.tree_shardings(
            mesh, sharding.cache_specs_tree(cache, mesh=mesh)))
    # prefill fills the cache through position s-1 and returns the
    # last-token logits
    logits, cache = model.prefill(params, prompt, cache,
                                  memory_embeds=memory_embeds)
    step = jax.jit(model.decode_step)

    lowerings, armed = [], [False]

    def on_event(event, _secs, **_kw):
        if armed[0] and event == _LOWERING_EVENT:
            lowerings.append(event)

    toks, outs = [], []
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        for i in range(max_new):
            armed[0] = i > 0    # the first decode step has compiled
            outs.append(logits)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            toks.append(nxt)
            if i + 1 < max_new:
                logits, cache = step(params, cache, nxt, jnp.int32(s + i),
                                     memory_embeds=memory_embeds)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    return Generation(jnp.concatenate(toks, axis=1), jnp.stack(outs, axis=1),
                      len(lowerings))
