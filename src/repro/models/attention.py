"""Attention variants: GQA (full / sliding-window / chunked), decode-with-
cache, and cross-attention.  Sharding-friendly einsum formulation."""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from .layers import (apply_rope, dense_init, dtype_of, pdtype_of, scope,
                     softcap)

NEG_INF = -1e30


def attn_init(key, cfg: ModelConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    pd = pdtype_of(cfg)
    hd = cfg.head_dim
    return {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * hd, pd),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * hd, pd),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * hd, pd),
        "wo": dense_init(k4, cfg.n_heads * hd, cfg.d_model, pd,
                         scale=cfg.residual_scale),
    }


def _split_heads(x, n_heads, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, hd)


def _mask(sq: int, skv: int, *, causal: bool, window: int,
          q_offset: int = 0):
    q_ids = q_offset + jnp.arange(sq)[:, None]
    k_ids = jnp.arange(skv)[None, :]
    m = jnp.ones((sq, skv), dtype=bool)
    if causal:
        m &= k_ids <= q_ids
    if window > 0:
        m &= k_ids >= q_ids - window
    return m


def _sdpa(q, k, v, *, scale: float, causal: bool, window: int,
          logit_cap: float, q_offset: int = 0):
    """q: (B,Sq,H,hd); k,v: (B,Skv,Hkv,hd) -> (B,Sq,H,hd)."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    group = h // hkv
    qg = q.reshape(b, sq, hkv, group, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = softcap(s, logit_cap)
    mask = _mask(sq, k.shape[1], causal=causal, window=window,
                 q_offset=q_offset)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, hd).astype(q.dtype)


def _sdpa_chunked(q, k, v, *, scale: float, causal: bool, window: int,
                  logit_cap: float, chunk: int, unroll: bool = False):
    """Flash-in-XLA: scan over query chunks; never materializes (Sq, Skv)
    for all queries at once.  Memory per step: (B,H,chunk,Skv).

    unroll=True inlines the chunk loop (dry-run accounting: XLA
    cost_analysis counts while-loop bodies once)."""
    b, sq, h, hd = q.shape
    assert sq % chunk == 0, (sq, chunk)
    nq = sq // chunk
    qc = q.reshape(b, nq, chunk, h, hd).transpose(1, 0, 2, 3, 4)

    if unroll:
        outs = [
            _sdpa(qc[i], k, v, scale=scale, causal=causal, window=window,
                  logit_cap=logit_cap, q_offset=i * chunk)
            for i in range(nq)
        ]
        out = jnp.stack(outs, axis=0)
        return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)

    def step(carry, inp):
        i, qi = inp
        o = _sdpa(qi, k, v, scale=scale, causal=causal, window=window,
                  logit_cap=logit_cap, q_offset=i * chunk)
        return carry, o

    _, outs = jax.lax.scan(step, 0, (jnp.arange(nq), qc))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def _project_qkv(p, x, cfg: ModelConfig, *, positions=None,
                 kv_override: Optional[Tuple] = None):
    """q, k, v of a sequence: (B, S, H, hd) and (B, Skv, Hkv, hd), q and k
    roped at ``positions`` (default 0..S-1) unless the keys and values
    come from ``kv_override`` (cross-attention)."""
    dt = dtype_of(cfg)
    s = x.shape[1]
    hd = cfg.head_dim
    q = _split_heads(x @ p["wq"].astype(dt), cfg.n_heads, hd)
    if kv_override is None:
        k = _split_heads(x @ p["wk"].astype(dt), cfg.n_kv_heads, hd)
        v = _split_heads(x @ p["wv"].astype(dt), cfg.n_kv_heads, hd)
    else:
        src = kv_override
        k = _split_heads(src @ p["wk"].astype(dt), cfg.n_kv_heads, hd)
        v = _split_heads(src @ p["wv"].astype(dt), cfg.n_kv_heads, hd)
    if positions is None:
        positions = jnp.arange(s)
    if kv_override is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions if k.shape[1] == s
                       else jnp.arange(k.shape[1]), cfg.rope_theta)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "heads", None))
    return q, k, v


def _attend(p, q, k, v, cfg: ModelConfig, *, causal: bool, window: int):
    """Attention of q over k, v and the output projection: (B, S, D).
    Query chunks of ``cfg.attn_chunk`` when it divides a longer sequence,
    else all the scores at once."""
    dt = dtype_of(cfg)
    b, s = q.shape[:2]
    hd = cfg.head_dim
    scale = hd ** -0.5

    if cfg.attn_chunk > 0 and s > cfg.attn_chunk and s % cfg.attn_chunk == 0:
        o = _sdpa_chunked(q, k, v, scale=scale, causal=causal, window=window,
                          logit_cap=cfg.attn_logit_softcap,
                          chunk=cfg.attn_chunk,
                          unroll=cfg.attn_chunk_unroll)
    else:
        o = _sdpa(q, k, v, scale=scale, causal=causal, window=window,
                  logit_cap=cfg.attn_logit_softcap)
    o = constrain(o, ("batch", "seq", "heads", None))
    out = o.reshape(b, s, cfg.n_heads * hd) @ p["wo"].astype(dt)
    return constrain(out, ("batch", "seq", "embed"))


def attn_apply(p, x, cfg: ModelConfig, *, positions=None,
               causal: bool = True, window: int = 0,
               kv_override: Optional[Tuple] = None):
    """Training/prefill attention.  kv_override supplies encoder KV for
    cross-attention (k_in, v_in already projected inputs)."""
    q, k, v = _project_qkv(p, x, cfg, positions=positions,
                           kv_override=kv_override)
    return _attend(p, q, k, v, cfg, causal=causal, window=window)


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  *, window: int = 0) -> Dict:
    """Linear cache for full attention; ring cache of size `window + 1` for
    SWA (the mask k >= q - window keeps window+1 keys including the current
    token; keeps long_500k SWA decode memory at O(window))."""
    size = min(window + 1, max_len) if window > 0 else max_len
    shape = (batch, size, cfg.n_kv_heads, cfg.head_dim)
    dt = dtype_of(cfg)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def write_kv_prompt(cache: Dict, k, v, *, window: int = 0) -> Dict:
    """Store a prompt's roped keys and values, (B, S, Hkv, hd) for
    positions 0..S-1, under the slot rule of ``decode_attn_apply``:
    position p in slot p, or p % size in a window's ring, so a prompt
    longer than the ring leaves its last ``size`` positions there.  Slots
    at or beyond S keep what ``cache`` held."""
    s = k.shape[1]
    size = cache["k"].shape[1]
    if s > size and not (window > 0 and size == window + 1):
        raise ValueError(f"a {s}-token prompt does not fit a {size}-slot "
                         f"KV cache")
    with scope("cache"):
        if s <= size:
            return {"k": jax.lax.dynamic_update_slice_in_dim(
                        cache["k"], k, 0, axis=1),
                    "v": jax.lax.dynamic_update_slice_in_dim(
                        cache["v"], v, 0, axis=1)}
        # positions s-size..s-1 fill the ring, position p at slot p % size
        return {"k": jnp.roll(k[:, s - size:], s % size, axis=1),
                "v": jnp.roll(v[:, s - size:], s % size, axis=1)}


def prefill_attn_apply(p, x, cache: Dict, cfg: ModelConfig, *,
                       window: int = 0):
    """A whole prompt at positions 0..S-1: causal (windowed) attention as
    ``attn_apply`` computes it, with the keys and values written to the
    cache; returns (out, new_cache)."""
    q, k, v = _project_qkv(p, x, cfg)
    out = _attend(p, q, k, v, cfg, causal=True, window=window)
    return out, write_kv_prompt(cache, k, v, window=window)


def decode_attn_apply(p, x, cache: Dict, pos, cfg: ModelConfig, *,
                      window: int = 0):
    """One-token decode.  x: (B, 1, D); pos: scalar int32 (same for the
    whole batch); returns (out, new_cache)."""
    dt = dtype_of(cfg)
    b = x.shape[0]
    hd = cfg.head_dim
    q = _split_heads(x @ p["wq"].astype(dt), cfg.n_heads, hd)
    k_new = _split_heads(x @ p["wk"].astype(dt), cfg.n_kv_heads, hd)
    v_new = _split_heads(x @ p["wv"].astype(dt), cfg.n_kv_heads, hd)
    posv = jnp.full((b, 1), pos)
    q = apply_rope(q, posv, cfg.rope_theta)
    k_new = apply_rope(k_new, posv, cfg.rope_theta)

    size = cache["k"].shape[1]
    slot = pos % size if window > 0 else pos
    with scope("cache"):
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot,
                                                axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot,
                                                axis=1)

    hkv = cfg.n_kv_heads
    group = cfg.n_heads // hkv
    qg = q.reshape(b, hkv, group, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * hd ** -0.5
    s = softcap(s, cfg.attn_logit_softcap)

    slots = jnp.arange(size)
    if window > 0:
        # ring buffer: slot holds absolute position p iff p = pos - ((slot_now
        # - slot) mod size); valid iff p >= 0 and p > pos - window... all ring
        # entries are within the window by construction once warm.
        age = (slot - slots) % size
        abs_pos = pos - age
        valid = (abs_pos >= 0) & (age < size)
    else:
        valid = slots <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    pbar = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", pbar, v.astype(jnp.float32))
    o = o.reshape(b, 1, cfg.n_heads * hd).astype(dt)
    out = o @ p["wo"].astype(dt)
    return out, {"k": k, "v": v}
