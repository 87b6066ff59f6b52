"""Multi-head Latent Attention (DeepSeek-V3): keys and values compressed
into a small latent; the cache stores (normed latent, shared rope key)
instead of full K/V.

Per layer, with h the normed input:
  q = W_qb RMSNorm(W_qa h)  -> per head [q_nope (head_dim), q_rope]
  [c, k_rope] = W_dkv h,  c = RMSNorm(c)          (the cached latent)
  k_nope = W_uk c, v = W_uv c                     (per head)
  score = (q_nope . k_nope + q_rope . k_rope) * scale

Two forms of the same attention:
  expanded  (``mla_apply``, ``mla_prefill``): K and V expanded from the
            latents of the whole sequence (the shared rope key broadcast
            to every head); causal attention in blocks of queries and
            keys with an online softmax, so that at most one block pair
            of scores is live.
  absorbed  (``mla_decode``): q_nope W_uk^T is a query in latent space,
            scored against the latent cache with the rope keys; the
            output is formed in latent space and then goes through W_uv
            and W_o.  The cache is never expanded.
Each traced layer program counts its form in
``repro_jax_mla_lowerings_total{form}``.

Rotary embedding on the rope dimensions as the release's modelling code
applies it: each (even, odd) pair de-interleaved to [evens, odds], then
rotate-half, with YaRN frequencies; the softmax scale is (head_dim +
rope_head_dim)^-1/2 times mscale(factor)^2, mscale(f) = 0.1 ln f + 1.
(The release's mscale and mscale_all_dim are both 1, so its cos/sin
factor mscale / mscale_all_dim is 1.)
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from ..obs import metrics
from .attention import NEG_INF
from .layers import (apply_rope, dense_init, dtype_of, pdtype_of, rmsnorm,
                     scope, yarn_mscale)

MLA_FORMS = ("expanded", "absorbed")

#: bytes of float32 scores of one (query block, key block) pair, over the
#: batch and the heads, that the expanded form may hold at once
SCORE_BLOCK_BYTES = 1 << 27
#: queries per block where all the scores do not fit at once
QUERY_BLOCK = 128


def _mla_lowerings(form: str) -> metrics.Counter:
    return metrics.counter("repro_jax_mla_lowerings_total",
                           "latent-attention layer programs traced, by "
                           "form: keys and values expanded from the "
                           "latents, or the query absorbed into latent "
                           "space",
                           form=form)


for _form in MLA_FORMS:
    _mla_lowerings(_form)


def mla_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    pd = pdtype_of(cfg)
    hd, rd, vd = cfg.head_dim, cfg.rope_head_dim, cfg.value_dim
    p = {
        "w_dkv": dense_init(ks[0], cfg.d_model, cfg.kv_lora_rank + rd, pd),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), pd),
        "w_uk": dense_init(ks[1], cfg.kv_lora_rank, cfg.n_heads * hd, pd),
        "w_uv": dense_init(ks[2], cfg.kv_lora_rank, cfg.n_heads * vd, pd),
        "wo": dense_init(ks[3], cfg.n_heads * vd, cfg.d_model, pd,
                         scale=cfg.residual_scale),
    }
    if cfg.q_lora_rank > 0:
        p["w_qa"] = dense_init(ks[4], cfg.d_model, cfg.q_lora_rank, pd)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), pd)
        p["w_qb"] = dense_init(ks[5], cfg.q_lora_rank,
                               cfg.n_heads * (hd + rd), pd)
    else:
        p["wq"] = dense_init(ks[4], cfg.d_model, cfg.n_heads * (hd + rd), pd)
    return p


def softmax_scale(cfg: ModelConfig) -> float:
    scale = (cfg.head_dim + cfg.rope_head_dim) ** -0.5
    if cfg.yarn_factor > 0:
        scale *= yarn_mscale(cfg.yarn_factor) ** 2
    return scale


def _rope(x, positions, cfg: ModelConfig):
    """Rotary embedding of (B, S, H, rope_head_dim) in the release's
    convention (see the module docstring)."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    yarn = ((cfg.yarn_factor, cfg.yarn_original_max_pos)
            if cfg.yarn_factor > 0 else None)
    return apply_rope(x, positions, cfg.rope_theta, yarn=yarn)


def _queries(p, x, cfg: ModelConfig, positions):
    dt = dtype_of(cfg)
    b, s, _ = x.shape
    hd, rd = cfg.head_dim, cfg.rope_head_dim
    if "w_qa" in p:
        qc = rmsnorm(x @ p["w_qa"].astype(dt), p["q_norm"], cfg.norm_eps)
        q = qc @ p["w_qb"].astype(dt)
    else:
        q = x @ p["wq"].astype(dt)
    q = q.reshape(b, s, cfg.n_heads, hd + rd)
    return q[..., :hd], _rope(q[..., hd:], positions, cfg)


def _latent_kv(p, x, cfg: ModelConfig, positions):
    """The normed latent (B, S, kv_lora_rank) and the roped shared key
    (B, S, rope_head_dim): what the cache holds."""
    dt = dtype_of(cfg)
    ckv = x @ p["w_dkv"].astype(dt)
    latent = rmsnorm(ckv[..., :cfg.kv_lora_rank], p["kv_norm"],
                     cfg.norm_eps)
    k_rope = _rope(ckv[..., None, cfg.kv_lora_rank:], positions, cfg)
    return latent, k_rope[:, :, 0, :]


def _score_blocks(b: int, h: int, s: int) -> Tuple[int, int]:
    """(queries, keys) per block: all of them where their float32 scores
    fit ``SCORE_BLOCK_BYTES``, else ``QUERY_BLOCK`` queries against the
    largest power of two of keys, from 128 up, that fits."""
    if b * h * s * s * 4 <= SCORE_BLOCK_BYTES:
        return s, s
    bk = 128
    while b * h * QUERY_BLOCK * 2 * bk * 4 <= SCORE_BLOCK_BYTES and 2 * bk < s:
        bk *= 2
    return QUERY_BLOCK, bk


def _visit(carry, q, k, v, scale, q0, k0):
    """One (query block, key block) pair into the running softmax: carry
    is the row max, row sum and unnormalised output, float32; q (B, bq,
    H, dq), k (B, bk, H, dq), v (B, bk, H, vd) in the model's dtype, the
    products accumulated in float32."""
    m, l, acc = carry
    f32 = jnp.float32
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=f32) * scale
    q_ids = q0 + jnp.arange(q.shape[1])[:, None]
    k_ids = k0 + jnp.arange(k.shape[1])[None, :]
    s = jnp.where((k_ids <= q_ids)[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    corr = jnp.exp(m - m_new)
    pbar = jnp.exp(s - m_new[..., None])
    l = l * corr + jnp.sum(pbar, axis=-1)
    acc = acc * corr[..., None] + jnp.einsum(
        "bhqk,bkhv->bhqv", pbar.astype(v.dtype), v,
        preferred_element_type=f32)
    return m_new, l, acc


def _causal_attention(q, k, v, scale):
    """Causal attention of positions 0..S-1 over themselves: q, k (B, S,
    H, dq), v (B, S, H, vd) -> (B, S, H, vd).  Blocks of queries
    (``_score_blocks``) scan over the key blocks that reach no later than
    their last query; later ones are skipped."""
    b, s, h, _ = q.shape
    vd = v.shape[-1]
    bq, bk = _score_blocks(b, h, s)

    def init(rows):
        return (jnp.full((b, h, rows), NEG_INF, jnp.float32),
                jnp.zeros((b, h, rows), jnp.float32),
                jnp.zeros((b, h, rows, vd), jnp.float32))

    if bq >= s:
        _, l, acc = _visit(init(s), q, k, v, scale, 0, 0)
        o = acc / l[..., None]
        return o.transpose(0, 2, 1, 3).astype(v.dtype)

    nk = -(-s // bk)
    nq = nk * bk // bq
    # padded keys lie after every real query, so the causal mask drops them
    pad = nk * bk - s

    def blocks(x, n, size):
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        return x.reshape((b, n, size) + x.shape[2:]).swapaxes(0, 1)

    qs = blocks(q, nq, bq)
    ks, vs = blocks(k, nk, bk), blocks(v, nk, bk)

    def q_step(_, xs):
        i, q_i = xs

        def k_step(carry, ys):
            j, k_j, v_j = ys
            carry = jax.lax.cond(
                j * bk <= i * bq + bq - 1,
                lambda c: _visit(c, q_i, k_j, v_j, scale, i * bq, j * bk),
                lambda c: c, carry)
            return carry, None

        (_, l, acc), _ = jax.lax.scan(k_step, init(bq),
                                      (jnp.arange(nk), ks, vs))
        return None, (acc / l[..., None]).astype(v.dtype)

    _, o = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    # (nq, B, H, bq, vd) -> (B, S, H, vd)
    return o.transpose(1, 0, 3, 2, 4).reshape(b, nq * bq, h, vd)[:, :s]


def _expanded(p, x, cfg: ModelConfig):
    """Expanded-form attention of a whole sequence at positions 0..S-1:
    (out (B, S, D), latent, k_rope)."""
    _mla_lowerings("expanded").inc()
    dt = dtype_of(cfg)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q_nope, q_rope = _queries(p, x, cfg, positions)
    latent, k_rope = _latent_kv(p, x, cfg, positions)
    latent = constrain(latent, ("batch", "seq", None))
    h = cfg.n_heads
    k_nope = (latent @ p["w_uk"].astype(dt)).reshape(b, s, h, cfg.head_dim)
    v = (latent @ p["w_uv"].astype(dt)).reshape(b, s, h, cfg.value_dim)
    # one product over both parts of each head: [nope, rope] . [nope, rope]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], (b, s, h, k_rope.shape[-1]))], axis=-1)
    o = _causal_attention(q, k, v, softmax_scale(cfg))
    o = o.reshape(b, s, cfg.n_heads * cfg.value_dim)
    return o @ p["wo"].astype(dt), latent, k_rope


def mla_apply(p, x, cfg: ModelConfig):
    """Causal self-attention of a sequence (training forward)."""
    return _expanded(p, x, cfg)[0]


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    dt = dtype_of(cfg)
    return {
        "latent": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_len, cfg.rope_head_dim), dt),
    }


def mla_prefill(p, x, cache: Dict, cfg: ModelConfig
                ) -> Tuple[jax.Array, Dict]:
    """A whole prompt at positions 0..S-1 in the expanded form, its S
    latents and rope keys written to the cache in one store; slots at or
    beyond S keep what ``cache`` held."""
    s, size = x.shape[1], cache["latent"].shape[1]
    if s > size:
        raise ValueError(f"a {s}-token prompt does not fit a {size}-slot "
                         f"latent cache")
    out, latent, k_rope = _expanded(p, x, cfg)
    with scope("cache"):
        new = {"latent": jax.lax.dynamic_update_slice_in_dim(
                   cache["latent"], latent.astype(cache["latent"].dtype), 0,
                   axis=1),
               "k_rope": jax.lax.dynamic_update_slice_in_dim(
                   cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), 0,
                   axis=1)}
    return out, new


def mla_decode(p, x, cache: Dict, pos, cfg: ModelConfig
               ) -> Tuple[jax.Array, Dict]:
    """One token at ``pos`` in the absorbed form; returns (out, new
    cache)."""
    _mla_lowerings("absorbed").inc()
    dt, f32 = dtype_of(cfg), jnp.float32
    b = x.shape[0]
    h, hd, vd, r = (cfg.n_heads, cfg.head_dim, cfg.value_dim,
                    cfg.kv_lora_rank)
    posv = jnp.full((b, 1), pos)
    q_nope, q_rope = _queries(p, x, cfg, posv)
    lat_new, kr_new = _latent_kv(p, x, cfg, posv)
    with scope("cache"):
        latent = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], lat_new.astype(cache["latent"].dtype), pos,
            axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos,
            axis=1)
    # the query in latent space: q_nope W_uk^T, per head
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(f32),
                       p["w_uk"].reshape(r, h, hd).astype(f32))
    s = (jnp.einsum("bhr,bsr->bhs", q_lat, latent.astype(f32))
         + jnp.einsum("bhe,bse->bhs", q_rope[:, 0].astype(f32),
                      k_rope.astype(f32))) * softmax_scale(cfg)
    valid = jnp.arange(latent.shape[1]) <= pos
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    pbar = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pbar, latent.astype(f32))
    o = jnp.einsum("bhr,rhv->bhv", o_lat,
                   p["w_uv"].reshape(r, h, vd).astype(f32))
    out = o.reshape(b, 1, h * vd).astype(dt) @ p["wo"].astype(dt)
    return out, {"latent": latent, "k_rope": k_rope}
