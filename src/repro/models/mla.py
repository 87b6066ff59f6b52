"""Multi-head Latent Attention (DeepSeek-V3): KV compressed into a small
latent; cache stores (latent, shared rope-key) instead of full K/V."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from .attention import NEG_INF
from .layers import apply_rope, dense_init, dtype_of, pdtype_of, scope


def mla_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    pd = pdtype_of(cfg)
    hd, rd = cfg.head_dim, cfg.rope_head_dim
    p = {
        "w_dkv": dense_init(ks[0], cfg.d_model, cfg.kv_lora_rank + rd, pd),
        "w_uk": dense_init(ks[1], cfg.kv_lora_rank, cfg.n_heads * hd, pd),
        "w_uv": dense_init(ks[2], cfg.kv_lora_rank, cfg.n_heads * hd, pd),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, pd,
                         scale=cfg.residual_scale),
    }
    if cfg.q_lora_rank > 0:
        p["w_qa"] = dense_init(ks[4], cfg.d_model, cfg.q_lora_rank, pd)
        p["w_qb"] = dense_init(ks[5], cfg.q_lora_rank,
                               cfg.n_heads * (hd + rd), pd)
    else:
        p["wq"] = dense_init(ks[4], cfg.d_model, cfg.n_heads * (hd + rd), pd)
    return p


def _queries(p, x, cfg: ModelConfig, positions):
    dt = dtype_of(cfg)
    b, s, _ = x.shape
    hd, rd = cfg.head_dim, cfg.rope_head_dim
    if "w_qa" in p:
        q = (x @ p["w_qa"].astype(dt)) @ p["w_qb"].astype(dt)
    else:
        q = x @ p["wq"].astype(dt)
    q = q.reshape(b, s, cfg.n_heads, hd + rd)
    q_nope, q_rope = q[..., :hd], q[..., hd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _latent_kv(p, x, cfg: ModelConfig, positions):
    dt = dtype_of(cfg)
    ckv = x @ p["w_dkv"].astype(dt)
    latent, k_rope = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    return latent, k_rope


def _attend(q_nope, q_rope, latent, k_rope, p, cfg: ModelConfig, *,
            causal: bool, q_offset: int = 0, valid=None):
    dt = dtype_of(cfg)
    b, sq = q_nope.shape[:2]
    skv = latent.shape[1]
    hd = cfg.head_dim
    k = (latent @ p["w_uk"].astype(dt)).reshape(b, skv, cfg.n_heads, hd)
    v = (latent @ p["w_uv"].astype(dt)).reshape(b, skv, cfg.n_heads, hd)
    scale = (hd + cfg.rope_head_dim) ** -0.5
    s = (jnp.einsum("bqhd,bshd->bhqs", q_nope.astype(jnp.float32),
                    k.astype(jnp.float32))
         + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                      k_rope.astype(jnp.float32))) * scale
    if causal:
        q_ids = q_offset + jnp.arange(sq)[:, None]
        k_ids = jnp.arange(skv)[None, :]
        s = jnp.where((k_ids <= q_ids)[None, None], s, NEG_INF)
    if valid is not None:
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    pbar = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bshd->bqhd", pbar, v.astype(jnp.float32))
    return o.reshape(b, sq, cfg.n_heads * hd).astype(dt)


def mla_apply(p, x, cfg: ModelConfig, *, positions=None):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q_nope, q_rope = _queries(p, x, cfg, positions)
    latent, k_rope = _latent_kv(p, x, cfg, positions)
    latent = constrain(latent, ("batch", "seq", None))
    o = _attend(q_nope, q_rope, latent, k_rope, p, cfg, causal=True)
    return o @ p["wo"].astype(dtype_of(cfg))


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict:
    dt = dtype_of(cfg)
    return {
        "latent": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_len, cfg.rope_head_dim), dt),
    }


def mla_decode(p, x, cache: Dict, pos, cfg: ModelConfig
               ) -> Tuple[jax.Array, Dict]:
    b = x.shape[0]
    posv = jnp.full((b, 1), pos)
    q_nope, q_rope = _queries(p, x, cfg, posv)
    lat_new, kr_new = _latent_kv(p, x, cfg, posv)
    with scope("cache"):
        latent = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], lat_new, pos, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], kr_new, pos, axis=1)
    valid = jnp.arange(latent.shape[1]) <= pos
    o = _attend(q_nope, q_rope, latent, k_rope, p, cfg, causal=False,
                valid=valid)
    out = o @ p["wo"].astype(dtype_of(cfg))
    return out, {"latent": latent, "k_rope": k_rope}
