"""RG-LRU recurrent block (RecurrentGemma): conv1d + real-gated linear
recurrent unit, with associative-scan training path and O(1) decode.

    r_t = sigmoid(blockdiag(W_r) x_t)          recurrence gate
    i_t = sigmoid(blockdiag(W_i) x_t)          input gate
    a_t = exp(-c * softplus(Lambda) * r_t)     per-channel decay, c = 8
    h_t = a_t h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from .layers import dense_init, dtype_of, pdtype_of, scope

RGLRU_C = 8.0
N_GATE_BLOCKS = 8


def _width(cfg: ModelConfig) -> int:
    return cfg.lru_width or cfg.d_model


def rglru_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    pd = pdtype_of(cfg)
    w = _width(cfg)
    bs = w // N_GATE_BLOCKS
    # Lambda init so decay a ~ U(0.9, 0.999) at r=0.5
    lam = jax.random.uniform(ks[4], (w,), minval=2.0, maxval=6.0)
    return {
        "wx": dense_init(ks[0], cfg.d_model, w, pd),      # x branch
        "wy": dense_init(ks[1], cfg.d_model, w, pd),      # gate branch
        "conv_w": (jax.random.normal(ks[5], (4, w)) * 0.1).astype(pd),
        "conv_b": jnp.zeros((w,), pd),
        "w_gates": (jax.random.normal(ks[2], (2, N_GATE_BLOCKS, bs, bs))
                    * bs ** -0.5).astype(pd),
        "b_gates": jnp.zeros((2, w), pd),
        "lam": lam.astype(jnp.float32),
        "w_out": dense_init(ks[3], w, cfg.d_model, pd,
                            scale=cfg.residual_scale),
    }


def _gates(p, x):
    """x: (..., W) -> (r, i) each (..., W) via block-diagonal projections."""
    shp = x.shape
    w = shp[-1]
    bs = w // N_GATE_BLOCKS
    xb = x.reshape(*shp[:-1], N_GATE_BLOCKS, bs)
    g = jnp.einsum("...nb,gnbc->g...nc", xb.astype(jnp.float32),
                   p["w_gates"].astype(jnp.float32))
    g = g.reshape(2, *shp[:-1], w) + p["b_gates"].astype(
        jnp.float32).reshape(2, *([1] * (len(shp) - 1)), w)
    r, i = jax.nn.sigmoid(g[0]), jax.nn.sigmoid(g[1])
    return r, i


def _decay(p, r):
    return jnp.exp(-RGLRU_C * jax.nn.softplus(p["lam"]) * r)


def _conv(x, w, b):
    pad = jnp.pad(x, ((0, 0), (w.shape[0] - 1, 0), (0, 0)))
    s = x.shape[1]
    out = sum(pad[:, j:j + s, :] * w[j][None, None, :]
              for j in range(w.shape[0]))
    return out + b[None, None, :]


def rglru_apply(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (B, S, D)."""
    dt = dtype_of(cfg)
    xb = x @ p["wx"].astype(dt)                    # (B, S, W)
    gate = jax.nn.gelu(x @ p["wy"].astype(dt))
    xb = _conv(xb, p["conv_w"].astype(dt), p["conv_b"].astype(dt))
    r, i = _gates(p, xb)
    a = _decay(p, r)                               # (B, S, W)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    u = beta * i * xb.astype(jnp.float32)

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, u), axis=1)
    h = constrain(h.astype(dt), ("batch", "seq", "ffn"))
    out = (h * gate) @ p["w_out"].astype(dt)
    return constrain(out, ("batch", "seq", "embed"))


def init_rglru_cache(cfg: ModelConfig, batch: int) -> Dict:
    w = _width(cfg)
    return {
        "h": jnp.zeros((batch, w), jnp.float32),
        "conv": jnp.zeros((batch, 3, w), dtype_of(cfg)),
    }


def rglru_decode(p, x, cache: Dict, pos, cfg: ModelConfig
                 ) -> Tuple[jax.Array, Dict]:
    dt = dtype_of(cfg)
    xb = (x @ p["wx"].astype(dt))[:, 0, :]         # (B, W)
    gate = jax.nn.gelu(x @ p["wy"].astype(dt))[:, 0, :]
    with scope("cache"):
        hist = jnp.concatenate([cache["conv"], xb[:, None, :]], axis=1)
        new_conv = hist[:, 1:, :]
    conv = jnp.einsum("bwc,wc->bc", hist, p["conv_w"].astype(dt)) \
        + p["conv_b"].astype(dt)
    r, i = _gates(p, conv)
    a = _decay(p, r)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    with scope("cache"):
        h = a * cache["h"] + beta * i * conv.astype(jnp.float32)
    out = ((h.astype(dt) * gate) @ p["w_out"].astype(dt))[:, None, :]
    return out, {"h": h, "conv": new_conv}
