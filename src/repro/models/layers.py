"""Shared layers: init helpers, RMSNorm, rotary embeddings, SwiGLU MLP."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain


#: The names of the ``jax.named_scope`` the model puts on its device ops,
#: and the only ones it uses (``scope`` refuses any other).  An op takes
#: the innermost of them on its path: ``embed``; ``layers``, the loop over
#: layer groups (with its slicing of the stacked weights and cache); one
#: per block part, each with its norm and residual add (``attn``, ``mlp``,
#: ``ssm``, ``moe``, ``rglru``, ``cross``); ``cache``, the ops that write
#: a block's carried decode state; ``head``, the final norm and logits.
#: The chip benchmark's per-layer device times read this list, so a name
#: changed here changes what they measure.
SCOPES = ("embed", "layers", "attn", "mlp", "ssm", "moe", "rglru", "cross",
          "cache", "head")


def scope(name: str):
    """``jax.named_scope(name)`` for one of ``SCOPES``: metadata on the
    ops traced inside it, with no effect on what they compute."""
    if name not in SCOPES:
        raise ValueError(f"{name!r} is not one of {SCOPES}")
    return jax.named_scope(name)


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def pdtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale * (d_in ** -0.5)
    return (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


def rmsnorm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

#: YaRN's correction range, in turns over the original context: DeepSeek-V3's
#: rope_scaling beta_fast and beta_slow, the only values in use
YARN_BETA_FAST = 32.0
YARN_BETA_SLOW = 1.0


def rope_freqs(head_dim: int, theta: float, yarn=None):
    """Inverse frequencies theta^(-2i/d); with ``yarn`` (factor, original
    max positions), YaRN's: the frequencies that turn fewer than
    YARN_BETA_SLOW times over the original context divided by the factor,
    those that turn more than YARN_BETA_FAST times kept, and a linear ramp
    between."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))
    if yarn is None:
        return freqs
    factor, original = yarn

    def dim_of(rotations):
        return (head_dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(YARN_BETA_FAST)), 0)
    high = min(math.ceil(dim_of(YARN_BETA_SLOW)), head_dim - 1)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def yarn_mscale(factor: float) -> float:
    """YaRN's attention temperature term, 0.1 ln(factor) + 1 (mscale 1)."""
    return 1.0 if factor <= 1 else 0.1 * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float, *, yarn=None):
    """x: (B, S, H, D_even); positions: (B, S) or (S,).  Rotate-half form;
    ``yarn`` as in ``rope_freqs``."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, yarn)                 # (d/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, d/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, width: Optional[int] = None):
    width = width or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    pd = pdtype_of(cfg)
    return {
        "wg": dense_init(k1, cfg.d_model, width, pd),
        "wu": dense_init(k2, cfg.d_model, width, pd),
        "wd": dense_init(k3, width, cfg.d_model, pd,
                         scale=cfg.residual_scale),
    }


def mlp_apply(p, x, cfg: ModelConfig):
    dt = dtype_of(cfg)
    h = jax.nn.silu(x @ p["wg"].astype(dt)) * (x @ p["wu"].astype(dt))
    h = constrain(h, ("batch", "seq", "ffn"))
    out = h @ p["wd"].astype(dt)
    return constrain(out, ("batch", "seq", "embed"))


def softcap(x, cap: float):
    if cap <= 0:
        return x
    return cap * jnp.tanh(x / cap)
