"""Block registry: per-kind (init, apply, cache_init/spec, decode).

Every block owns its norms and residual adds.  Kinds:
  attn        full causal GQA attention + SwiGLU MLP
  local_attn  sliding-window GQA attention + MLP
  moe         (MLA or GQA) attention + MoE FFN (returns aux loss)
  ssm         Mamba2 mixer (no MLP; the block IS the mixer)
  rglru       RG-LRU recurrent mixer + MLP
  cross_attn  self-attn + cross-attn(memory) + MLP (whisper dec / vlm)
  enc_attn    bidirectional attention + MLP (whisper encoder)
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .layers import dtype_of, mlp_apply, mlp_init, rmsnorm


def _no_aux():
    """Zero auxiliary loss (built per call: a module-level array would
    start a JAX backend at import)."""
    return jnp.zeros((), jnp.float32)


def _norm_init(cfg):
    return jnp.ones((cfg.d_model,), jnp.dtype(cfg.param_dtype))


# --------------------------------------------------------------------------
# attn / local_attn
# --------------------------------------------------------------------------

def _attn_block_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = {"ln1": _norm_init(cfg), "attn": attn_mod.attn_init(k1, cfg)}
    if cfg.d_ff > 0:
        p["ln2"] = _norm_init(cfg)
        p["mlp"] = mlp_init(k2, cfg)
    return p


def _attn_block_apply(p, x, cfg: ModelConfig, *, window=0, causal=True,
                      memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    x = x + attn_mod.attn_apply(p["attn"], h, cfg, causal=causal,
                                window=window)
    if "mlp" in p:
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp_apply(p["mlp"], h, cfg)
    return x, _no_aux()


def _attn_cache(cfg, batch, max_len, *, window=0):
    return {"kv": attn_mod.init_kv_cache(cfg, batch, max_len, window=window)}


def _attn_decode(p, x, cache, pos, cfg: ModelConfig, *, window=0,
                 memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    o, kv = attn_mod.decode_attn_apply(p["attn"], h, cache["kv"], pos, cfg,
                                       window=window)
    x = x + o
    if "mlp" in p:
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp_apply(p["mlp"], h, cfg)
    return x, {"kv": kv}


# --------------------------------------------------------------------------
# moe (attention = MLA or GQA, FFN = MoE)
# --------------------------------------------------------------------------

def _moe_block_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    attn_p = (mla_mod.mla_init(k1, cfg) if cfg.use_mla
              else attn_mod.attn_init(k1, cfg))
    return {"ln1": _norm_init(cfg), "attn": attn_p,
            "ln2": _norm_init(cfg), "moe": moe_mod.moe_init(k2, cfg)}


def _moe_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        x = x + mla_mod.mla_apply(p["attn"], h, cfg)
    else:
        x = x + attn_mod.attn_apply(p["attn"], h, cfg, causal=True)
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    y, aux = moe_mod.moe_apply(p["moe"], h, cfg)
    return x + y, aux


def _moe_cache(cfg, batch, max_len, **kw):
    if cfg.use_mla:
        return {"mla": mla_mod.init_mla_cache(cfg, batch, max_len)}
    return {"kv": attn_mod.init_kv_cache(cfg, batch, max_len)}


def _moe_decode(p, x, cache, pos, cfg: ModelConfig, *, memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        o, new = mla_mod.mla_decode(p["attn"], h, cache["mla"], pos, cfg)
        new_cache = {"mla": new}
    else:
        o, new = attn_mod.decode_attn_apply(p["attn"], h, cache["kv"],
                                            pos, cfg)
        new_cache = {"kv": new}
    x = x + o
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    y, _ = moe_mod.moe_apply(p["moe"], h, cfg)
    return x + y, new_cache


# --------------------------------------------------------------------------
# ssm
# --------------------------------------------------------------------------

def _ssm_block_init(key, cfg: ModelConfig):
    return {"ln1": _norm_init(cfg), "ssm": ssm_mod.ssm_init(key, cfg)}


def _ssm_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    return x + ssm_mod.ssm_apply(p["ssm"], h, cfg), _no_aux()


def _ssm_cache(cfg, batch, max_len, **kw):
    return {"ssm": ssm_mod.init_ssm_cache(cfg, batch)}


def _ssm_decode(p, x, cache, pos, cfg: ModelConfig, *, memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    o, new = ssm_mod.ssm_decode(p["ssm"], h, cache["ssm"], pos, cfg)
    return x + o, {"ssm": new}


# --------------------------------------------------------------------------
# rglru
# --------------------------------------------------------------------------

def _rglru_block_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    p = {"ln1": _norm_init(cfg), "lru": rglru_mod.rglru_init(k1, cfg)}
    if cfg.d_ff > 0:
        p["ln2"] = _norm_init(cfg)
        p["mlp"] = mlp_init(k2, cfg)
    return p


def _rglru_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    x = x + rglru_mod.rglru_apply(p["lru"], h, cfg)
    if "mlp" in p:
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp_apply(p["mlp"], h, cfg)
    return x, _no_aux()


def _rglru_cache(cfg, batch, max_len, **kw):
    return {"lru": rglru_mod.init_rglru_cache(cfg, batch)}


def _rglru_decode(p, x, cache, pos, cfg: ModelConfig, *, memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    o, new = rglru_mod.rglru_decode(p["lru"], h, cache["lru"], pos, cfg)
    x = x + o
    if "mlp" in p:
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + mlp_apply(p["mlp"], h, cfg)
    return x, {"lru": new}


# --------------------------------------------------------------------------
# cross_attn (self + cross + mlp) and enc_attn (bidirectional + mlp)
# --------------------------------------------------------------------------

def _cross_block_init(key, cfg: ModelConfig):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": _norm_init(cfg), "attn": attn_mod.attn_init(k1, cfg),
            "lnx": _norm_init(cfg), "xattn": attn_mod.attn_init(k2, cfg),
            "ln2": _norm_init(cfg), "mlp": mlp_init(k3, cfg),
            "xgate": jnp.zeros((), jnp.dtype(cfg.param_dtype))}


def _cross_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    assert memory is not None, "cross_attn block needs memory"
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    x = x + attn_mod.attn_apply(p["attn"], h, cfg, causal=True)
    h = rmsnorm(x, p["lnx"], cfg.norm_eps)
    xo = attn_mod.attn_apply(p["xattn"], h, cfg, causal=False,
                             kv_override=memory)
    x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * xo
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    return x + mlp_apply(p["mlp"], h, cfg), _no_aux()


def _cross_cache(cfg, batch, max_len, **kw):
    return {"kv": attn_mod.init_kv_cache(cfg, batch, max_len)}


def _cross_decode(p, x, cache, pos, cfg: ModelConfig, *, memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    o, kv = attn_mod.decode_attn_apply(p["attn"], h, cache["kv"], pos, cfg)
    x = x + o
    h = rmsnorm(x, p["lnx"], cfg.norm_eps)
    xo = attn_mod.attn_apply(p["xattn"], h, cfg, causal=False,
                             kv_override=memory)
    x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * xo
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    return x + mlp_apply(p["mlp"], h, cfg), {"kv": kv}


def _enc_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    return _attn_block_apply(p, x, cfg, causal=False)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

class BlockDef:
    def __init__(self, init, apply, cache, decode):
        self.init = init
        self.apply = apply
        self.cache = cache
        self.decode = decode


REGISTRY: Dict[str, BlockDef] = {
    "attn": BlockDef(
        _attn_block_init,
        lambda p, x, cfg, **kw: _attn_block_apply(p, x, cfg, window=0, **kw),
        lambda cfg, b, m, **kw: _attn_cache(cfg, b, m, window=0),
        lambda p, x, c, pos, cfg, **kw: _attn_decode(p, x, c, pos, cfg,
                                                     window=0, **kw)),
    "local_attn": BlockDef(
        _attn_block_init,
        lambda p, x, cfg, **kw: _attn_block_apply(
            p, x, cfg, window=cfg.window, **kw),
        lambda cfg, b, m, **kw: _attn_cache(cfg, b, m, window=cfg.window),
        lambda p, x, c, pos, cfg, **kw: _attn_decode(
            p, x, c, pos, cfg, window=cfg.window, **kw)),
    "moe": BlockDef(_moe_block_init, _moe_block_apply, _moe_cache,
                    _moe_decode),
    "ssm": BlockDef(_ssm_block_init, _ssm_block_apply, _ssm_cache,
                    _ssm_decode),
    "rglru": BlockDef(_rglru_block_init, _rglru_block_apply, _rglru_cache,
                      _rglru_decode),
    "cross_attn": BlockDef(_cross_block_init, _cross_block_apply,
                           _cross_cache, _cross_decode),
    "enc_attn": BlockDef(_attn_block_init, _enc_block_apply,
                         lambda cfg, b, m, **kw: {},
                         None),
}
