"""Block registry: per-kind (init, apply, cache_init/spec, decode).

Every block owns its norms and residual adds, each under the scope of
the block part it feeds (``layers.SCOPES``).  Kinds:
  attn        full causal GQA attention + SwiGLU MLP
  local_attn  sliding-window GQA attention + MLP
  moe         (MLA or GQA) attention + MoE FFN (returns aux loss)
  mla         MLA attention + SwiGLU MLP (DeepSeek-V3's leading dense
              layers, width d_ff)
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
from .layers import dtype_of, mlp_apply, mlp_init, rmsnorm, scope


def _no_aux():
    """Zero auxiliary loss (built per call: a module-level array would
    start a JAX backend at import)."""
    return jnp.zeros((), jnp.float32)


def _norm_init(cfg):
    return jnp.ones((cfg.d_model,), jnp.dtype(cfg.param_dtype))


@scope("mlp")
def _mlp_residual(p, x, cfg: ModelConfig):
    """x + MLP(norm(x))."""
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    return x + mlp_apply(p["mlp"], h, cfg)


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
    with scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        x = x + attn_mod.attn_apply(p["attn"], h, cfg, causal=causal,
                                    window=window)
    if "mlp" in p:
        x = _mlp_residual(p, x, cfg)
    return x, _no_aux()


def _attn_cache(cfg, batch, max_len, *, window=0):
    return {"kv": attn_mod.init_kv_cache(cfg, batch, max_len, window=window)}


def _attn_decode(p, x, cache, pos, cfg: ModelConfig, *, window=0,
                 memory=None):
    with scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        o, kv = attn_mod.decode_attn_apply(p["attn"], h, cache["kv"], pos,
                                           cfg, window=window)
        x = x + o
    if "mlp" in p:
        x = _mlp_residual(p, x, cfg)
    return x, {"kv": kv}


def _attn_prefill(p, x, cache, cfg: ModelConfig, *, window=0):
    """The whole prompt through the block, its keys and values into the
    cache."""
    with scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        o, kv = attn_mod.prefill_attn_apply(p["attn"], h, cache["kv"], cfg,
                                            window=window)
        x = x + o
    if "mlp" in p:
        x = _mlp_residual(p, x, cfg)
    return x, {"kv": kv}


# --------------------------------------------------------------------------
# moe (attention = MLA or GQA, FFN = MoE)
# --------------------------------------------------------------------------

def _attention_init(key, cfg: ModelConfig):
    return (mla_mod.mla_init(key, cfg) if cfg.use_mla
            else attn_mod.attn_init(key, cfg))


def _attention_apply(p, x, cfg: ModelConfig):
    """x + the block's causal self-attention (MLA or GQA)."""
    with scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if cfg.use_mla:
            return x + mla_mod.mla_apply(p["attn"], h, cfg)
        return x + attn_mod.attn_apply(p["attn"], h, cfg, causal=True)


def _attention_cache(cfg, batch, max_len):
    if cfg.use_mla:
        return {"mla": mla_mod.init_mla_cache(cfg, batch, max_len)}
    return {"kv": attn_mod.init_kv_cache(cfg, batch, max_len)}


def _attention_decode(p, x, cache, pos, cfg: ModelConfig):
    with scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if cfg.use_mla:
            o, new = mla_mod.mla_decode(p["attn"], h, cache["mla"], pos,
                                        cfg)
            new_cache = {"mla": new}
        else:
            o, new = attn_mod.decode_attn_apply(p["attn"], h, cache["kv"],
                                                pos, cfg)
            new_cache = {"kv": new}
        return x + o, new_cache


def _attention_prefill(p, x, cache, cfg: ModelConfig):
    with scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        if cfg.use_mla:
            o, new = mla_mod.mla_prefill(p["attn"], h, cache["mla"], cfg)
            new_cache = {"mla": new}
        else:
            o, new = attn_mod.prefill_attn_apply(p["attn"], h, cache["kv"],
                                                 cfg)
            new_cache = {"kv": new}
        return x + o, new_cache


def _moe_block_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg), "attn": _attention_init(k1, cfg),
            "ln2": _norm_init(cfg), "moe": moe_mod.moe_init(k2, cfg)}


@scope("moe")
def _moe_residual(p, x, cfg: ModelConfig):
    """(x + MoE(norm(x)), aux loss)."""
    h = rmsnorm(x, p["ln2"], cfg.norm_eps)
    y, aux = moe_mod.moe_apply(p["moe"], h, cfg)
    return x + y, aux


def _moe_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    return _moe_residual(p, _attention_apply(p, x, cfg), cfg)


def _moe_cache(cfg, batch, max_len, **kw):
    return _attention_cache(cfg, batch, max_len)


def _moe_decode(p, x, cache, pos, cfg: ModelConfig, *, memory=None):
    x, new_cache = _attention_decode(p, x, cache, pos, cfg)
    return _moe_residual(p, x, cfg)[0], new_cache


def _moe_prefill(p, x, cache, cfg: ModelConfig):
    x, new_cache = _attention_prefill(p, x, cache, cfg)
    return _moe_residual(p, x, cfg)[0], new_cache


# --------------------------------------------------------------------------
# mla (MLA attention + dense SwiGLU: the leading dense layers)
# --------------------------------------------------------------------------

def _mla_block_init(key, cfg: ModelConfig):
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg), "attn": mla_mod.mla_init(k1, cfg),
            "ln2": _norm_init(cfg), "mlp": mlp_init(k2, cfg)}


def _mla_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    return _mlp_residual(p, _attention_apply(p, x, cfg), cfg), _no_aux()


def _mla_decode(p, x, cache, pos, cfg: ModelConfig, *, memory=None):
    x, new_cache = _attention_decode(p, x, cache, pos, cfg)
    return _mlp_residual(p, x, cfg), new_cache


def _mla_prefill(p, x, cache, cfg: ModelConfig):
    x, new_cache = _attention_prefill(p, x, cache, cfg)
    return _mlp_residual(p, x, cfg), new_cache


# --------------------------------------------------------------------------
# ssm
# --------------------------------------------------------------------------

def _ssm_block_init(key, cfg: ModelConfig):
    return {"ln1": _norm_init(cfg), "ssm": ssm_mod.ssm_init(key, cfg)}


@scope("ssm")
def _ssm_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    return x + ssm_mod.ssm_apply(p["ssm"], h, cfg), _no_aux()


def _ssm_cache(cfg, batch, max_len, **kw):
    return {"ssm": ssm_mod.init_ssm_cache(cfg, batch)}


@scope("ssm")
def _ssm_decode(p, x, cache, pos, cfg: ModelConfig, *, memory=None):
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    o, new = ssm_mod.ssm_decode(p["ssm"], h, cache["ssm"], pos, cfg)
    return x + o, {"ssm": new}


@scope("ssm")
def _ssm_prefill(p, x, cache, cfg: ModelConfig):
    """The whole prompt through the mixer in one chunked pass, its conv
    window and final state into the cache."""
    h = rmsnorm(x, p["ln1"], cfg.norm_eps)
    o, new = ssm_mod.ssm_prefill(p["ssm"], h, cache["ssm"], cfg)
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
    with scope("rglru"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        x = x + rglru_mod.rglru_apply(p["lru"], h, cfg)
    if "mlp" in p:
        x = _mlp_residual(p, x, cfg)
    return x, _no_aux()


def _rglru_cache(cfg, batch, max_len, **kw):
    return {"lru": rglru_mod.init_rglru_cache(cfg, batch)}


def _rglru_decode(p, x, cache, pos, cfg: ModelConfig, *, memory=None):
    with scope("rglru"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        o, new = rglru_mod.rglru_decode(p["lru"], h, cache["lru"], pos, cfg)
        x = x + o
    if "mlp" in p:
        x = _mlp_residual(p, x, cfg)
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


@scope("cross")
def _cross_residual(p, x, memory, cfg: ModelConfig):
    """x + gate * cross-attention(norm(x), memory)."""
    h = rmsnorm(x, p["lnx"], cfg.norm_eps)
    xo = attn_mod.attn_apply(p["xattn"], h, cfg, causal=False,
                             kv_override=memory)
    return x + jnp.tanh(p["xgate"]).astype(x.dtype) * xo


def _cross_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    assert memory is not None, "cross_attn block needs memory"
    with scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        x = x + attn_mod.attn_apply(p["attn"], h, cfg, causal=True)
    x = _cross_residual(p, x, memory, cfg)
    return _mlp_residual(p, x, cfg), _no_aux()


def _cross_cache(cfg, batch, max_len, **kw):
    return {"kv": attn_mod.init_kv_cache(cfg, batch, max_len)}


def _cross_decode(p, x, cache, pos, cfg: ModelConfig, *, memory=None):
    with scope("attn"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        o, kv = attn_mod.decode_attn_apply(p["attn"], h, cache["kv"], pos,
                                           cfg)
        x = x + o
    x = _cross_residual(p, x, memory, cfg)
    return _mlp_residual(p, x, cfg), {"kv": kv}


def _enc_block_apply(p, x, cfg: ModelConfig, *, memory=None):
    return _attn_block_apply(p, x, cfg, causal=False)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

class BlockDef:
    """``prefill(p, x, cache, cfg) -> (x, new_cache)`` takes a whole
    prompt at positions 0..S-1 at once: attention writes its keys and
    values, the SSM mixer its conv window and final state.  None where the
    block's state is built one token at a time through ``decode``
    (``rglru``, ``cross_attn``)."""

    def __init__(self, init, apply, cache, decode, prefill=None):
        self.init = init
        self.apply = apply
        self.cache = cache
        self.decode = decode
        self.prefill = prefill


REGISTRY: Dict[str, BlockDef] = {
    "attn": BlockDef(
        _attn_block_init,
        lambda p, x, cfg, **kw: _attn_block_apply(p, x, cfg, window=0, **kw),
        lambda cfg, b, m, **kw: _attn_cache(cfg, b, m, window=0),
        lambda p, x, c, pos, cfg, **kw: _attn_decode(p, x, c, pos, cfg,
                                                     window=0, **kw),
        lambda p, x, c, cfg: _attn_prefill(p, x, c, cfg, window=0)),
    "local_attn": BlockDef(
        _attn_block_init,
        lambda p, x, cfg, **kw: _attn_block_apply(
            p, x, cfg, window=cfg.window, **kw),
        lambda cfg, b, m, **kw: _attn_cache(cfg, b, m, window=cfg.window),
        lambda p, x, c, pos, cfg, **kw: _attn_decode(
            p, x, c, pos, cfg, window=cfg.window, **kw),
        lambda p, x, c, cfg: _attn_prefill(p, x, c, cfg,
                                           window=cfg.window)),
    "moe": BlockDef(_moe_block_init, _moe_block_apply, _moe_cache,
                    _moe_decode, _moe_prefill),
    "mla": BlockDef(_mla_block_init, _mla_block_apply,
                    lambda cfg, b, m, **kw: _attention_cache(cfg, b, m),
                    _mla_decode, _mla_prefill),
    "ssm": BlockDef(_ssm_block_init, _ssm_block_apply, _ssm_cache,
                    _ssm_decode, _ssm_prefill),
    "rglru": BlockDef(_rglru_block_init, _rglru_block_apply, _rglru_cache,
                      _rglru_decode),
    "cross_attn": BlockDef(_cross_block_init, _cross_block_apply,
                           _cross_cache, _cross_decode),
    "enc_attn": BlockDef(_attn_block_init, _enc_block_apply,
                         lambda cfg, b, m, **kw: {},
                         None),
}
