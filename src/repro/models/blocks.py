"""Block registry: each block kind is a sequence mixer plus an FFN, run by
one body in all three modes (``apply``, ``prefill``, ``decode``).

The body: under the mixer's scope (``layers.SCOPES``), norm, mixer and
residual; for ``cross_attn``, a gated cross-attention residual of the
memory under ``cross``; then the FFN's residual under the FFN's scope.
Mixers: ``gqa`` (full, sliding-window or bidirectional attention),
``MLA`` (latent attention), ``SSM`` (Mamba2) and ``RGLRU``.  FFNs: the
dense SwiGLU ``MLP`` (present iff the params hold ``mlp``), ``MOE``
(which returns the aux loss), or none.  Kinds:
  attn        causal GQA + MLP
  local_attn  sliding-window GQA + MLP
  moe         the model's attention (MLA or GQA) + MoE
  mla         MLA + MLP (DeepSeek-V3's leading dense layers, width d_ff)
  ssm         Mamba2 mixer, no FFN
  rglru       RG-LRU recurrent mixer + MLP
  cross_attn  causal GQA + gated cross-attention of the memory + MLP
              (whisper decoder / vlm)
  enc_attn    bidirectional GQA + MLP, no cache (whisper encoder)
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn_mod
from . import mla as mla_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .layers import mlp_apply, mlp_init, rmsnorm, scope


class Mixer(NamedTuple):
    """A block's sequence mixer: its key in the block's params and in its
    cache, the scope its norm, ops and residual run under, and

    init(key, cfg) -> params
    apply(p, h, cfg) -> out, a whole sequence from an empty state
    cache(cfg, batch, max_len) -> its decode state
    decode(p, h, cache, pos, cfg) -> (out, cache), one token at ``pos``
    prefill(p, h, cache, cfg) -> (out, cache), a whole prompt at positions
        0..S-1; None where the state is built one token at a time."""
    param: str
    cache_key: str
    scope: str
    init: Callable
    apply: Callable
    cache: Callable
    decode: Callable
    prefill: Optional[Callable]


def gqa(local: bool = False, causal: bool = True) -> Mixer:
    """Grouped-query attention: over the last ``cfg.window`` positions
    when ``local``, bidirectional unless ``causal``."""
    def window(cfg):
        return cfg.window if local else 0

    return Mixer(
        "attn", "kv", "attn", attn_mod.attn_init,
        lambda p, h, cfg: attn_mod.attn_apply(p, h, cfg, causal=causal,
                                              window=window(cfg)),
        lambda cfg, b, m: attn_mod.init_kv_cache(cfg, b, m,
                                                 window=window(cfg)),
        lambda p, h, c, pos, cfg: attn_mod.decode_attn_apply(
            p, h, c, pos, cfg, window=window(cfg)),
        lambda p, h, c, cfg: attn_mod.prefill_attn_apply(
            p, h, c, cfg, window=window(cfg)))


GQA = gqa()
MLA = Mixer("attn", "mla", "attn", mla_mod.mla_init, mla_mod.mla_apply,
            mla_mod.init_mla_cache, mla_mod.mla_decode, mla_mod.mla_prefill)
SSM = Mixer("ssm", "ssm", "ssm", ssm_mod.ssm_init, ssm_mod.ssm_apply,
            lambda cfg, b, m: ssm_mod.init_ssm_cache(cfg, b),
            ssm_mod.ssm_decode, ssm_mod.ssm_prefill)
RGLRU = Mixer("lru", "lru", "rglru", rglru_mod.rglru_init,
              rglru_mod.rglru_apply,
              lambda cfg, b, m: rglru_mod.init_rglru_cache(cfg, b),
              rglru_mod.rglru_decode, None)


class FFN(NamedTuple):
    """A block's feed-forward part: its key in the block's params (and its
    scope), ``init(key, cfg)`` and ``apply(p, h, cfg) -> (out, aux loss
    or None)``."""
    param: str
    init: Callable
    apply: Callable


MLP = FFN("mlp", mlp_init, lambda p, h, cfg: (mlp_apply(p, h, cfg), None))
MOE = FFN("moe", moe_mod.moe_init, moe_mod.moe_apply)


def _no_aux():
    """Zero auxiliary loss (built per call: a module-level array would
    start a JAX backend at import)."""
    return jnp.zeros((), jnp.float32)


def _norm_init(cfg):
    return jnp.ones((cfg.d_model,), jnp.dtype(cfg.param_dtype))


@scope("cross")
def _cross_residual(p, x, memory, cfg: ModelConfig):
    """x + gate * cross-attention(norm(x), memory)."""
    h = rmsnorm(x, p["lnx"], cfg.norm_eps)
    xo = attn_mod.attn_apply(p["xattn"], h, cfg, causal=False,
                             kv_override=memory)
    return x + jnp.tanh(p["xgate"]).astype(x.dtype) * xo


class BlockDef:
    """One block kind.  ``mixer`` is None for the model's own attention:
    MLA when ``cfg.use_mla``, else causal GQA.

    ``apply(p, x, cfg, memory=None) -> (x, aux loss)``;
    ``cache(cfg, batch, max_len)``;
    ``decode(p, x, cache, pos, cfg, memory=None) -> (x, new_cache)``;
    ``prefill(p, x, cache, cfg) -> (x, new_cache)`` takes a whole prompt
    at positions 0..S-1 at once: attention writes its keys and values,
    the SSM mixer its conv window and final state.  ``prefill`` is None
    where the block's state is built one token at a time through
    ``decode`` (``rglru``, ``cross_attn``), and both are None for a block
    without a cache (``enc_attn``)."""

    def __init__(self, mixer: Optional[Mixer], ffn: Optional[FFN], *,
                 cross: bool = False, cached: bool = True):
        self._mixer, self.ffn, self.cross = mixer, ffn, cross
        self.decode = self._decode if cached else None
        sequence = mixer is None or mixer.prefill is not None
        self.prefill = (self._prefill if cached and sequence and not cross
                        else None)

    def mixer(self, cfg: ModelConfig) -> Mixer:
        if self._mixer is not None:
            return self._mixer
        return MLA if cfg.use_mla else GQA

    def init(self, key, cfg: ModelConfig) -> Dict:
        mixer = self.mixer(cfg)
        if self.ffn is None:
            return {"ln1": _norm_init(cfg), mixer.param: mixer.init(key, cfg)}
        keys = jax.random.split(key, 3 if self.cross else 2)
        p = {"ln1": _norm_init(cfg), mixer.param: mixer.init(keys[0], cfg)}
        if self.cross:
            p.update(lnx=_norm_init(cfg),
                     xattn=attn_mod.attn_init(keys[1], cfg),
                     xgate=jnp.zeros((), jnp.dtype(cfg.param_dtype)))
        if self.ffn is MOE or cfg.d_ff > 0:
            p.update(ln2=_norm_init(cfg),
                     **{self.ffn.param: self.ffn.init(keys[-1], cfg)})
        return p

    def _body(self, p, x, cfg: ModelConfig, memory, run):
        """x through the block, ``run(mixer, its params, h) -> (out,
        state)`` running the mixer -> (x, the mixer's new cache, the FFN's
        aux loss or None)."""
        mixer = self.mixer(cfg)
        with scope(mixer.scope):
            h = rmsnorm(x, p["ln1"], cfg.norm_eps)
            out, state = run(mixer, p[mixer.param], h)
            x = x + out
        if self.cross:
            assert memory is not None, "cross_attn block needs memory"
            x = _cross_residual(p, x, memory, cfg)
        aux = None
        if self.ffn is not None and self.ffn.param in p:
            with scope(self.ffn.param):
                h = rmsnorm(x, p["ln2"], cfg.norm_eps)
                out, aux = self.ffn.apply(p[self.ffn.param], h, cfg)
                x = x + out
        return x, {mixer.cache_key: state}, aux

    def apply(self, p, x, cfg: ModelConfig, memory=None):
        x, _, aux = self._body(p, x, cfg, memory, lambda m, mp, h: (
            m.apply(mp, h, cfg), None))
        return x, _no_aux() if aux is None else aux

    def cache(self, cfg: ModelConfig, batch: int, max_len: int) -> Dict:
        mixer = self.mixer(cfg)
        return {mixer.cache_key: mixer.cache(cfg, batch, max_len)}

    def _decode(self, p, x, cache, pos, cfg: ModelConfig, memory=None):
        x, new, _ = self._body(p, x, cfg, memory, lambda m, mp, h: m.decode(
            mp, h, cache[m.cache_key], pos, cfg))
        return x, new

    def _prefill(self, p, x, cache, cfg: ModelConfig):
        x, new, _ = self._body(p, x, cfg, None, lambda m, mp, h: (
            m.prefill(mp, h, cache[m.cache_key], cfg)))
        return x, new


REGISTRY: Dict[str, BlockDef] = {
    "attn": BlockDef(GQA, MLP),
    "local_attn": BlockDef(gqa(local=True), MLP),
    "moe": BlockDef(None, MOE),
    "mla": BlockDef(MLA, MLP),
    "ssm": BlockDef(SSM, None),
    "rglru": BlockDef(RGLRU, MLP),
    "cross_attn": BlockDef(GQA, MLP, cross=True),
    "enc_attn": BlockDef(gqa(causal=False), MLP, cached=False),
}
