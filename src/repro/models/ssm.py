"""Mamba2 block: split in-projections -> causal depthwise convs -> SSD scan
-> gated RMSNorm -> out-proj.

Projections are SEPARATE weights per stream (z, x, B, C, dt) rather than one
fused matmul: fused output slicing would cut across "model"-axis shards and
force XLA to re-gather the whole activation (found in the dry-run: 3e14
collective bytes on train_4k).  B/C are small (2N per token) and computed
replicated; z/x/dt shard cleanly on heads/channels.

The SSD is the chunked-jnp path with head-block processing
(kernels/ssd/ref.py); the prefill (``ssm_prefill``) starts it from the
cache's state and returns the final one.  The Pallas SSD kernel
(kernels/ssd) is not on the model's path."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from .layers import dense_init, dtype_of, pdtype_of, rmsnorm, scope


# the prefill's SSD vectorizes as many heads as keep one head block's
# float32 (B, nc, L, L) tile within this many bytes (kernels/ssd/ref.py)
PREFILL_TILE_BYTES = 64 << 20


def _dims(cfg: ModelConfig):
    di = cfg.d_inner
    n = cfg.ssm_state
    h = cfg.ssm_heads
    conv_ch = di + 2 * n
    return di, n, h, conv_ch


def ssm_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    pd = pdtype_of(cfg)
    di, n, h, conv_ch = _dims(cfg)
    return {
        # separate stream projections (shard-aligned; see module docstring)
        "w_z": dense_init(ks[0], cfg.d_model, di, pd),
        "w_xs": dense_init(ks[1], cfg.d_model, di, pd),
        "w_b": dense_init(ks[2], cfg.d_model, n, pd),
        "w_c": dense_init(ks[3], cfg.d_model, n, pd),
        "w_dtp": dense_init(ks[4], cfg.d_model, h, pd),
        "conv_w": (jax.random.normal(ks[5], (cfg.conv_width, conv_ch))
                   * 0.1).astype(pd),
        "conv_b": jnp.zeros((conv_ch,), pd),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm_scale": jnp.ones((di,), pd),
        "w_out": dense_init(ks[7], di, cfg.d_model, pd,
                            scale=cfg.residual_scale),
    }


def _conv_split(p, cfg: ModelConfig):
    """Per-stream views of the depthwise conv parameters."""
    di, n, _, _ = _dims(cfg)
    w, b = p["conv_w"], p["conv_b"]
    return ((w[:, :di], b[:di]),
            (w[:, di:di + n], b[di:di + n]),
            (w[:, di + n:], b[di + n:]))


def _causal_conv(x, w, b, *, width: int, left=None):
    """Depthwise causal conv over seq: x (B, S, C).  ``left`` (B, width-1,
    C) is the context before position 0; None reads zeros."""
    if left is None:
        pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    else:
        pad = jnp.concatenate([left, x], axis=1)
    s = x.shape[1]
    out = sum(pad[:, j:j + s, :] * w[j][None, None, :] for j in range(width))
    return jax.nn.silu(out + b[None, None, :])


def _ssm_sequence(p, x, cfg: ModelConfig, cache=None):
    """The mixer over a whole sequence: projections, causal conv, chunked
    SSD, D skip, gated RMSNorm, out-projection -> (out, new cache).

    Without ``cache`` the sequence starts from zeros (``ssm_apply``) and the
    new cache is None.  With ``cache`` (``init_ssm_cache``'s layout) the
    conv reads its window as the context before position 0 and the SSD
    starts from its state; the new cache holds the last ``conv_width - 1``
    raw x/B/C rows and the state after the last position, as a token loop
    of ``ssm_decode`` leaves them."""
    dt_ = dtype_of(cfg)
    di, n, h, _ = _dims(cfg)
    b, s, _ = x.shape
    z = x @ p["w_z"].astype(dt_)
    xs = x @ p["w_xs"].astype(dt_)
    bmat = x @ p["w_b"].astype(dt_)
    cmat = x @ p["w_c"].astype(dt_)
    dt_raw = x @ p["w_dtp"].astype(dt_)

    lefts = (None, None, None)
    new_cache = None
    if cache is not None:
        width = cfg.conv_width
        with scope("cache"):
            hist = cache["conv"]
            lefts = (hist[..., :di], hist[..., di:di + n], hist[..., di + n:])
            raw = jnp.concatenate([v[:, -(width - 1):]
                                   for v in (xs, bmat, cmat)], axis=-1)
            conv = jnp.concatenate([hist, raw], axis=1)[:, -(width - 1):]

    (wx, bx), (wb, bb), (wc, bc) = _conv_split(p, cfg)
    xs = _causal_conv(xs, wx.astype(dt_), bx.astype(dt_),
                      width=cfg.conv_width, left=lefts[0])
    bmat = _causal_conv(bmat, wb.astype(dt_), bb.astype(dt_),
                        width=cfg.conv_width, left=lefts[1])
    cmat = _causal_conv(cmat, wc.astype(dt_), bc.astype(dt_),
                        width=cfg.conv_width, left=lefts[2])

    xh = xs.reshape(b, s, h, cfg.ssm_headdim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])
    xh = constrain(xh, ("batch", "seq", "heads", None))

    from ..distributed import sharding as shd
    from ..distributed.sharding import axis_size
    mesh = shd.active_mesh()
    # head blocks: keep the "model"-sharded head slice vectorized, loop
    # the rest (memory ~ per-chip heads x (nc, L, L); kernels/ssd/ref.py)
    shard = max(axis_size("model"), 1)
    hb = max(1, h // shard)
    if cache is not None:
        # the chunked SSD from the cache's state, whatever the length: the
        # chunk follows the prompt, a ragged tail is padded with dt = 0.
        # GSPMD whatever ssd_shard_map says: that path saves the backward's
        # all-reduces, and a prefill has no backward
        from ..kernels.ssd.ref import ssd_chunked_from
        chunk = min(cfg.ssm_chunk, s)
        y, state = ssd_chunked_from(
            cache["ssm"], xh.astype(jnp.float32), dt, p["a_log"],
            bmat.astype(jnp.float32), cmat.astype(jnp.float32),
            chunk=chunk, unroll_heads=cfg.attn_chunk_unroll,
            head_blocks=_prefill_head_blocks(h, shard, b, -(-s // chunk),
                                             chunk))
        new_cache = {"conv": conv, "ssm": state}
    elif cfg.ssd_shard_map and mesh is not None and axis_size("model") > 1:
        rules = shd.current_rules() or {}
        dp = rules.get("batch")
        dp_axes = (dp,) if isinstance(dp, str) else (dp or ())
        y = ssd_apply_shard_map(
            xh.astype(jnp.float32), dt, p["a_log"],
            bmat.astype(jnp.float32), cmat.astype(jnp.float32), cfg,
            mesh=mesh, dp_axes=dp_axes)
    else:
        from ..kernels.ssd.ref import ssd_chunked_jnp
        y = ssd_chunked_jnp(xh.astype(jnp.float32), dt, p["a_log"],
                            bmat.astype(jnp.float32),
                            cmat.astype(jnp.float32),
                            chunk=min(cfg.ssm_chunk, s),
                            unroll_heads=cfg.attn_chunk_unroll,
                            head_blocks=hb)
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    y = y.reshape(b, s, di).astype(dt_)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    out = y @ p["w_out"].astype(dt_)
    return constrain(out, ("batch", "seq", "embed")), new_cache


def _prefill_head_blocks(h: int, shard: int, b: int, nc: int,
                         chunk: int) -> int:
    """Head blocks for the prefill's SSD: each block the widest multiple
    of the sharded head slice that divides ``h`` and keeps its float32
    (b, nc, chunk, chunk) tiles within ``PREFILL_TILE_BYTES``."""
    per_head = b * nc * chunk * chunk * 4
    width = max([w for w in range(shard, h + 1, shard)
                 if h % w == 0 and w * per_head <= PREFILL_TILE_BYTES],
                default=shard)
    return h // width


def ssm_apply(p, x, cfg: ModelConfig):
    """x: (B, S, D) from a zero state -> (B, S, D)."""
    return _ssm_sequence(p, x, cfg)[0]


def ssm_prefill(p, x, cache: Dict, cfg: ModelConfig
                ) -> Tuple[jax.Array, Dict]:
    """A whole prompt x (B, S, D) in one chunked pass from ``cache`` ->
    (out (B, S, D), the cache after position S-1: conv window and SSM
    state)."""
    return _ssm_sequence(p, x, cfg, cache)


def init_ssm_cache(cfg: ModelConfig, batch: int) -> Dict:
    di, n, h, conv_ch = _dims(cfg)
    dt_ = dtype_of(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_ch), dt_),
        "ssm": jnp.zeros((batch, h, n, cfg.ssm_headdim), jnp.float32),
    }


def ssm_decode(p, x, cache: Dict, pos, cfg: ModelConfig
               ) -> Tuple[jax.Array, Dict]:
    """x: (B, 1, D) single-token step."""
    dt_ = dtype_of(cfg)
    di, n, h, conv_ch = _dims(cfg)
    b = x.shape[0]
    x0 = x[:, 0, :]
    z = x0 @ p["w_z"].astype(dt_)
    new = jnp.concatenate([x0 @ p["w_xs"].astype(dt_),
                           x0 @ p["w_b"].astype(dt_),
                           x0 @ p["w_c"].astype(dt_)], axis=-1)
    dt_raw = x0 @ p["w_dtp"].astype(dt_)

    with scope("cache"):
        hist = jnp.concatenate([cache["conv"], new[:, None, :]], axis=1)
        conv = hist[:, 1:, :]
    w = p["conv_w"].astype(dt_)
    conv_out = jnp.einsum("bwc,wc->bc", hist, w) + p["conv_b"].astype(dt_)
    xbc = jax.nn.silu(conv_out)

    xs = xbc[:, :di].reshape(b, h, cfg.ssm_headdim).astype(jnp.float32)
    bmat = xbc[:, di:di + n].astype(jnp.float32)
    cmat = xbc[:, di + n:].astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"][None, :])
    a = -jnp.exp(p["a_log"])                                  # (H,)
    da = jnp.exp(dt * a[None, :])                             # (B, H)
    inc = dt[:, :, None, None] * bmat[:, None, :, None] * xs[:, :, None, :]
    with scope("cache"):
        ssm = da[:, :, None, None] * cache["ssm"] + inc       # (B,H,N,P)
    y = jnp.einsum("bn,bhnp->bhp", cmat, ssm)
    y = y + xs * p["d_skip"][None, :, None]
    y = y.reshape(b, di).astype(dt_)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_scale"], cfg.norm_eps)
    out = (y @ p["w_out"].astype(dt_))[:, None, :]
    return out, {"conv": conv, "ssm": ssm}


# ---------------------------------------------------------------------------
# shard_map SSD path (§Perf hillclimb; cfg.ssd_shard_map).
#
# Everything the SSD needs is already per-shard local: x-heads shard over
# "model", batch over the DP axes, B/C replicated over "model".  Running the
# chunked scan inside shard_map means autodiff inserts exactly ONE psum per
# replicated input's gradient (dB, dC, dA) per layer — instead of GSPMD's
# per-head-block (B,nc,L,L)-sized backward all-reduces (6.8e13 collective
# bytes in the dry run's cost analysis of mamba2 train_4k).
# ---------------------------------------------------------------------------

def _ssd_local_body(xh, dt, a_log, bmat, cmat, *, chunk: int,
                    unroll_heads: bool, tile_dtype=None):
    from ..distributed.sharding import manual_region
    from ..kernels.ssd.ref import ssd_chunked_jnp
    # per-shard: all local heads vectorized in one block (no inner loop)
    with manual_region():
        return ssd_chunked_jnp(xh, dt, a_log, bmat, cmat, chunk=chunk,
                               unroll_heads=unroll_heads, head_blocks=1,
                               tile_dtype=tile_dtype)


def ssd_apply_shard_map(xh, dt, a_log, bmat, cmat, cfg: ModelConfig, *,
                        mesh, dp_axes, model_axis: str = "model"):
    """xh: (B,S,H,P) head-sharded; dt: (B,S,H); bmat/cmat: (B,S,N)."""
    import functools
    from jax.sharding import PartitionSpec as P

    dp = tuple(dp_axes) if dp_axes else None
    body = functools.partial(
        _ssd_local_body, chunk=cfg.ssm_chunk,
        unroll_heads=cfg.attn_chunk_unroll,
        tile_dtype=jnp.bfloat16 if cfg.ssd_tile_bf16 else None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp, None, model_axis, None),   # x heads sharded
                  P(dp, None, model_axis),          # dt heads sharded
                  P(model_axis,),                   # A_log per local head
                  P(dp, None, None),                # B replicated over model
                  P(dp, None, None)),               # C replicated over model
        out_specs=P(dp, None, model_axis, None),
        check_vma=False,
    )(xh, dt, a_log, bmat, cmat)
