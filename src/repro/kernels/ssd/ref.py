"""Pure-jnp oracles for the SSD recurrence.

``ssd_scan_ref``      exact sequential per-timestep scan (ground truth).
``ssd_chunked_jnp``   chunked SSD in vectorized jnp: per-chunk quadratic
                      terms + associative scan across chunks.  This is the
                      XLA execution path for SSM models when the Pallas
                      kernel is off — fully parallel (no while loop), so
                      dry-run cost_analysis counts its work correctly.
``ssd_chunked_from``  the chunked SSD from an initial state ``h0`` (B, H,
                      N, P), returning the final state too: the prefill's
                      cache.  ``ssd_scan_ref`` takes ``h0`` the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_scan_ref(x, dt, a_log, b, c, h0=None):
    """Exact per-timestep recurrence.

    x: (B, S, H, P); dt: (B, S, H); a_log: (H,); b, c: (B, S, N).
    With ``h0`` (B, H, N, P) the scan starts from it and returns
    (y, final state (B, H, N, P) float32).
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    a = -jnp.exp(a_log.astype(jnp.float32))          # (H,)

    def step(h_state, inputs):
        x_t, dt_t, b_t, c_t = inputs                 # (H,P),(H,),(N,),(N,)
        da = jnp.exp(dt_t * a)                       # (H,)
        inc = dt_t[:, None, None] * b_t[None, :, None] \
            * x_t[:, None, :]                        # (H, N, P)
        h_state = da[:, None, None] * h_state + inc
        y_t = jnp.einsum("n,hnp->hp", c_t, h_state)
        return h_state, y_t

    def per_batch(xb, dtb, bb, cb, hb0):
        h_last, ys = jax.lax.scan(
            step, hb0,
            (xb.astype(jnp.float32), dtb.astype(jnp.float32),
             bb.astype(jnp.float32), cb.astype(jnp.float32)))
        return ys, h_last                            # (S, H, P), (H, N, P)

    start = (jnp.zeros((bsz, h, n, p), jnp.float32) if h0 is None
             else h0.astype(jnp.float32))
    ys, h_last = jax.vmap(per_batch)(x, dt, b, c, start)
    ys = ys.astype(x.dtype)
    return ys if h0 is None else (ys, h_last)


def _ssd_chunked_one_head(xh, dth, a_h, bf, cf, tile_dtype=None):
    """Chunked SSD for ONE head (keeps the (L, L) decay matrix per
    (batch, chunk) only — the memory shape the Pallas kernel realizes).

    xh: (B, nc, L, P); dth: (B, nc, L); a_h: scalar; bf, cf: (B, nc, L, N).
    Returns y and the state after the last chunk (B, N, P), both from a
    zero state.
    tile_dtype: storage dtype for the (L, L) tiles (bf16 halves the HBM
    traffic the XLA fallback pays on them; accumulation stays fp32 via
    preferred_element_type — §Perf hillclimb).
    """
    chunk = xh.shape[2]
    td = tile_dtype or jnp.float32
    dta = dth * a_h                                           # (B,nc,L)
    # lax.cumsum, not jnp.cumsum: the same sums, but jnp's jitted wrapper
    # puts this function's name on the ops' path under vmap, where only
    # the model's scopes belong
    g = jax.lax.cumsum(dta, axis=2)
    g_last = g[:, :, -1]                                      # (B,nc)

    # intra-chunk quadratic term
    cb = jax.lax.dot_general(
        cf.astype(td), bf.astype(td),
        (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)                   # (B,nc,L,L)
    i_ids = jnp.arange(chunk)[:, None]
    j_ids = jnp.arange(chunk)[None, :]
    seg = g[:, :, :, None] - g[:, :, None, :]                 # (B,nc,L,L)
    # mask BEFORE exp: masked (j > i) entries have seg > 0 and would
    # overflow; where-after-exp leaks inf into the gradient (inf * 0 = nan)
    lmat = jnp.exp(jnp.where((j_ids <= i_ids)[None, None], seg, -1e30))
    y_intra = jax.lax.dot_general(
        (cb * lmat).astype(td), (xh * dth[..., None]).astype(td),
        (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32)                   # (B,nc,L,P)

    # per-chunk state contribution + cross-chunk associative scan
    decay_state = jnp.exp(g_last[:, :, None] - g)             # (B,nc,L)
    inc = jnp.einsum("bcln,bcl,bclp->bcnp",
                     bf, dth * decay_state, xh)               # (B,nc,N,P)
    chunk_decay = jnp.exp(g_last)                             # (B,nc)

    def combine(lhs, rhs):
        d1, s1 = lhs
        d2, s2 = rhs
        return d1 * d2, s1 * d2[..., None, None] + s2

    _, st_sc = jax.lax.associative_scan(
        combine, (chunk_decay, inc), axis=1)
    h_in = jnp.concatenate(
        [jnp.zeros_like(st_sc[:, :1]), st_sc[:, :-1]], axis=1)

    y_inter = jnp.einsum("bcln,bcl,bcnp->bclp",
                         cf, jnp.exp(g), h_in)
    return y_intra + y_inter, st_sc[:, -1]                    # (B,nc,L,P)


def _ssd_chunked(x, dt, a_log, b, c, *, chunk: int, unroll_heads: bool,
                 head_blocks: int, tile_dtype):
    """``ssd_chunked_jnp``'s body -> (y float32 (B, S, H, P), the state
    after the last position float32 (B, H, N, P)), from a zero state."""
    bsz, s_in, h, p = x.shape
    n = b.shape[-1]
    # a ragged tail: dt = 0 there (decay 1, increment 0), outputs dropped
    pad = -s_in % chunk
    if pad:
        tail = ((0, 0), (0, pad))
        x = jnp.pad(x, tail + ((0, 0), (0, 0)))
        dt = jnp.pad(dt, tail + ((0, 0),))
        b = jnp.pad(b, tail + ((0, 0),))
        c = jnp.pad(c, tail + ((0, 0),))
    s = s_in + pad
    nc = s // chunk
    a = -jnp.exp(a_log.astype(jnp.float32))                   # (H,)
    hb = head_blocks if head_blocks > 0 else h
    hb = min(hb, h)
    while h % hb != 0:
        hb -= 1
    hs = h // hb                                              # vmapped width

    from ...distributed.sharding import constrain
    xf = x.astype(jnp.float32).reshape(bsz, nc, chunk, hb, hs, p)
    dtf = dt.astype(jnp.float32).reshape(bsz, nc, chunk, hb, hs)
    xf = constrain(xf, ("batch", None, None, None, "head_shard", None))
    dtf = constrain(dtf, ("batch", None, None, None, "head_shard"))
    bf = b.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    cf = c.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    af = a.reshape(hb, hs)

    # vectorize the one-head body over the (sharded) inner head axis
    import functools
    one_head = functools.partial(_ssd_chunked_one_head,
                                 tile_dtype=tile_dtype)
    one_block = jax.vmap(one_head,
                         in_axes=(3, 3, 0, None, None), out_axes=(3, 1))
    # -> xh (B,nc,L,HS,P), dth (B,nc,L,HS), a (HS,)
    #    => y (B,nc,L,HS,P), state (B,HS,N,P)

    if unroll_heads:
        outs = [one_block(xf[:, :, :, i], dtf[:, :, :, i], af[i], bf, cf)
                for i in range(hb)]
        y = jnp.stack([o[0] for o in outs], axis=3)     # (B,nc,L,HB,HS,P)
        state = jnp.stack([o[1] for o in outs], axis=1)  # (B,HB,HS,N,P)
    else:
        xm = jnp.moveaxis(xf, 3, 0)                     # (HB,B,nc,L,HS,P)
        dtm = jnp.moveaxis(dtf, 3, 0)
        y, state = jax.lax.map(
            lambda args: one_block(args[0], args[1], args[2], bf, cf),
            (xm, dtm, af))
        y = jnp.moveaxis(y, 0, 3)
        state = jnp.moveaxis(state, 0, 1)
    y = y.reshape(bsz, s, h, p)
    if pad:
        y = y[:, :s_in]
    return y, state.reshape(bsz, h, n, p)


def ssd_chunked_jnp(x, dt, a_log, b, c, *, chunk: int = 128,
                    unroll_heads: bool = False,
                    head_blocks: int = 0,
                    tile_dtype=None):
    """Chunked SSD in vectorized jnp (arXiv:2405.21060 Alg. 1), processed
    in HEAD BLOCKS so only (heads_per_block-vmapped) (B, nc, L, L) decay
    matrices are live — mirroring the Pallas kernel's VMEM tiling.

    The head axis is split (head_blocks, heads_per_block); the inner axis
    stays vectorized (it is the "model"-sharded axis in SPMD lowerings, so
    each chip computes only its own heads), while the outer axis is looped:
    unroll_heads=True inlines that loop (dry-run accounting: XLA
    cost_analysis counts loop bodies once); False uses lax.map (memory-
    faithful).  head_blocks=0 defaults to one block per head.

    A sequence that is not a multiple of ``chunk`` is padded at its end
    with dt = 0 (decay 1, increment 0, so the state stays exact) and the
    padded positions' outputs are dropped.
    """
    y, _ = _ssd_chunked(x, dt, a_log, b, c, chunk=chunk,
                        unroll_heads=unroll_heads, head_blocks=head_blocks,
                        tile_dtype=tile_dtype)
    return y.astype(x.dtype)


def ssd_chunked_from(h0, x, dt, a_log, b, c, *, chunk: int = 128,
                     unroll_heads: bool = False, head_blocks: int = 0):
    """``ssd_chunked_jnp`` started from the state ``h0`` (B, H, N, P) ->
    (y, the state after the last position, float32 (B, H, N, P)).

    The recurrence is linear in its state, so ``h0``'s share is added to
    the zero-state scan: it reaches position t decayed by
    exp(sum_{u <= t} a dt_u), and is read there through C_t."""
    y, state = _ssd_chunked(x, dt, a_log, b, c, chunk=chunk,
                            unroll_heads=unroll_heads,
                            head_blocks=head_blocks, tile_dtype=None)
    h0 = h0.astype(jnp.float32)
    a = -jnp.exp(a_log.astype(jnp.float32))
    decay = jnp.exp(jax.lax.cumsum(dt.astype(jnp.float32) * a, axis=1))
    y = y + (jnp.einsum("bsn,bhnp->bshp", c.astype(jnp.float32), h0)
             * decay[..., None])                              # (B,S,H,P)
    return y.astype(x.dtype), decay[:, -1, :, None, None] * h0 + state
