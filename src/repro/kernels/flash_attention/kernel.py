"""Blocked flash attention, Pallas TPU.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks); the kv axis is the
innermost, SEQUENTIAL grid dimension ("arbitrary" semantics on TPU), so the
online-softmax running state (m, l, o-accumulator) lives in VMEM scratch and
carries across kv steps.

BlockSpec tiling (VMEM working set per step, bf16, bq=bk=128, d<=256):
    q tile  (bq, d)    ~ 64 KB     k tile (bkv, d) ~ 64 KB
    v tile  (bkv, d)   ~ 64 KB     acc    (bq, d) f32 ~ 128 KB
well under the ~128 MB/core VMEM budget; scores (bq, bkv) stay in VREG/VMEM.

Masking supports causal and sliding-window (SWA: h2o-danube /
recurrentgemma local attention).  GQA head mapping happens via the k/v
index_map (no materialized kv broadcast).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 sm_scale: float, causal: bool, window: int,
                 block_q: int, block_kv: int, num_kv_blocks: int,
                 seq_len: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = kj * block_kv

    # Skip fully-masked blocks (causal: block strictly above the diagonal;
    # window: block strictly left of the oldest query row's window).
    relevant = jnp.bool_(True)
    if causal:
        relevant = jnp.logical_and(relevant, k_start <= q_start + block_q - 1)
    if window > 0:
        relevant = jnp.logical_and(
            relevant, k_start + block_kv - 1 >= q_start - window)

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bkv)

        q_ids = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        k_ids = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = k_ids < seq_len                    # padded tail
        if causal:
            mask = jnp.logical_and(mask, k_ids <= q_ids)
        if window > 0:
            mask = jnp.logical_and(mask, k_ids >= q_ids - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                       # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kj == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)           # fully-masked rows
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "causal", "window", "block_q", "block_kv",
                     "interpret"))
def mha(q, k, v, *, sm_scale: float, causal: bool = True, window: int = 0,
        block_q: int = DEFAULT_BLOCK_Q, block_kv: int = DEFAULT_BLOCK_KV,
        interpret: bool = True):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D); Hq % Hkv == 0.

    window > 0 keeps keys with q_pos - window <= k_pos (on top of causal).
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    nq = pl.cdiv(s, block_q)
    nkv = pl.cdiv(s, block_kv)

    grid = (b, hq, nq, nkv)
    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, kj: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_kv, d),
                           lambda bi, hi, qi, kj: (bi, hi // group, kj, 0))
    o_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bi, hi, qi, kj: (bi, hi, qi, 0))

    kernel = functools.partial(
        _attn_kernel, sm_scale=sm_scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, num_kv_blocks=nkv, seq_len=s)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),      # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
