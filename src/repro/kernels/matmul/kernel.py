"""Tiled MXU matmul, Pallas TPU — the tensor-throughput microbenchmark
kernel (paper §V-A(iv)) adapted from CUDA CTA tiles to MXU BlockSpecs.

Grid (M/bm, N/bn, K/bk); K is the innermost sequential axis; a float32
accumulator tile (bm, bn) lives in VMEM scratch across K steps (the TPU
analogue of TMEM-resident accumulators — paper Eq. 2's D_accum).

VMEM working set per step: A (bm, bk) + B (bk, bn) + acc (bm, bn) f32.
bm=bn=256, bk=512 bf16 => 0.25 + 0.25 + 0.25 MB — MXU-aligned multiples
of 128 (the model's mxu_utilization term rewards this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, num_k: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kj == num_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "out_dtype"))
def matmul_tiled(a, b, *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                 bk: int = DEFAULT_BK, interpret: bool = True,
                 out_dtype=None):
    """a: (M, K) @ b: (K, N) -> (M, N), tiled with fp32 accumulation."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    out_dtype = out_dtype or a.dtype

    return pl.pallas_call(
        functools.partial(_matmul_kernel, num_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
