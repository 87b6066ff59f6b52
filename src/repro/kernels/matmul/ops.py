"""Public matmul op with block-size selection hooks.

``predict_block_time`` prices a candidate (bm, bn, bk) with the core
analytical model — the paper's adaptive tile selection (§IV-B) applied to
BlockSpec shapes."""
from __future__ import annotations

from typing import Optional, Tuple

from ..device import resolve_interpret, use_reference
from . import kernel, ref


def matmul(a, b, *, bm: int = kernel.DEFAULT_BM, bn: int = kernel.DEFAULT_BN,
           bk: int = kernel.DEFAULT_BK, use_kernel: bool = True,
           interpret: Optional[bool] = None, out_dtype=None):
    interpret = resolve_interpret(interpret)
    m, k = a.shape
    n = b.shape[1]
    if not use_kernel or use_reference(min(m, n, k) >= 8, interpret,
                                       f"matmul {m}x{k}x{n}"):
        return ref.matmul(a, b, out_dtype=out_dtype)
    return kernel.matmul_tiled(a, b, bm=bm, bn=bn, bk=bk,
                               interpret=interpret, out_dtype=out_dtype)


def predict_block_time(m: int, n: int, k: int,
                       blocks: Tuple[int, int, int],
                       precision: str = "bf16") -> float:
    """Analytical step-time for one (bm,bn,bk) BlockSpec on TPU v5e:
    Blackwell-style stage model re-derived for the MXU (DESIGN.md §3).

    Per grid step: T = max(T_mxu, (1-alpha) T_dma) + T_sync, where the
    working set (A tile + B tile + f32 acc) must fit VMEM (else spill
    penalty) and MXU utilization degrades for dims < 512 (pipeline
    fill of the 128x128 systolic array).
    """
    from repro.core import hardware
    from repro.core.hardware import BYTES_PER_ELEM
    hw = hardware.TPU_V5E
    bm, bn, bk = blocks
    eb = BYTES_PER_ELEM[precision]
    steps = -(-m // bm) * -(-n // bn) * -(-k // bk)

    mxu_util = 1.0
    for d in (bm, bn, bk):
        if d % 128 != 0:
            mxu_util *= d / (128 * -(-d // 128))
        if d < 512:
            mxu_util *= 0.85 + 0.15 * d / 512     # systolic fill fraction
    t_mxu = 2.0 * bm * bn * bk / (
        hw.sustained_flops(precision, matrix=True) * mxu_util)

    tile_bytes = (bm * bk + bk * bn) * eb
    working_set = tile_bytes * 2 + bm * bn * 4    # dbl-buffered + f32 acc
    t_dma = tile_bytes / hw.hbm_sustained_bw
    spill = 2.0 if working_set > hw.accum_capacity_bytes else 1.0
    t_sync = hw.cycles_to_seconds(hw.mbarrier_latency_cycles)
    t_step = max(t_mxu * spill,
                 (1 - hw.pipeline_overlap_alpha) * t_dma) + t_sync
    t_store = m * n * eb / hw.hbm_sustained_bw
    return hw.launch_latency_s + steps * t_step + t_store


def select_blocks(m: int, n: int, k: int, *,
                  candidates=((128, 128, 128), (256, 256, 256),
                              (256, 256, 512), (512, 512, 256)),
                  precision: str = "bf16"):
    """Model-driven argmin over BlockSpec candidates (paper's tile
    selection on TPU)."""
    costs = {c: predict_block_time(m, n, k, c, precision) for c in candidates}
    best = min(costs, key=costs.get)
    return best, costs
