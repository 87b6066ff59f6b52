"""Public flash-attention op: kernel on TPU, interpret-mode kernel on CPU,
where shapes the kernel does not tile run the reference instead."""
from __future__ import annotations

from typing import Optional

from ..device import resolve_interpret, use_reference
from . import kernel, ref


def flash_attention(q, k, v, *, sm_scale: Optional[float] = None,
                    causal: bool = True, window: int = 0,
                    block_q: int = kernel.DEFAULT_BLOCK_Q,
                    block_kv: int = kernel.DEFAULT_BLOCK_KV,
                    use_kernel: bool = True,
                    interpret: Optional[bool] = None):
    """Batched multi-head attention with GQA, causal & sliding-window.

    q: (B, Hq, S, D); k, v: (B, Hkv, S, D) -> (B, Hq, S, D).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    interpret = resolve_interpret(interpret)
    s = q.shape[2]
    if not use_kernel or use_reference(s % 8 == 0, interpret,
                                       f"flash_attention seq {s}"):
        return ref.attention(q, k, v, sm_scale=sm_scale, causal=causal,
                             window=window)
    return kernel.mha(q, k, v, sm_scale=sm_scale, causal=causal,
                      window=window, block_q=block_q, block_kv=block_kv,
                      interpret=interpret)
