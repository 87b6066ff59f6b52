"""Public RMSNorm op: flattens leading dims, dispatches kernel or oracle."""
from __future__ import annotations

from typing import Optional

from ..device import resolve_interpret, use_reference
from . import kernel, ref


def rmsnorm(x, w, *, eps: float = 1e-6, use_kernel: bool = True,
            interpret: Optional[bool] = None):
    """x: (..., D), w: (D,)."""
    interpret = resolve_interpret(interpret)
    d = x.shape[-1]
    if not use_kernel or use_reference(x.ndim >= 2 and d % 8 == 0,
                                       interpret, f"rmsnorm {x.shape}"):
        return ref.rmsnorm(x, w, eps=eps)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, d)
    out = kernel.rmsnorm_2d(x2, w, eps=eps, interpret=interpret)
    return out.reshape(*lead, d)
