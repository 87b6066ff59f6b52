"""Public SSD op: Pallas chunked scan with jnp-scan fallback."""
from __future__ import annotations

from typing import Optional

from ..device import resolve_interpret, use_reference
from . import kernel, ref


def ssd_scan(x, dt, a_log, b, c, *, chunk: int = kernel.DEFAULT_CHUNK,
             use_kernel: bool = True, interpret: Optional[bool] = None,
             unroll_heads: bool = False, head_blocks: int = 0):
    """Mamba2 SSD: x (B,S,H,P), dt (B,S,H) > 0, a_log (H,), b/c (B,S,N).

    Paths: Pallas kernel (TPU target) > chunked jnp (XLA fallback /
    dry-run) > exact sequential scan (odd lengths)."""
    interpret = resolve_interpret(interpret)
    s = x.shape[1]
    eff_chunk = min(chunk, s)
    # compiled blocks put the chunk on the lane axis of dt: a multiple of
    # 128 or the whole sequence
    in_domain = s % eff_chunk == 0 and (
        interpret or eff_chunk == s or eff_chunk % 128 == 0)
    if use_kernel and not use_reference(
            in_domain, interpret, f"ssd seq {s} chunk {eff_chunk}"):
        return kernel.ssd(x, dt, a_log, b, c, chunk=eff_chunk,
                          interpret=interpret)
    if s % eff_chunk != 0:
        return ref.ssd_scan_ref(x, dt, a_log, b, c)
    return ref.ssd_chunked_jnp(x, dt, a_log, b, c, chunk=eff_chunk,
                               unroll_heads=unroll_heads,
                               head_blocks=head_blocks)
