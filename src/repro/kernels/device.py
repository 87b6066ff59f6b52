"""Where a Pallas kernel runs: compiled for the TPU, interpreted elsewhere.

Every public op in ``kernels/*/ops.py`` decides its mode here, from the
platform of the default device, unless the caller passes ``interpret``.
An interpreted kernel (the CPU test path) may hand a shape it does not
tile to its reference; a compiled kernel raises instead, so a run on the
chip never times the reference under the kernel's name.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` if given, else interpret unless the default device
    is a TPU."""
    if interpret is not None:
        return interpret
    return jax.devices()[0].platform != "tpu"


def use_reference(in_domain: bool, interpret: bool, what: str) -> bool:
    """True when the op should run its reference for this shape.

    Raises ``ValueError`` for a shape outside the kernel's domain when the
    kernel would be compiled."""
    if in_domain:
        return False
    if interpret:
        return True
    raise ValueError(f"{what}: shape outside the compiled kernel's domain")
