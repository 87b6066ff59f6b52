"""Production mesh construction.

A function (not module-level constant) so importing this module never
touches jax device state.  Production target: TPU v5e pods of 256 chips
(16x16 ICI torus); multi-pod adds a leading DCI-connected "pod" axis.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with Auto axes: sharding follows the in/out
    shardings and ``with_sharding_constraint`` hints, and ops such as
    ``jnp.take`` need no explicit ``out_sharding``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}; have {len(devs)} — run "
            f"via repro.launch.dryrun (its main selects 512 placeholder "
            f"CPU devices)")
    # dry-run container: 512 placeholder devices; single-pod uses 256
    return make_mesh(shape, axes, devices=devs[:n])


def make_test_mesh(*, devices: Optional[int] = None, model: int = 2,
                   pod: int = 1):
    """Small mesh for CPU subprocess tests (8 host devices)."""
    n = devices or len(jax.devices())
    data = n // (model * pod)
    if pod > 1:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def mesh_spec_of(mesh) -> "object":
    """core.collectives.MeshSpec view of a jax Mesh (for the analytical
    collective model)."""
    from ..core.collectives import MeshSpec
    return MeshSpec(axes=tuple(
        (name, int(mesh.shape[name])) for name in mesh.axis_names))
