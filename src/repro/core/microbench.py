"""Runnable microbenchmarks (paper §V-A) for the host we can actually
measure: this container's CPU, through JAX.

The paper's loop is microbenchmark -> parameters -> predict -> validate.
On B200/MI300A we rely on the paper's published measurements; HERE we close
the loop with real timings: measure sustained GEMM throughput, streaming
bandwidth and dispatch overhead, then emit a calibrated ``cpu_host``
parameter file that core.generic / core.predict consume.

Everything uses the paper's measurement protocol (warmups, repeats, median;
core.validate.measure_median), with reduced defaults so the suite runs in
seconds on CI.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .hardware import CPU_HOST, HardwareParams, register
from .validate import measure_median


@dataclass
class MeasuredSuite:
    """One microbenchmark suite run: workloads + their measured medians.

    This is the calibration artifact that travels over the wire
    (``serve.codec.encode_suite``): a client measures kernels locally,
    ships the suite, and the server fits disclosed multipliers against
    its own predictions (paper §IV-D loop, served).  ``meta`` carries
    free-form floats about the run (repeats, warmups, ...).
    """

    name: str
    workloads: List["Workload"]
    measured_s: List[float]
    meta: Dict[str, float] = field(default_factory=dict)

    def __post_init__(self):
        if len(self.workloads) != len(self.measured_s):
            raise ValueError(
                f"suite {self.name!r}: {len(self.workloads)} workloads "
                f"vs {len(self.measured_s)} measurements")

    def __len__(self) -> int:
        return len(self.workloads)

    # ------------------------------------------------------- serialization
    def to_dict(self) -> Dict:
        return {"name": self.name,
                "workloads": [w.to_dict() for w in self.workloads],
                "measured_s": [float(t) for t in self.measured_s],
                "meta": dict(self.meta)}

    @staticmethod
    def from_dict(d: Dict) -> "MeasuredSuite":
        from .workload import Workload
        if not isinstance(d, dict):
            raise ValueError(f"suite payload must be a dict, got "
                             f"{type(d).__name__}")
        try:
            return MeasuredSuite(
                name=str(d["name"]),
                workloads=[Workload.from_dict(w) for w in d["workloads"]],
                measured_s=[float(t) for t in d["measured_s"]],
                meta={str(k): float(v)
                      for k, v in (d.get("meta") or {}).items()})
        except (KeyError, TypeError) as e:
            raise ValueError(f"bad suite payload: {e}") from None

DEFAULT_REPEATS = 15
DEFAULT_WARMUPS = 3


def _on_host(fn):
    """Run ``fn`` on the host CPU device, whatever the default device is:
    its results are registered as the host's (``cpu_host*``)."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_device(jax.devices("cpu")[0]):
            return fn(*args, **kwargs)
    return wrapped


def _timed(fn: Callable[[], jax.Array], *, repeats: int, warmups: int
           ) -> float:
    def run():
        fn().block_until_ready()
    med, _ = measure_median(run, repeats=repeats, warmups=warmups)
    return med


@_on_host
def measure_matmul_flops(n: int = 1024, *, dtype=jnp.float32,
                         repeats: int = DEFAULT_REPEATS,
                         warmups: int = DEFAULT_WARMUPS) -> float:
    """Sustained matrix FLOP/s: the tensor-throughput microbenchmark."""
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), dtype)
    b = jax.random.normal(key, (n, n), dtype)
    f = jax.jit(lambda x, y: x @ y)
    f(a, b).block_until_ready()
    t = _timed(lambda: f(a, b), repeats=repeats, warmups=warmups)
    return 2.0 * n ** 3 / t


@_on_host
def measure_stream_bandwidth(nbytes: int = 1 << 26, *,
                             repeats: int = DEFAULT_REPEATS,
                             warmups: int = DEFAULT_WARMUPS) -> float:
    """Sustained memory bandwidth via vector copy (2 bytes moved per
    element byte: read + write)."""
    n = nbytes // 4
    x = jnp.arange(n, dtype=jnp.float32)
    f = jax.jit(lambda v: v + 1.0)
    f(x).block_until_ready()
    t = _timed(lambda: f(x), repeats=repeats, warmups=warmups)
    return 2.0 * nbytes / t


@_on_host
def measure_launch_latency(*, repeats: int = 50,
                           warmups: int = 10) -> float:
    """Dispatch overhead: time an O(1) jitted program."""
    x = jnp.float32(1.0)
    f = jax.jit(lambda v: v * 2.0)
    f(x).block_until_ready()
    return _timed(lambda: f(x), repeats=repeats, warmups=warmups)


@_on_host
def measure_vector_flops(n: int = 1 << 22, *,
                         repeats: int = DEFAULT_REPEATS,
                         warmups: int = DEFAULT_WARMUPS) -> float:
    """Non-matrix FLOP throughput (fused elementwise chain, 8 flops/elem,
    high arithmetic intensity so bandwidth is not the limiter)."""
    x = jnp.ones((n,), jnp.float32)

    def chain(v):
        for _ in range(4):
            v = v * 1.0001 + 0.5
        return v
    f = jax.jit(chain)
    f(x).block_until_ready()
    t = _timed(lambda: f(x), repeats=repeats, warmups=warmups)
    return 8.0 * n / t


def calibrate_host(*, quick: bool = True) -> HardwareParams:
    """Run all host microbenchmarks and return a measured parameter file
    (registered as 'cpu_host_measured')."""
    reps = 7 if quick else DEFAULT_REPEATS
    gemm_n = 512 if quick else 1024
    stream_b = (1 << 24) if quick else (1 << 26)

    mat = measure_matmul_flops(gemm_n, repeats=reps)
    bw = measure_stream_bandwidth(stream_b, repeats=reps)
    vec = measure_vector_flops(1 << 20 if quick else 1 << 22, repeats=reps)
    launch = measure_launch_latency()

    hw = CPU_HOST.with_updates(
        name="cpu_host_measured",
        tensor_peak_flops={"fp32": mat * 1.15, "fp64": mat * 0.6},
        tensor_sustained_flops={"fp32": mat, "fp64": mat * 0.5},
        vector_peak_flops={"fp32": vec * 1.15},
        vector_sustained_flops={"fp32": vec},
        hbm_peak_bw=bw * 1.2,
        hbm_sustained_bw=bw,
        launch_latency_s=launch,
        working_set_scale_bytes=0.0,  # disable Eq. 16 blend on host (caches
                                      # already folded into sustained number)
    )
    # overwrite: re-calibration legitimately replaces the previous run
    register(hw, overwrite=True)
    return hw


# ---------------------------------------------------------------------------
# The host validation suite: real kernels with real measured medians.
# Mirrors the paper's workload classes (Table IX).
# ---------------------------------------------------------------------------

@_on_host
def host_suite(*, quick: bool = True):
    """Returns (workloads, measured_seconds, runnables) for the CPU host.

    Classes: memory-bound (copy/add/transpose/reduction), compute-bound
    (GEMMs), balanced (elementwise-heavy), stencil (2D 5-point).
    """
    from .workload import Workload

    reps = 7 if quick else 30
    warm = 2 if quick else 10
    key = jax.random.PRNGKey(0)

    cases = []  # (workload, thunk)

    def add_case(w: Workload, thunk: Callable[[], jax.Array]):
        thunk().block_until_ready()  # compile
        cases.append((w, thunk))

    # --- memory-bound -----------------------------------------------------
    n = (1 << 22) if quick else (1 << 24)
    x = jnp.arange(n, dtype=jnp.float32)
    y = jnp.ones((n,), jnp.float32)
    fc = jax.jit(lambda v: v * 1.0)
    fa = jax.jit(lambda a, b: a + b)
    fr = jax.jit(lambda v: jnp.sum(v))
    add_case(Workload(name="vec_copy", wclass="memory", flops=0.0,
                      bytes=8.0 * n, precision="fp32",
                      working_set_bytes=8.0 * n),
             lambda: fc(x))
    add_case(Workload(name="vec_add", wclass="memory", flops=float(n),
                      bytes=12.0 * n, precision="fp32",
                      working_set_bytes=12.0 * n),
             lambda: fa(x, y))
    add_case(Workload(name="reduction", wclass="memory", flops=float(n),
                      bytes=4.0 * n, precision="fp32",
                      working_set_bytes=4.0 * n),
             lambda: fr(x))
    m2 = 1024 if quick else 2048
    t2 = jax.random.normal(key, (m2, m2), jnp.float32)
    ft = jax.jit(lambda v: v.T.copy() if hasattr(v.T, "copy")
                 else jnp.transpose(v) + 0.0)
    ft = jax.jit(lambda v: jnp.transpose(v) + 0.0)
    add_case(Workload(name="transpose_2d", wclass="memory",
                      flops=float(m2 * m2), bytes=8.0 * m2 * m2,
                      precision="fp32", working_set_bytes=8.0 * m2 * m2),
             lambda: ft(t2))

    # --- compute-bound ----------------------------------------------------
    for gn in ((256, 512) if quick else (512, 1024, 2048)):
        a = jax.random.normal(key, (gn, gn), jnp.float32)
        b = jax.random.normal(key, (gn, gn), jnp.float32)
        fm = jax.jit(lambda p, q: p @ q)
        add_case(Workload(name=f"gemm_{gn}", wclass="compute",
                          flops=2.0 * gn ** 3, bytes=12.0 * gn * gn,
                          precision="fp32", matrix=True,
                          working_set_bytes=12.0 * gn * gn),
                 (lambda fm=fm, a=a, b=b: fm(a, b)))

    # --- balanced ----------------------------------------------------------
    nb = (1 << 20) if quick else (1 << 22)
    xb = jnp.linspace(0.0, 1.0, nb, dtype=jnp.float32)

    def bal(v):
        for _ in range(8):
            v = v * v + 0.1
        return v
    fb = jax.jit(bal)
    add_case(Workload(name="poly_chain", wclass="balanced",
                      flops=16.0 * nb, bytes=8.0 * nb, precision="fp32",
                      working_set_bytes=8.0 * nb),
             lambda: fb(xb))

    # --- stencil -----------------------------------------------------------
    sg = 512 if quick else 1024
    grid = jax.random.normal(key, (sg, sg), jnp.float32)

    def stencil(g):
        return (g
                + 0.1 * (jnp.roll(g, 1, 0) + jnp.roll(g, -1, 0)
                         + jnp.roll(g, 1, 1) + jnp.roll(g, -1, 1)
                         - 4.0 * g))
    fs = jax.jit(stencil)
    add_case(Workload(name="hotspot_like_stencil", wclass="stencil",
                      flops=7.0 * sg * sg, bytes=8.0 * sg * sg,
                      precision="fp32", working_set_bytes=8.0 * sg * sg),
             lambda: fs(grid))

    workloads = [w for w, _ in cases]
    measured = []
    for _, thunk in cases:
        def run(thunk=thunk):
            thunk().block_until_ready()
        med, _ = measure_median(run, repeats=reps, warmups=warm)
        measured.append(med)
    return workloads, measured


def host_suite_result(*, quick: bool = True) -> MeasuredSuite:
    """``host_suite`` packaged as a wire-shippable :class:`MeasuredSuite`
    (what ``PredictionClient.calibrate`` uploads)."""
    workloads, measured = host_suite(quick=quick)
    return MeasuredSuite(name="host_suite", workloads=workloads,
                         measured_s=measured,
                         meta={"quick": 1.0 if quick else 0.0})
