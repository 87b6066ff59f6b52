"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed with JAX: it compiles for a topology that is
described, not present, and refuses what the chip would refuse (block
shapes that do not tile, VMEM overuse, programs that do not fit HBM).
Interpret mode, which the kernel tests use, checks none of that.  Each
kernel is compiled with ``interpret=False`` at the widths ``chip_smoke.py``
runs; danube's decode step at its full config must fit one chip's HBM.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.matmul import kernel as mm_kernel
from repro.kernels.rmsnorm import kernel as rms_kernel
from repro.kernels.ssd import kernel as ssd_kernel
from repro.models import build

V5E_HBM_BYTES = 16e9
BF16, F32 = jnp.bfloat16, jnp.float32
DANUBE = get_config("h2o-danube-1.8b")
MAMBA2 = get_config("mamba2-1.3b")
TOKENS = 4096

_HD = DANUBE.head_dim
_SSD_H = MAMBA2.d_model * MAMBA2.ssm_expand // MAMBA2.ssm_headdim
KERNELS = {
    "matmul_tiled": (
        lambda a, b: mm_kernel.matmul_tiled(a, b, interpret=False),
        [((TOKENS, DANUBE.d_model), BF16), ((DANUBE.d_model, DANUBE.d_ff),
                                            BF16)]),
    "rmsnorm_2d": (
        lambda x, w: rms_kernel.rmsnorm_2d(x, w, interpret=False),
        [((TOKENS, DANUBE.d_model), BF16), ((DANUBE.d_model,), BF16)]),
    "mha": (
        lambda q, k, v: fa_kernel.mha(q, k, v, sm_scale=_HD ** -0.5,
                                      window=DANUBE.window, interpret=False),
        [((1, DANUBE.n_heads, TOKENS, _HD), BF16),
         ((1, DANUBE.n_kv_heads, TOKENS, _HD), BF16),
         ((1, DANUBE.n_kv_heads, TOKENS, _HD), BF16)]),
    "ssd": (
        lambda x, dt, a, b, c: ssd_kernel.ssd(
            x, dt, a, b, c, chunk=MAMBA2.ssm_chunk, interpret=False),
        [((1, TOKENS, _SSD_H, MAMBA2.ssm_headdim), F32),
         ((1, TOKENS, _SSD_H), F32), ((_SSD_H,), F32),
         ((1, TOKENS, MAMBA2.ssm_state), F32),
         ((1, TOKENS, MAMBA2.ssm_state), F32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with JAX's persistent cache
    off: an entry compiled for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_danube_decode_step_fits_one_v5e(one_chip):
    """The serving step chip_smoke.py runs: batch 8, a 1,088-slot cache
    (1,024 prompt + 64 new tokens), full published config."""
    model = build(DANUBE)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(model.init_cache(8, 1088, abstract=True))
    tok = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.decode_step).lower(params, cache, tok,
                                                pos).compile()
    # one-token decode is plain XLA at every config: no Pallas kernel is
    # expected in it (the flash kernel serves whole sequences only)
    assert "tpu_custom_call" not in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 3.5e9      # 1.8B bf16 params
    assert total < V5E_HBM_BYTES, total
