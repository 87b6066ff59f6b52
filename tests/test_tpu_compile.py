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
from repro.obs import metrics

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


def test_danube_prefill_fits_one_v5e(one_chip):
    """The prefill chip_smoke.py serves: batch 8, a 1,024-token prompt into
    a 1,088-slot cache, full published config, in one sequence-parallel
    pass (no token loop left in the program)."""
    model = build(DANUBE)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(model.init_cache(8, 1088, abstract=True))
    tokens = jax.ShapeDtypeStruct((8, 1024), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.prefill).lower(params, tokens, cache).compile()
    # one while loop: the layer scan
    assert compiled.as_text().count(" while(") == 1
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


def _cell_model(name):
    """A model of the chip benchmark's configuration ``name`` as its file
    states it."""
    import json
    from pathlib import Path

    from repro.configs.base import ModelConfig

    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "configs" / f"{name}.json")
    m = json.loads(path.read_text())["model"]
    return build(ModelConfig(**dict(m, pattern=tuple(m["pattern"]))))


def _dsv3_cell_model():
    """The chip benchmark's cut DeepSeek-V3: 3 dense and 4 expert layers,
    8 of 256 experts held, a quarter of the vocabulary, published widths."""
    return _cell_model("deepseek-v3-671b")


@pytest.mark.parametrize("program", ["prefill", "decode_step"])
def test_dsv3_cell_fits_one_v5e(one_chip, program):
    """The DeepSeek-V3 cell's programs: batch 4, a 4,096-token prompt into
    a 4,224-slot latent cache, and one decode step; 9.1 GB of weights and
    what each program needs beside them fit one chip's HBM."""
    model = _dsv3_cell_model()

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(model.init_cache(4, 4224, abstract=True))
    if program == "prefill":
        args = (params, jax.ShapeDtypeStruct((4, 4096), jnp.int32,
                                             sharding=one_chip), cache)
    else:
        args = (params, cache,
                jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    compiled = jax.jit(getattr(model, program)).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 9.1e9      # 4,555 M bf16 params
    assert total < 15.75e9, total


@pytest.mark.parametrize("batch, prompt, cache_len", [
    (12, 128, 640),      # the mamba2 cell: one chunk of 128
    (4, 4096, 4112),     # a long prompt: 16 chunks of 256
])
def test_mamba2_prefill_fits_one_v5e(one_chip, batch, prompt, cache_len):
    """mamba2's prefill as the benchmark's file states the model: one
    chunked pass over the prompt (no token loop), the SSD tiles and state
    in float32, beside 2.7 GB of weights within one chip's HBM."""
    model = _cell_model("mamba2-1.3b")

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(model.init_cache(batch, cache_len, abstract=True))
    tokens = jax.ShapeDtypeStruct((batch, prompt), jnp.int32,
                                  sharding=one_chip)
    series = metrics.snapshot()["repro_jax_prefill_lowerings_total"]
    before = {s["labels"]["path"]: s["value"] for s in series["series"]}
    compiled = jax.jit(model.prefill).lower(params, tokens, cache).compile()
    series = metrics.snapshot()["repro_jax_prefill_lowerings_total"]
    after = {s["labels"]["path"]: s["value"] for s in series["series"]}
    assert after["sequence"] == before["sequence"] + 1
    assert after["token"] == before["token"]
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 2.6e9      # 1.3B bf16 params
    assert total < 15.75e9, total
