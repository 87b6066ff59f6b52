"""chip_smoke.py's phases rehearsed on the CPU at small sizes.

The script itself refuses to run without a TPU, so these tests call its
phase functions directly: the serving path at the smoke config, the four
kernels interpreted at real widths with few tokens, and the sharded path
on four placeholder CPU devices (in a subprocess, which can choose its
device count before JAX starts).
"""
import importlib.util
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.serve_step import Generation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_phase_at_smoke_size(capsys):
    _load().main_phase(smoke=True, batch=2, prompt_len=16, max_new=4)
    out = capsys.readouterr().out
    assert "compilations in the decode loop after its first step: 0" in out
    assert "prefill last-token logits vs forward" in out


def test_kernel_phase_interpreted_at_real_widths(capsys):
    _load().kernel_phase(tokens=256, interpret=True)
    out = capsys.readouterr().out
    for name in ("matmul", "rmsnorm", "flash_attention", "ssd"):
        assert f"[kernel] {name} " in out


def test_mesh_phase_on_four_cpu_devices():
    code = textwrap.dedent(f"""
        import jax
        jax.config.update("jax_num_cpu_devices", 4)
        import importlib.util
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      {SCRIPT!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.mesh_phase(smoke=True, batch=4, prompt_len=16, max_new=6)
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout
    for d in range(4):
        assert f"[mesh] device {d}: params " in out.stdout


def test_refuses_to_run_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def _gen(tokens, logits):
    return Generation(jnp.asarray(tokens), jnp.asarray(logits, jnp.float32),
                      0)


def test_agree_allows_divergence_only_at_a_near_tie():
    cs = _load()
    logits = np.zeros((1, 3, 4), np.float32)
    logits[0, :, 1] = 10.0
    logits[0, 1, 2] = 9.9                      # step 1: a near tie
    tokens = np.array([[1, 1, 1]])
    assert cs._agree(_gen(tokens, logits), _gen(tokens, logits), 0.05) == 0
    other = logits.copy()
    other[0, 1, 2] = 10.05                     # tie broken the other way
    other[0, 2] = 0.0                          # then a different context
    assert cs._agree(_gen(tokens, logits),
                     _gen(np.array([[1, 2, 0]]), other), 0.05) == 1
    clear = logits.copy()
    clear[0, 1, 2] = 0.0                       # no tie: a real disagreement
    with pytest.raises(cs.PhaseError):
        cs._agree(_gen(tokens, clear), _gen(np.array([[1, 2, 0]]), other),
                  0.05)
