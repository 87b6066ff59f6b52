"""Per-kernel validation: shape/dtype sweeps vs pure-jnp oracles,
interpret=True (TPU kernels executed in Python on CPU).

The exhaustive interpret-mode sweeps take minutes and are marked ``slow``;
the fast tier-1 gate (-m "not slow") keeps one cheap test per kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import kernel as fa_kernel, ops as fa_ops, \
    ref as fa_ref
from repro.kernels.matmul import kernel as mm_kernel, ops as mm_ops, \
    ref as mm_ref
from repro.kernels.rmsnorm import kernel as rms_kernel, ops as rms_ops, \
    ref as rms_ref
from repro.kernels.ssd import kernel as ssd_kernel, ops as ssd_ops, \
    ref as ssd_ref

KEY = jax.random.PRNGKey(42)

TOL = {jnp.float32: 5e-5, jnp.bfloat16: 5e-2}


def tol_for(dtype):
    return TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]


class TestFlashAttention:
    @pytest.mark.slow
    @pytest.mark.parametrize("b,hq,hkv,s,d", [
        (1, 2, 2, 128, 64),
        (2, 4, 2, 256, 64),     # GQA group 2
        (1, 8, 1, 128, 32),     # MQA
        (1, 2, 2, 384, 128),    # non-pow2 seq blocks
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_sweep(self, b, hq, hkv, s, d, dtype):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
        k = jax.random.normal(ks[1], (b, hkv, s, d), dtype)
        v = jax.random.normal(ks[2], (b, hkv, s, d), dtype)
        out = fa_kernel.mha(q, k, v, sm_scale=d ** -0.5, causal=True,
                            block_q=64, block_kv=64)
        exp = fa_ref.attention(q, k, v, sm_scale=d ** -0.5, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            atol=tol_for(dtype), rtol=tol_for(dtype))

    @pytest.mark.slow
    @pytest.mark.parametrize("window", [32, 64, 200])
    def test_sliding_window(self, window):
        b, h, s, d = 1, 2, 256, 64
        ks = jax.random.split(KEY, 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
                   for kk in ks)
        out = fa_kernel.mha(q, k, v, sm_scale=d ** -0.5, causal=True,
                            window=window, block_q=64, block_kv=64)
        exp = fa_ref.attention(q, k, v, sm_scale=d ** -0.5, causal=True,
                               window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=5e-5, rtol=5e-5)

    def test_non_causal(self):
        b, h, s, d = 1, 2, 128, 64
        ks = jax.random.split(KEY, 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
                   for kk in ks)
        out = fa_kernel.mha(q, k, v, sm_scale=d ** -0.5, causal=False,
                            block_q=64, block_kv=64)
        exp = fa_ref.attention(q, k, v, sm_scale=d ** -0.5, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=5e-5, rtol=5e-5)

    @pytest.mark.slow
    def test_block_size_invariance(self):
        """Output must not depend on the BlockSpec tiling."""
        b, h, s, d = 1, 2, 256, 64
        ks = jax.random.split(KEY, 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
                   for kk in ks)
        outs = [fa_kernel.mha(q, k, v, sm_scale=0.125, causal=True,
                              block_q=bq, block_kv=bk)
                for bq, bk in ((32, 32), (64, 128), (128, 64), (256, 256))]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       atol=2e-5, rtol=2e-5)

    def test_ops_fallback_odd_seq(self):
        """Odd sequence lengths route to the oracle transparently."""
        b, h, s, d = 1, 2, 100, 64
        ks = jax.random.split(KEY, 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.float32)
                   for kk in ks)
        out = fa_ops.flash_attention(q, k, v)
        exp = fa_ref.attention(q, k, v, sm_scale=d ** -0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)


class TestMatmul:
    @pytest.mark.slow
    @pytest.mark.parametrize("m,n,k", [
        (128, 128, 128), (256, 512, 384), (512, 256, 1024), (64, 64, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, m, n, k, dtype):
        a = jax.random.normal(KEY, (m, k), dtype)
        b = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
        out = mm_kernel.matmul_tiled(a, b, bm=128, bn=128, bk=128)
        exp = mm_ref.matmul(a, b)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            atol=tol_for(dtype) * k ** 0.5, rtol=tol_for(dtype))

    @pytest.mark.slow
    def test_block_invariance(self):
        a = jax.random.normal(KEY, (256, 256), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(1), (256, 256), jnp.float32)
        outs = [mm_kernel.matmul_tiled(a, b, bm=bm, bn=bn, bk=bk)
                for bm, bn, bk in ((64, 64, 64), (128, 256, 128),
                                   (256, 128, 256))]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       atol=1e-4, rtol=1e-5)

    def test_model_driven_block_selection(self):
        """ops.select_blocks returns the analytical argmin (paper's
        adaptive tile selection on TPU BlockSpecs)."""
        best, costs = mm_ops.select_blocks(4096, 4096, 4096)
        assert costs[best] == min(costs.values())
        assert len(costs) >= 4


class TestRMSNorm:
    @pytest.mark.slow
    @pytest.mark.parametrize("r,d", [(8, 64), (256, 512), (1024, 128),
                                     (100, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, r, d, dtype):
        x = jax.random.normal(KEY, (r, d), dtype)
        w = jax.random.normal(jax.random.PRNGKey(7), (d,), dtype)
        out = rms_kernel.rmsnorm_2d(x, w, block_rows=64)
        exp = rms_ref.rmsnorm(x, w)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            atol=tol_for(dtype), rtol=tol_for(dtype))

    def test_leading_dims_flatten(self):
        x = jax.random.normal(KEY, (2, 3, 16, 64), jnp.float32)
        w = jnp.ones((64,), jnp.float32)
        out = rms_ops.rmsnorm(x, w)
        exp = rms_ref.rmsnorm(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)

    def test_unit_weight_normalizes(self):
        x = 3.0 * jax.random.normal(KEY, (64, 128), jnp.float32)
        out = rms_ops.rmsnorm(x, jnp.ones((128,)))
        rms = np.sqrt(np.mean(np.asarray(out) ** 2, axis=-1))
        np.testing.assert_allclose(rms, 1.0, atol=1e-3)


class TestSSD:
    @pytest.mark.slow
    @pytest.mark.parametrize("b,s,h,p,n,chunk", [
        (1, 128, 2, 16, 32, 32),
        (2, 256, 3, 16, 32, 64),
        (1, 256, 2, 32, 64, 128),
        (1, 64, 1, 8, 16, 64),      # chunk == seq
    ])
    def test_sweep_vs_sequential_scan(self, b, s, h, p, n, chunk):
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a_log = 0.5 * jax.random.normal(ks[2], (h,))
        bm = jax.random.normal(ks[3], (b, s, n)) / np.sqrt(n)
        cm = jax.random.normal(ks[4], (b, s, n)) / np.sqrt(n)
        out = ssd_kernel.ssd(x, dt, a_log, bm, cm, chunk=chunk)
        exp = ssd_ref.ssd_scan_ref(x, dt, a_log, bm, cm)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=5e-4, rtol=5e-3)

    @pytest.mark.slow
    def test_chunk_invariance(self):
        """Chunked SSD must equal the recurrence regardless of chunking."""
        b, s, h, p, n = 1, 128, 2, 16, 32
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a_log = 0.5 * jax.random.normal(ks[2], (h,))
        bm = jax.random.normal(ks[3], (b, s, n)) / np.sqrt(n)
        cm = jax.random.normal(ks[4], (b, s, n)) / np.sqrt(n)
        outs = [ssd_kernel.ssd(x, dt, a_log, bm, cm, chunk=c)
                for c in (32, 64, 128)]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       atol=2e-4, rtol=2e-3)

    def test_decay_stability(self):
        """Large dt*A: state must decay, outputs bounded (no NaN/Inf)."""
        b, s, h, p, n = 1, 128, 1, 8, 16
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
        dt = 10.0 * jnp.ones((b, s, h))
        a_log = jnp.ones((h,)) * 2.0     # strongly negative A
        bm = jax.random.normal(ks[3], (b, s, n))
        cm = jax.random.normal(ks[4], (b, s, n))
        out = ssd_kernel.ssd(x, dt, a_log, bm, cm, chunk=64)
        assert bool(jnp.all(jnp.isfinite(out)))

    def test_ops_fallback(self):
        """A seq that is not a multiple of the chunk takes the chunked jnp
        path, its tail padded with dt = 0: the exact scan's outputs."""
        b, s, h, p, n = 1, 100, 1, 8, 16
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
        a_log = 0.5 * jax.random.normal(ks[2], (h,))
        bm = jax.random.normal(ks[3], (b, s, n))
        cm = jax.random.normal(ks[4], (b, s, n))
        out = ssd_ops.ssd_scan(x, dt, a_log, bm, cm, chunk=64)
        exp = ssd_ref.ssd_scan_ref(x, dt, a_log, bm, cm)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=1e-5, rtol=1e-5)
