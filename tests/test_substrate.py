"""Substrate tests: optimizer, schedules, data pipeline, checkpointing
(incl. async + elastic reshard), gradient compression, end-to-end training
loss decrease, and greedy generation."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.data import SyntheticLMData, make_batch_specs
from repro.models import build
from repro.optim import adamw_init, adamw_update, clip_by_global_norm, \
    compress_int8, cosine_schedule, decompress_int8, \
    error_feedback_update, linear_schedule, wsd_schedule
from repro.train import checkpoint as ckpt
from repro.train.serve_step import greedy_generate
from repro.train.train_step import init_state, make_train_step

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab=128)


class TestOptim:
    def test_adamw_reduces_quadratic(self):
        params = {"w": jnp.array([3.0, -2.0])}
        state = adamw_init(params)
        for _ in range(200):
            grads = {"w": 2 * params["w"]}
            params, state, _ = adamw_update(params, grads, state, lr=0.05,
                                            weight_decay=0.0)
        assert float(jnp.max(jnp.abs(params["w"]))) < 0.2

    def test_moment_dtype_bf16(self):
        params = {"w": jnp.ones((4,), jnp.float32)}
        state = adamw_init(params, moment_dtype="bfloat16")
        assert state["mu"]["w"].dtype == jnp.bfloat16

    def test_clip_by_global_norm(self):
        g = {"a": jnp.full((10,), 10.0)}
        clipped, norm = clip_by_global_norm(g, 1.0)
        cn = float(jnp.sqrt(jnp.sum(clipped["a"] ** 2)))
        assert abs(cn - 1.0) < 1e-5
        assert float(norm) > 1.0

    def test_schedules_shape(self):
        for sched in (linear_schedule(1.0, 10, 100),
                      cosine_schedule(1.0, 10, 100),
                      wsd_schedule(1.0, 10, 100)):
            assert float(sched(0)) == pytest.approx(0.0, abs=1e-6)
            assert float(sched(10)) == pytest.approx(1.0, rel=1e-3)
            assert float(sched(99)) < 0.5

    def test_wsd_has_stable_plateau(self):
        sched = wsd_schedule(1.0, 10, 1000, decay_fraction=0.1)
        # stable phase: constant at peak
        assert float(sched(500)) == pytest.approx(1.0)
        assert float(sched(880)) == pytest.approx(1.0)
        # decay phase: rapidly down
        assert float(sched(990)) < 0.3


class TestGradCompression:
    def test_roundtrip_small_error(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (1000,))
        q, s = compress_int8(g)
        deq = decompress_int8(q, s)
        assert q.dtype == jnp.int8
        rel = float(jnp.linalg.norm(deq - g) / jnp.linalg.norm(g))
        assert rel < 0.02

    def test_error_feedback_unbiased_over_steps(self):
        """With constant gradient, EF-compressed updates average to the
        true gradient (residual stays bounded)."""
        g = {"w": jax.random.normal(jax.random.PRNGKey(1), (256,)) * 1e-3}
        res = {"w": jnp.zeros((256,), jnp.float32)}
        acc = jnp.zeros((256,))
        n = 50
        for _ in range(n):
            deq, res = error_feedback_update(g, res)
            acc = acc + deq["w"]
        err = float(jnp.linalg.norm(acc / n - g["w"])
                    / jnp.linalg.norm(g["w"]))
        assert err < 0.05
        assert float(jnp.linalg.norm(res["w"])) < \
            float(jnp.linalg.norm(g["w"])) * 2


class TestData:
    def test_deterministic_and_seekable(self):
        data = SyntheticLMData(TINY, batch=4, seq_len=32, seed=7)
        b1 = data.batch_at(10)
        b2 = data.batch_at(10)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        b3 = data.batch_at(11)
        assert not np.array_equal(b1["tokens"], b3["tokens"])

    def test_labels_are_next_tokens(self):
        data = SyntheticLMData(TINY, batch=2, seq_len=16)
        b = data.batch_at(0)
        assert b["tokens"].shape == b["labels"].shape == (2, 16)

    def test_prefetch_iterator(self):
        data = SyntheticLMData(TINY, batch=2, seq_len=8)
        it = data.iter_batches(start_step=5)
        first = next(it)
        np.testing.assert_array_equal(first["tokens"],
                                      data.batch_at(5)["tokens"])

    def test_batch_specs_match_real_batches(self):
        specs = make_batch_specs(TINY, batch=4, seq_len=32)
        data = SyntheticLMData(TINY, batch=4, seq_len=32)
        b = data.batch_at(0)
        for k, spec in specs.items():
            assert tuple(b[k].shape) == tuple(spec.shape), k


class TestCheckpoint:
    def test_roundtrip_exact(self, tmp_path):
        tree = {"a": jnp.arange(10, dtype=jnp.float32),
                "b": {"c": jnp.ones((3, 4), jnp.bfloat16)}}
        p = str(tmp_path / "ckpt_000001")
        ckpt.save(p, tree, step=1)
        like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            tree)
        restored, manifest = ckpt.restore(p, like)
        assert manifest["step"] == 1
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(tree["a"]))
        assert restored["b"]["c"].dtype == jnp.bfloat16

    def test_corruption_detected(self, tmp_path):
        tree = {"a": jnp.arange(8, dtype=jnp.float32)}
        p = str(tmp_path / "ckpt_000001")
        ckpt.save(p, tree)
        man = ckpt.load_manifest(p)
        man["leaves"]["a"]["hash"] = "0" * 32
        import json
        with open(os.path.join(p, "manifest.json"), "w") as f:
            json.dump(man, f)
        with pytest.raises(IOError):
            ckpt.restore(p, tree)

    def test_shape_mismatch_rejected(self, tmp_path):
        tree = {"a": jnp.zeros((4,))}
        p = str(tmp_path / "ckpt_000001")
        ckpt.save(p, tree)
        with pytest.raises(ValueError):
            ckpt.restore(p, {"a": jnp.zeros((5,))})

    def test_async_save(self, tmp_path):
        tree = {"a": jnp.arange(100, dtype=jnp.float32)}
        p = str(tmp_path / "ckpt_000002")
        saver = ckpt.AsyncCheckpointer()
        saver.save(p, tree, step=2)
        saver.wait()
        restored, man = ckpt.restore(p, tree)
        assert man["step"] == 2

    def test_latest_step_dir_and_retention(self, tmp_path):
        tree = {"a": jnp.zeros((2,))}
        for s in (1, 2, 3, 4):
            ckpt.save(str(tmp_path / f"ckpt_{s:06d}"), tree, step=s,
                      keep_last=2)
        latest = ckpt.latest_step_dir(str(tmp_path))
        assert latest.endswith("ckpt_000004")
        remaining = sorted(d for d in os.listdir(tmp_path)
                           if d.startswith("ckpt_"))
        assert remaining == ["ckpt_000003", "ckpt_000004"]

    def test_elastic_reshard_across_device_counts(self, tmp_path):
        """Save unsharded, restore with an explicit (1-device) sharding —
        the elastic path; multi-device resharding is exercised in
        tests/test_distributed.py subprocesses."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
        p = str(tmp_path / "ckpt_000001")
        ckpt.save(p, tree)
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        sh = {"w": NamedSharding(mesh, P("data", None))}
        restored, _ = ckpt.restore(p, tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))


class TestEndToEnd:
    def test_loss_decreases(self):
        model = build(TINY)
        state = init_state(model, jax.random.PRNGKey(0))
        data = SyntheticLMData(TINY, batch=8, seq_len=32)
        step = jax.jit(make_train_step(model, lr=3e-3))
        losses = []
        for i in range(30):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        first = sum(losses[:5]) / 5
        last = sum(losses[-5:]) / 5
        assert last < first - 0.25, (first, last)

    def test_grad_accum_matches_full_batch(self):
        """microbatches=2 must equal the full-batch gradient step."""
        model = build(TINY)
        state0 = init_state(model, jax.random.PRNGKey(0))
        data = SyntheticLMData(TINY, batch=8, seq_len=16)
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
        s1, m1 = jax.jit(make_train_step(model, lr=1e-2))(state0, batch)
        s2, m2 = jax.jit(make_train_step(model, lr=1e-2,
                                         microbatches=2))(state0, batch)
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=2e-5, rtol=2e-4)

    def test_compressed_grads_still_learn(self):
        model = build(TINY)
        state = init_state(model, jax.random.PRNGKey(0),
                           compress_grads=True)
        data = SyntheticLMData(TINY, batch=8, seq_len=32)
        step = jax.jit(make_train_step(model, lr=3e-3,
                                       compress_grads=True))
        losses = []
        for i in range(25):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert sum(losses[-5:]) / 5 < sum(losses[:5]) / 5 - 0.2

    def test_greedy_generate_shapes(self):
        model = build(TINY)
        params = model.init(jax.random.PRNGKey(0))
        prompt = jnp.ones((2, 8), jnp.int32)
        gen = greedy_generate(model, params, prompt, max_new=5)
        out = gen.tokens
        assert out.shape == (2, 5)
        assert bool(jnp.all(out >= 0)) and bool(jnp.all(out < TINY.vocab))
        assert gen.logits.shape == (2, 5, TINY.vocab)
        assert bool(jnp.all(out == jnp.argmax(gen.logits, axis=-1)))
        assert gen.compiles_after_first_step == 0

    def test_greedy_generate_counts_compiles_in_the_loop(self):
        """A prefill whose logits dtype differs from the decode step's makes
        the loop compile a second argmax: the counter must see it.  (A
        vocab no other test uses, so that argmax is not compiled yet.)"""
        model = build(TINY.replace(vocab=97))

        class Bf16Prefill:
            init_cache = model.init_cache
            decode_step = model.decode_step

            def prefill(self, *a, **kw):
                logits, cache = model.prefill(*a, **kw)
                return logits.astype(jnp.bfloat16), cache

        params = model.init(jax.random.PRNGKey(0))
        gen = greedy_generate(Bf16Prefill(), params,
                              jnp.ones((2, 8), jnp.int32), max_new=4)
        assert gen.compiles_after_first_step > 0

    def test_train_resume_from_checkpoint_exact(self, tmp_path):
        """Train 5 steps, checkpoint, train 5 more; vs. train 10 straight:
        identical params (deterministic data + saved step)."""
        model = build(TINY)
        data = SyntheticLMData(TINY, batch=4, seq_len=16)
        step = jax.jit(make_train_step(model, lr=1e-3))

        def run(state, start, n):
            for i in range(start, start + n):
                batch = {k: jnp.asarray(v)
                         for k, v in data.batch_at(i).items()}
                state, _ = step(state, batch)
            return state

        s_full = run(init_state(model, jax.random.PRNGKey(0)), 0, 10)
        s_half = run(init_state(model, jax.random.PRNGKey(0)), 0, 5)
        p = str(tmp_path / "ckpt_000005")
        ckpt.save(p, s_half, step=5)
        like = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), s_half)
        s_restored, man = ckpt.restore(p, like)
        s_resumed = run(s_restored, man["step"], 5)
        for a, b in zip(jax.tree.leaves(s_full["params"]),
                        jax.tree.leaves(s_resumed["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


class TestCompileCache:
    """launch/compile_cache.py: the environment's directory when set,
    else a fixed path inside the checkout."""

    def _run(self, tmp_path, env_dir):
        import subprocess
        import sys
        import textwrap
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        code = textwrap.dedent("""
            import jax, jax.numpy as jnp
            from repro.launch.compile_cache import enable_compile_cache
            used = enable_compile_cache()
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
            print(used)
            print(jax.config.jax_compilation_cache_dir)
        """ if env_dir else """
            import jax
            from repro.launch.compile_cache import enable_compile_cache
            print(enable_compile_cache())
            print(jax.config.jax_compilation_cache_dir)
        """)
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(PYTHONPATH=os.path.join(repo, "src"), JAX_PLATFORMS="cpu")
        if env_dir:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=tmp_path, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr
        return repo, out.stdout.split()

    def test_environment_directory_wins(self, tmp_path):
        want = str(tmp_path / "cc")
        _, (used, config) = self._run(tmp_path, want)
        assert used == config == want
        assert os.listdir(want)          # entries written there

    def test_default_is_a_fixed_path_in_the_checkout(self, tmp_path):
        repo, (used, config) = self._run(tmp_path, None)
        assert used == config == os.path.join(repo, ".jax_cache")
