"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU, asserting output shapes and no NaNs (assignment
requirement), plus decode-path equivalence and full-config bookkeeping."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, SHAPES, all_cells, cell_applicable, \
    get_config, memory_len
from repro.models import build

SEQ = 16
BATCH = 2


def _batch(cfg, key):
    k1, k2 = jax.random.split(key)
    tokens = jax.random.randint(k1, (BATCH, SEQ), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    mlen = memory_len(cfg, SEQ)
    if mlen is not None:
        batch["memory_embeds"] = jax.random.normal(
            k2, (BATCH, max(mlen, 4), cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
class TestSmoke:
    def test_forward_shapes_no_nan(self, arch):
        cfg = get_config(arch, smoke=True)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = _batch(cfg, jax.random.PRNGKey(1))
        logits, aux = model.forward(params, batch["tokens"],
                                    memory_embeds=batch.get("memory_embeds"))
        assert logits.shape == (BATCH, SEQ, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits)))
        assert bool(jnp.isfinite(aux))

    def test_one_train_step(self, arch):
        """loss + grads + SGD step: finite loss, finite grads, params move."""
        cfg = get_config(arch, smoke=True)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = _batch(cfg, jax.random.PRNGKey(1))

        def loss(p):
            l, _ = model.loss_fn(p, batch)
            return l

        l0, grads = jax.value_and_grad(loss)(params)
        assert bool(jnp.isfinite(l0))
        gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                             for g in jax.tree.leaves(grads)))
        assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0.0
        new = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype),
                           params, grads)
        l1 = loss(new)
        assert bool(jnp.isfinite(l1))

    def test_decode_matches_forward(self, arch):
        cfg = get_config(arch, smoke=True)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = _batch(cfg, jax.random.PRNGKey(1))
        logits, _ = model.forward(params, batch["tokens"],
                                  memory_embeds=batch.get("memory_embeds"))
        cache = model.init_cache(BATCH, SEQ)
        last, _ = model.prefill(params, batch["tokens"], cache,
                                memory_embeds=batch.get("memory_embeds"))
        err = float(jnp.max(jnp.abs(last - logits[:, -1, :])))
        assert err < 5e-3, err

    def test_bf16_prefill_matches_forward(self, arch):
        """Every full config computes in bfloat16: prefill's scan must
        carry bf16 logits and agree with the forward pass."""
        cfg = get_config(arch, smoke=True).replace(dtype="bfloat16",
                                                   param_dtype="bfloat16")
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = _batch(cfg, jax.random.PRNGKey(1))
        logits, _ = model.forward(params, batch["tokens"],
                                  memory_embeds=batch.get("memory_embeds"))
        last, _ = model.prefill(params, batch["tokens"],
                                model.init_cache(BATCH, SEQ),
                                memory_embeds=batch.get("memory_embeds"))
        assert last.dtype == jnp.bfloat16
        ref = logits[:, -1, :].astype(jnp.float32)
        err = float(jnp.max(jnp.abs(last.astype(jnp.float32) - ref)))
        assert err <= 5e-2 * float(jnp.max(jnp.abs(ref))), err

    def test_full_config_bookkeeping(self, arch):
        """Full config: analytic param count sane, exact assigned dims."""
        cfg = get_config(arch)
        n = cfg.param_count()
        assert n > 1e6
        # spot-check assigned dimensions
        expected = {
            "mamba2-1.3b": (48, 2048, 50280),
            "h2o-danube-1.8b": (24, 2560, 32000),
            "minicpm-2b": (40, 2304, 122753),
            "deepseek-67b": (95, 8192, 102400),
            "llama3-405b": (126, 16384, 128256),
            "deepseek-v3-671b": (61, 7168, 129280),
            "qwen3-moe-235b-a22b": (94, 4096, 151936),
            "whisper-tiny": (4, 384, 51865),
            "recurrentgemma-9b": (38, 4096, 256000),
            "llama-3.2-vision-90b": (100, 8192, 128256),
        }[arch]
        assert (cfg.n_layers, cfg.d_model, cfg.vocab) == expected


class TestParamCountsVsBillions:
    """Analytic totals must land near the advertised model sizes."""

    @pytest.mark.parametrize("arch,lo,hi", [
        ("mamba2-1.3b", 1.1e9, 1.6e9),
        ("h2o-danube-1.8b", 1.5e9, 2.1e9),
        ("minicpm-2b", 2.0e9, 3.2e9),
        ("deepseek-67b", 60e9, 72e9),
        ("llama3-405b", 380e9, 430e9),
        ("deepseek-v3-671b", 620e9, 720e9),
        ("qwen3-moe-235b-a22b", 210e9, 260e9),
        ("whisper-tiny", 25e6, 60e6),
        ("recurrentgemma-9b", 8e9, 11e9),
        ("llama-3.2-vision-90b", 80e9, 100e9),
    ])
    def test_total_params(self, arch, lo, hi):
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B not in [{lo / 1e9}," \
                              f" {hi / 1e9}]B"

    def test_moe_active_params(self):
        """deepseek-v3: ~37B active of 671B; qwen3: ~22B active of 235B."""
        ds = get_config("deepseek-v3-671b")
        assert 30e9 <= ds.active_param_count() <= 45e9
        qw = get_config("qwen3-moe-235b-a22b")
        assert 18e9 <= qw.active_param_count() <= 28e9


class TestCellMatrix:
    def test_40_cells(self):
        cells = all_cells()
        assert len(cells) == 40
        runnable = [c for c in cells if c[2]]
        skipped = [c for c in cells if not c[2]]
        # long_500k runs only for the 3 sub-quadratic archs
        assert len(skipped) == 7
        assert all(s[1] == "long_500k" for s in skipped)
        assert len(runnable) == 33

    def test_decode_shapes_exist_for_encdec(self):
        """whisper is enc-dec (has a decoder) -> decode cells runnable."""
        ok, _ = cell_applicable("whisper-tiny", "decode_32k")
        assert ok
