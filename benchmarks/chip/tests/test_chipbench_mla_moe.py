"""DeepSeek-V3's yardstick and reference: the operation and byte counts of
``flops_mla_moe`` against numbers worked out by hand at the cell's shapes,
the plain reference against the program at a small size, and a whole run
of a small cell through the harness and its ``serve_routed`` job, correct
with the program and not with the fp8 control in its place nor with the
timed path broken."""
import dataclasses
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import control, flops_mla_moe, harness
from benchmarks.chip.jobs import serve_routed
from benchmarks.chip.reference import mla_moe
from benchmarks.chip.weights import key_from_seed, make_weights
from repro.configs.base import ModelConfig
from repro.models import build

DATA = Path(__file__).resolve().parent / "data"
CONFIG = harness.load_json(harness.ROOT / "benchmarks/chip/configs/"
                           "deepseek-v3-671b.json")
M = CONFIG["model"]

# per token, multiply-adds: MLA q down 7168 * 1536 = 11,010,048, q up
# 1536 * 128 * 192 = 37,748,736, latent and rope key 7168 * 576 =
# 4,128,768, key and value up 512 * 128 * 256 = 16,777,216, output
# 128 * 128 * 7168 = 117,440,512 -> 187,105,280
MLA = 187_105_280
DENSE_FFN = 3 * 7168 * 18432                   # 396,361,728
EXPERT = 3 * 7168 * 2048                       # 44,040,192
# router 7168 * 256, the shared expert, and 8 * 8 / 256 of an expert
MOE = 7168 * 256 + EXPERT + EXPERT // 4        # 56,885,248
TOKEN = 3 * (MLA + DENSE_FFN) + 4 * (MLA + MOE)    # 2,726,363,136
HEAD = 7168 * 32_320                           # 231,669,760


def test_per_token_counts_by_hand():
    assert flops_mla_moe.mla_macs(M) == MLA
    assert flops_mla_moe.moe_macs(M) == MOE
    assert flops_mla_moe.token_macs(M) == TOKEN
    assert 2 * TOKEN == 5_452_726_272              # 5.45 GFLOP a token


def test_prefill_flops_by_hand():
    # 4,096 * 4,097 / 2 causal pairs, 2 * 128 heads * (128 + 64 + 128)
    # operations each, in each of 7 layers
    scores = 8_390_656 * 81_920
    per_seq = 2 * TOKEN * 4096 + 7 * scores + 2 * HEAD
    want = 4 * per_seq
    assert want == 108_585_471_705_088             # 1.09e14
    assert flops_mla_moe.prefill_flops(M, 4, 4096) == want
    # at the v5e's 197 TF/s: 0.55 s
    assert 0.55 < want / 197e12 < 0.552


def test_each_phase_counts_its_smaller_form():
    pair = flops_mla_moe.expanded_pair_flops(M)
    key = flops_mla_moe.absorbed_key_flops(M)
    assert (pair, key) == (81_920, 2 * 128 * 1088)
    assert flops_mla_moe.prefill_score_flops(M, 10) == 55 * pair
    assert flops_mla_moe.decode_score_flops(M, 9) == 10 * key


def test_decode_bytes_by_hand():
    mla = (MLA + 512 + 1536) * 2                   # with the latent norms
    norms = 2 * 7168 * 2
    dense = mla + norms + DENSE_FFN * 2            # 1,166,966,784
    # 8 held experts, each reached by 4 tokens with chance
    # 1 - (31/32)^4 = 125,055 / 1,048,576; 88,080,384 bytes each
    touched = 84 * 8 * 125_055
    moe = mla + norms + 7168 * 256 * 4 + 256 * 4 + EXPERT * 2 + touched
    weights = 3 * dense + 4 * moe + 7168 * 2 + HEAD * 2 + 4 * 7168 * 2
    assert dense == 1_166_966_784
    assert weights == 6_179_118_464
    assert flops_mla_moe.weight_bytes(M, 4) == pytest.approx(weights)
    # 4097 live latents read and one written, 576 * 2 bytes, 4 sequences,
    # 7 layers
    cache = 7 * 4 * 1152 * 4098
    sb = CONFIG["state_bytes"]
    assert flops_mla_moe.decode_bytes(M, sb, 4, 4096) == pytest.approx(
        weights + cache)
    assert 6.31e9 < weights + cache < 6.32e9
    least = flops_mla_moe.decode_least_s(M, sb, 4, 4096, 197e12, 819e9)
    assert least == pytest.approx((weights + cache) / 819e9)   # 7.7 ms


def test_readers_read_a_dsv3_summary():
    calls = {"jit_decode_step": {"device_s": 127 * 0.0154, "count": 127},
             "jit_prefill": {"device_s": 1.1, "count": 1}}
    reading = {"model": M, "state_bytes": CONFIG["state_bytes"],
               "shape": {"batch": 4, "prompt_len": 4096, "new_tokens": 128},
               "peaks": harness.peaks_for("TPU v5 lite"),
               "summary": {"calls": calls, "busy_s": 3.0, "window_s": 3.1}}
    got = {n: harness.load_reader(n)(reading)
           for n in ("mfu.prefill.mla_moe", "mfu.decode.mla_moe",
                     "step_roofline.decode.mla_moe")}
    assert got["mfu.prefill.mla_moe"] == pytest.approx(
        100 * 108_585_471_705_088 / (1.1 * 197e12))
    assert 0 < got["mfu.decode.mla_moe"] < 5
    assert got["step_roofline.decode.mla_moe"] == pytest.approx(50, abs=0.5)


def tiny_model():
    tiny = harness.load_json(DATA / "tiny-dsv3.json")
    m = dict(tiny["model"], pattern=tuple(tiny["model"]["pattern"]))
    cfg = ModelConfig(**m)
    model = build(cfg)
    shapes = jax.eval_shape(model.init, key_from_seed(0, 0))
    return cfg, model, make_weights(shapes, tiny["init"], 5)


def test_prefill_then_decode_matches_reference():
    """float32 at a small size: the program's sequence prefill (expanded
    MLA) and decode through the latent cache (absorbed) against the
    reference's full forward, to float32 rounding."""
    cfg, model, params = tiny_model()
    b, p, n = 2, 11, 7          # past the YaRN range of 8 positions
    seq = np.random.default_rng(0).integers(0, cfg.vocab, (b, p + n))
    seq = seq.astype(np.int32)
    cache = model.init_cache(b, p + n)
    logits, cache = model.prefill(params, jnp.asarray(seq[:, :p]), cache)
    got = [logits]
    for i in range(n - 1):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray(seq[:, p + i:p + i + 1]),
            jnp.int32(p + i))
        got.append(logits)
    got = np.stack([np.asarray(g, np.float32) for g in got], axis=1)
    m = dataclasses.asdict(cfg)
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(mla_moe.forward(
            params, m, jnp.asarray(seq[r, :-1]), p - 1)) for r in range(b)])
    assert got.shape == want.shape == (b, n, cfg.vocab)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


class CpuChip:
    platform = "cpu"
    device_kind = "cpu"


@pytest.fixture
def tiny_root(tmp_path):
    traffic = tmp_path / "benchmarks" / "chip" / "traffic"
    traffic.mkdir(parents=True)
    (traffic / "tiny.json").write_text(json.dumps({
        "job": "serve_routed", "batch": 3, "prompt_len": 12,
        "new_tokens": 20, "check_requests": 3, "steps_ahead": 4}))
    bench = harness.load_benchmark()
    bench["configs"] = [{"name": "tiny-dsv3", "source": "test",
                         "file": "tiny-dsv3.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny-dsv3", "config": "tiny-dsv3",
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    shutil.copy(DATA / "tiny-dsv3.json", tmp_path)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def run_tiny(root, capsys):
    rc = harness.main(["--workload", "tiny-dsv3", "--seed",
                       str(2**31 + 91), "--seconds", "1", "--trace", "0"],
                      time.perf_counter(),
                      chips_check=lambda n: [CpuChip()], root=root)
    assert rc == 0
    return json.loads(capsys.readouterr()[0].strip().splitlines()[-1])


@pytest.mark.parametrize("in_place", ["program", "fp8_control",
                                      "state_unchanged"])
def test_small_run_decides_correct(tiny_root, capsys, monkeypatch, in_place):
    """A whole run of a small cell (float32, see tiny-dsv3.json): the
    program passes both limits; the fp8 control in the program's place
    fails the mean's, and a decode step that drops its cache writes
    fails both."""
    import repro.models
    from repro.models.model import LanguageModel

    if in_place == "fp8_control":
        res = harness.resolve(harness.load_benchmark(tiny_root), "tiny-dsv3",
                              tiny_root)
        build_ = repro.models.build
        monkeypatch.setattr(repro.models, "build", lambda cfg: (
            control.ReferenceInPlace(build_(cfg), res["reference"],
                                     res["config"]["model"])))
    if in_place == "state_unchanged":
        step = LanguageModel.decode_step
        monkeypatch.setattr(LanguageModel, "decode_step", lambda self, params,
                            cache, tokens, pos, **kw: (step(
                                self, params, cache, tokens, pos,
                                **kw)[0], cache))
    line = run_tiny(tiny_root, capsys)
    check = line["check"]
    assert set(check) == set(serve_routed.CHECKS)
    assert line["correct"] is (in_place == "program"), check
    over = {k for k, c in check.items() if c["value"] > c["limit"]}
    if in_place == "fp8_control":
        assert "mean_logit_gap" in over, check
    else:
        assert over == (set() if in_place == "program" else set(check))
    assert line["attempted"] > 0


def test_routed_readings_straddle_the_limits(tiny_root):
    """The readings the limits are set from: over a few seeds the served
    tokens' numbers lie under both limits, and the fp8 control's mean over
    its limit."""
    res = harness.resolve(harness.load_benchmark(tiny_root), "tiny-dsv3",
                          tiny_root)
    limits = res["config"]["check"]
    ctx = harness.Context(res, 0, 0.0, False, time.perf_counter(), "")
    rows = list(serve_routed.readings(ctx, [5, 6, 2**33 + 1]))
    for r in rows:
        assert r["tokens_checked"] == 3 * 20
        assert all(r["served"][k] <= limits[k] for k in serve_routed.CHECKS)
        assert r["control"]["mean_logit_gap"] > limits["mean_logit_gap"]
        assert r["control"]["max_logit_gap"] >= r["control"][
            "mean_logit_gap"]


def test_token_gaps_and_summary_by_hand():
    """The gap of each served token is the reference's best logit less
    its logit there; the summary is their mean and largest."""
    class Ref:
        @staticmethod
        def forward(params, m, tokens, first, fp8=False):
            rows = jnp.arange(tokens.shape[0] - first)[:, None]
            logits = jnp.asarray([[0.0, 1.0, 3.0]]) + 0 * rows
            return logits + (4.0 if fp8 else 0.0) * (jnp.arange(3) == 0)

    batch = type("B", (), {"prompts": np.zeros((1, 2), np.int32),
                           "tokens": np.asarray([[2, 1, 0]], np.int32)})
    served, ctl = serve_routed.token_gaps(Ref, None, {}, [(batch, 0)], 2,
                                          control=True)
    np.testing.assert_allclose(served, [0.0, 2.0, 3.0])
    np.testing.assert_allclose(ctl, [3.0, 3.0, 3.0])   # fp8 puts id 0 first
    assert serve_routed.summary(served) == {
        "mean_logit_gap": pytest.approx(5 / 3), "max_logit_gap": 3.0}
