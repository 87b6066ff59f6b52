"""Device time by model layer (``scopes.py``): self time and buckets on
hand-made nested events, the op-name map on hand-written HLO, the model's
scopes in its compiled calls, and a small trace recorded on a TPU v5 lite
chip with the program's scopes (``profile_scopes.py --record``)."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import scopes, trace_reduce
from repro.configs.base import ModelConfig
from repro.models import build
from repro.models.layers import SCOPES

DATA = Path(__file__).resolve().parent / "data"

HLO = """HloModule jit_decode_step, entry_computation_layout={()->()}

%fused_computation.1 (param_0.1: f32[4]) -> bf16[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %add.3 = f32[4]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(decode_step)/layers/while/body/closed_call/mlp/add"}
  ROOT %convert.2 = bf16[4]{0} convert(%add.3)
}

%fused_computation.2 (param_0.2: bf16[4]) -> (bf16[4], bf16[4]) {
  %param_0.2 = bf16[4]{0} parameter(0)
  %dynamic-update-slice.5 = bf16[4]{0} dynamic-update-slice(%param_0.2, %param_0.2), metadata={op_name="jit(decode_step)/layers/while/body/closed_call/attn/cache/dynamic_update_slice"}
  %negate.1 = bf16[4]{0} negate(%param_0.2)
  ROOT %tuple.1 = (bf16[4]{0}, bf16[4]{0}) tuple(%dynamic-update-slice.5, %negate.1)
}

ENTRY %main.9 (p: f32[4]) -> bf16[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = bf16[4]{0:T(128)(2,1)} fusion(f32[4]{0} %p), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = (bf16[4]{0}, bf16[4]{0}) fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = bf16[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode_step)/head/dot_general"}
  %copy.7 = bf16[24,8,576,8,80]{4,3,2,1,0} copy(%fusion.1)
  ROOT %while.2 = bf16[4]{0} while(%fusion.1), condition=%c, body=%b, metadata={op_name="jit(decode_step)/layers/while"}
}
"""


def test_scopes_are_the_programs_list():
    assert scopes.SCOPES == SCOPES


def test_op_names_from_hlo():
    names = scopes.op_names(HLO)
    assert scopes.module_of(HLO) == "jit_decode_step"
    # no op_name of its own, root a convert: the add below it names it
    assert scopes.bucket(names["fusion.1"]) == "mlp"
    # a multi-output fusion: its tuple root's first operand
    assert scopes.bucket(names["fusion.2"]) == "cache"
    assert scopes.bucket(names["fusion.3"]) == "head"      # its own
    assert names["copy.7"] is None
    assert scopes.bucket(names["while.2"]) == "layers"
    assert names["p"] is None


@pytest.mark.parametrize("op_name, want", [
    ("jit(decode_step)/layers/while/body/closed_call/attn/cache/dus", "cache"),
    ("jit(decode_step)/layers/while/body/closed_call/attn/dot_general",
     "attn"),
    ("jit(decode_step)/layers/while", "layers"),
    ("jit(prefill)/while/body/add", "unscoped"),
    (None, "unscoped"),
])
def test_bucket_is_the_innermost_listed_scope(op_name, want):
    assert scopes.bucket(op_name) == want


def test_self_time_of_nested_ops():
    # a while [0, 100] holding a fusion [10, 40] that holds [20, 30], and
    # a copy [50, 70]; a sibling op [100, 110] after the while
    spans = [(0, 100), (10, 40), (20, 30), (50, 70), (100, 110)]
    assert scopes.self_times(spans) == [50, 20, 10, 20, 10]
    # children that touch each other are counted once each
    assert scopes.self_times([(0, 10), (0, 5), (5, 10)]) == [0, 5, 5]


def hand_made():
    """One decode step [100, 200] in a window [90, 300]: a layer scan
    (while) holding an attention fusion, its cache write and an MLP
    fusion; a whole-cache copy with no op_name; an unscoped op; the
    head.  Another call runs before the window."""
    names = {"while.2": "jit(decode_step)/layers/while",
             "fusion.1": "jit(decode_step)/layers/while/body/attn/dot",
             "dus.3": "jit(decode_step)/layers/while/body/attn/cache/dus",
             "fusion.4": "jit(decode_step)/layers/while/body/mlp/dot",
             "copy.5": None,
             "add.6": "jit(decode_step)/add",
             "fusion.7": "jit(decode_step)/head/dot"}
    ops = [[0, "%while.2 = (s32[]{:T(128)}) while(%t)", 100, 170],
           [0, "%fusion.1 = bf16[8,1,2560]{2,1,0} fusion(%a)", 105, 125],
           [0, "%dus.3 = bf16[8,576,8,80]{3,2,1,0} dynamic-update-slice(%b)",
            125, 135],
           [0, "%fusion.4 = bf16[8,1,2560]{2,1,0} fusion(%c)", 140, 160],
           [0, "%copy.5 = bf16[24,8,576,8,80]{4,3,2,1,0} copy(%d)", 170,
            180],
           [0, "%add.6 = s32[]{:T(128)} add(%e, %f)", 180, 182],
           [0, "%fusion.7 = bf16[8,32000]{1,0} fusion(%g)", 185, 195],
           [0, "%fusion.1 = bf16[8,1,2560]{2,1,0} fusion(%a)", 20, 30]]
    return {"host": [["window", 90, 300]],
            "modules": [[0, "jit_decode_step(3)", 100, 200],
                        [0, "jit_decode_step(3)", 10, 40]],
            "ops": [op + [names[scopes.instruction(op[1])]] for op in ops]}


def test_summarize_buckets_self_time():
    leaves = {("bf16", (24, 8, 576, 8, 80)), ("bf16", (8, 576, 8, 80))}
    call = scopes.summarize(hand_made(), leaves)["jit_decode_step"]
    assert call["count"] == 1                   # one step in the window
    assert call["device_s"] == pytest.approx(100e-9)
    t = call["self_s"]
    # the while's 70 less its body's 20 + 10 + 20
    assert t["layers"] == pytest.approx(20e-9)
    assert t["attn"] == pytest.approx(20e-9)
    assert t["cache"] == pytest.approx(20e-9)   # the write and the copy
    assert call["cache_by_shape_s"] == pytest.approx(10e-9)
    assert t["mlp"] == pytest.approx(20e-9)
    assert t["head"] == pytest.approx(10e-9)
    assert t["unscoped"] == pytest.approx(2e-9)
    assert call["coverage"] == pytest.approx(90 / 100)
    # buckets add up to the device time less the gaps between ops
    assert sum(t.values()) == pytest.approx(92e-9)
    assert call["unscoped_ops"] == [["%add.6 s32[] add",
                                     pytest.approx(2e-9)]]


def test_shape_fallback_only_for_cache_leaves():
    t = scopes.summarize(hand_made())["jit_decode_step"]["self_s"]
    assert t["cache"] == pytest.approx(10e-9)
    assert t["unscoped"] == pytest.approx(12e-9)


def test_attach_maps_ops_within_their_module():
    ev = {"host": [["window", 0, 100]],
          "modules": [[0, "jit_decode_step(1)", 10, 50],
                      [0, "jit_other(2)", 60, 90]],
          "ops": [[0, "%fusion.3 = bf16[4]{0} fusion(%fusion.1)", 12, 20],
                  [0, "%fusion.3 = bf16[4]{0} fusion(%fusion.1)", 62, 70],
                  [0, "%while.2 = bf16[4]{0} while(%fusion.1)", 20, 50]]}
    ops = scopes.attach(ev, [HLO])["ops"]
    assert [op[4] for op in ops] == ["jit(decode_step)/head/dot_general",
                                     None, "jit(decode_step)/layers/while"]


def test_cache_shapes_from_the_model():
    cfg = json.loads((DATA / "tiny-mamba2.json").read_text())["model"]
    model = build(ModelConfig(**dict(cfg, pattern=tuple(cfg["pattern"]))))
    leaves = scopes.cache_shapes(jax.eval_shape(
        lambda: model.init_cache(3, 8)))
    assert ("f32", (2, 3, 8, 16, 16)) in leaves        # ssm state, stacked
    assert ("f32", (3, 8, 16, 16)) in leaves           # one layer's
    assert ("bf16", (3, 3, 160)) in leaves             # conv history


def test_summarize_of_the_small_trace_is_unchanged():
    """The existing reduction reads trace_small.json as it did: the same
    keys and the same numbers."""
    ev = json.loads((DATA / "trace_small.json").read_text())
    s = trace_reduce.summarize(ev)
    assert set(s) == {"window_s", "busy_s", "calls", "device_ops",
                      "idle_gaps"}
    assert s["window_s"] == pytest.approx(0.07216625)
    assert s["busy_s"] == pytest.approx(9.9346e-05)
    assert {k: v["count"] for k, v in s["calls"].items()} == {
        "jit_convert_element_type": 1, "jit_prefill": 1,
        "jit_decode_step": 4, "jit_next_token": 5}
    assert s["calls"]["jit_decode_step"]["device_s"] == pytest.approx(
        6.3254e-05)
    assert s["device_ops"][:2] == [
        ["%while (tuple) while", pytest.approx(7.6096e-05)],
        ["%fusion.13 bf16[8,1024] fusion", pytest.approx(6.0673e-05)]]
    assert s["idle_gaps"][0] == ["dispatch x20", pytest.approx(0.07089146)]


def compiled_text(name, call):
    cfg = json.loads((DATA / f"{name}.json").read_text())["model"]
    model = build(ModelConfig(**dict(cfg, pattern=tuple(cfg["pattern"]))))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, 8))
    if call == "decode_step":
        args = (params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))
    else:
        args = (params, jax.ShapeDtypeStruct((2, 4), jnp.int32), cache)
    return jax.jit(getattr(model, call)).lower(*args).compile().as_text()


# what JAX itself puts on an op's path: the jitted function, the
# transforms and control flow it traced through, an einsum's spec
JAX_PARTS = {"while", "body", "cond", "closed_call", "checkpoint", "remat"}


def is_jax_part(part):
    return part in JAX_PARTS or part.endswith(")") or "->" in part


@pytest.mark.parametrize("call", ["decode_step", "prefill"])
@pytest.mark.parametrize("name", ["tiny-danube", "tiny-mamba2"])
def test_compiled_calls_carry_the_documented_scopes(name, call):
    """Every name on an op's path is JAX's own or a documented scope, and
    at least 90% of the instructions that carry an op_name carry a scope.
    Parameters, constants and the ops that compute nothing (tuples,
    their elements, bitcasts) aside; the instructions the compiler adds
    carry no op_name (on the CPU, its float conversions of bf16)."""
    text = compiled_text(name, call)
    names = [op for op in scopes.op_names(text).items() if op[1]]
    lines = {ln.split("=")[0].split()[-1].lstrip("%"): ln
             for ln in text.splitlines() if " = " in ln}
    skip = (" parameter(", " constant(", " get-tuple-element(",
            " tuple(", " bitcast(")
    counted = [op for instr, op in names
               if not any(s in lines[instr] for s in skip)]
    for op in counted:
        path = op.split("/")[:-1]        # the last part is the primitive
        assert all(p in SCOPES or is_jax_part(p) for p in path), op
    scoped = [op for op in counted if scopes.bucket(op) != "unscoped"]
    assert len(scoped) >= 0.9 * len(counted), (
        len(scoped), len(counted),
        sorted({op for op in counted if scopes.bucket(op) == "unscoped"}))


def test_recorded_scoped_trace():
    """tiny-danube at batch 2, a 4-token prompt and 4 new tokens, recorded
    on a TPU v5 lite chip: every listed part of a decode step shows, the
    parts add up to no more than the step, and the scan's own time is
    counted once, under ``layers``."""
    ev = json.loads((DATA / "trace_scoped.json").read_text())
    cfg = json.loads((DATA / "tiny-danube.json").read_text())["model"]
    model = build(ModelConfig(**dict(cfg, pattern=tuple(cfg["pattern"]))))
    leaves = scopes.cache_shapes(jax.eval_shape(
        lambda: model.init_cache(2, 8)))
    calls = scopes.summarize(ev, leaves)
    assert {k: c["count"] for k, c in calls.items()} == {
        "jit_prefill": 1, "jit_decode_step": 3, "jit_next_token": 4}
    step = calls["jit_decode_step"]
    t = step["self_s"]
    assert {"embed", "layers", "attn", "mlp", "cache", "head"} <= set(t)
    assert all(v >= 0 for v in t.values())
    assert sum(t.values()) <= step["device_s"]
    assert 0.75 < step["coverage"] < 1
    # the prefill's token loop (its while and slicing) is in no scope
    assert calls["jit_prefill"]["unscoped_ops"][0][0].endswith(" while")
