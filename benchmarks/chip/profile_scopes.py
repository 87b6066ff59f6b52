"""Device time of one cell's calls by model layer, on the chip.

Runs a cell as the serving job's traced run does (weights from the seed,
the program's calls compiled ahead, one untraced batch, then one batch
under the profiler) and prints one JSON line: per jitted call its device
time, the self time of each scope (``scopes.py``) and the scoped share;
the per-decode-step times the per-layer metrics would read; the unscoped
decode ops; and the compile counters of ``repro.launch.compile_cache``
before the window (seconds by phase, cache hits and misses).

    python3 benchmarks/chip/profile_scopes.py --workload <cell> --seed <n>

With ``--config <file> --shape B,P,N --record <file>`` it runs that
configuration instead and writes the trace's events, each device op with
its ``op_name``, which is how ``tests/data/trace_scoped.json`` was made.
"""
import argparse
import json
import sys
import time
from pathlib import Path

T_PROCESS = time.perf_counter()

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.chip import harness, scopes, trace_reduce  # noqa: E402
from benchmarks.chip.jobs import serve  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.obs import metrics  # noqa: E402


def compile_split():
    """The compile counters so far: seconds by phase, counts."""
    snap = metrics.snapshot()

    def series(name):
        return snap.get(name, {"series": []})["series"]

    out = {f"{s['labels']['phase']}_s": s["value"]
           for s in series("repro_jax_compile_seconds_total")}
    out.update({f"cache_{s['labels']['result']}": s["value"]
                for s in series("repro_jax_compile_cache_total")})
    for key in ("lowerings", "backend_compiles"):
        out[key] = sum(s["value"] for s in series(f"repro_jax_{key}_total"))
    out["total_s"] = sum(v for k, v in out.items() if k.endswith("_s"))
    return out


def decode_metrics(step):
    """What the per-layer decode metrics read, in ms per decode step."""
    if not step:
        return {}
    t = step["self_s"]
    return {"decode_cache_ms": (t.get("cache", 0) + t.get("layers", 0)) * 1e3,
            "decode_attn_ms": t.get("attn", 0) * 1e3,
            "decode_mlp_ms": t.get("mlp", 0) * 1e3,
            "decode_ssm_ms": t.get("ssm", 0) * 1e3}


def profile(ctx, trace_dir):
    traffic, m = ctx.traffic, ctx.config["model"]
    b, p, n = traffic["batch"], traffic["prompt_len"], traffic["new_tokens"]
    params = ctx.weights()
    progs = serve.compile_program(ctx.model, params, b, p, n)
    jax.block_until_ready(progs["next_token"](
        jnp.zeros((b, m["vocab"]), jnp.dtype(m["dtype"]))))
    setup = dict(compile_split(), setup_s=time.perf_counter() - ctx.t_process)
    far = time.perf_counter() + 1e9
    serve.stream(progs, params, traffic, m["vocab"], ctx.seed,
                 deadline=far, max_batches=1)
    ann = jax.profiler.TraceAnnotation
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with ann("window"):
            serve.stream(progs, params, traffic, m["vocab"], ctx.seed,
                         deadline=far, first_batch=1, max_batches=1,
                         annotate=ann)
    finally:
        jax.profiler.stop_trace()
    ev = scopes.attach(trace_reduce.load(trace_reduce.find_xplane(trace_dir)),
                       [c.as_text() for c in progs.values()])
    leaves = scopes.cache_shapes(
        jax.eval_shape(lambda: ctx.model.init_cache(b, p + n)))
    calls = scopes.summarize(ev, leaves)
    step = calls.get("jit_decode_step")
    result = {
        "calls": calls,
        "decode": decode_metrics(step),
        "setup": setup,
        "device_ops": trace_reduce.summarize(
            {k: ev[k] if k != "ops" else [x[:4] for x in ev["ops"]]
             for k in ev})["device_ops"],
    }
    return ev, result


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--shape", help="B,P,N with --config")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--record", help="write the events here")
    args = ap.parse_args(argv)
    if args.workload:
        res = harness.resolve(harness.load_benchmark(), args.workload)
        chips = res["cell"]["chips"]
    else:
        config = harness.load_json(Path(args.config))
        b, p, n = (int(x) for x in args.shape.split(","))
        res = {"config": config, "reference": None,
               "traffic": {"batch": b, "prompt_len": p, "new_tokens": n,
                           "steps_ahead": 1}}
        chips = 1
    harness.require_chips(chips)
    compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    trace_dir = str(ROOT / ".bench_out" / "scope_trace")
    ctx = harness.Context(res, args.seed, 0.0, True, T_PROCESS, trace_dir)
    ev, result = profile(ctx, trace_dir)
    if args.record:
        Path(args.record).write_text(json.dumps(
            {k: [[x[0], trace_reduce.op_label(x[1])] + x[2:]
                 for x in ev[k]] if k == "ops" else ev[k] for k in ev}))
    print(json.dumps(dict(result, workload=args.workload or args.config,
                          seed=args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
