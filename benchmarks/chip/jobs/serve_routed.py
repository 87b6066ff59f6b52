"""Streaming greedy serving (``serve``) of a model that routes each token
to a few of many experts, with a check that a few routing flips do not
decide.

The client, the compiled calls, the end-to-end metrics and the sample of
requests checked are ``serve``'s, and so is each served token's gap: the
reference's best logit at its position less its logit for the served
token.  Where each token goes to top_k of many experts, bfloat16 rounding
turns the choice at a near-tie the other way from the float32 reference:
in DeepSeek-V3's one-chip cell a held expert is chosen differently at
about 8% of the checked tokens, and such a token's gap reaches 0.84 where
the fp8 control's widest gap in a run is at least 1.2 (PERF.md).  So the
widest gap alone cannot tell the configuration's precision from the one
below it with room.  Two numbers over the checked tokens are held to the
configuration's ``check`` limits:

  mean_logit_gap   the gaps' mean: precision lost anywhere moves every
                   token, a routing flip moves one
  max_logit_gap    the widest gap: a gross fault, such as a garbage or
                   misplaced token, moves it however few the tokens

The readings the limits are set from, per seed (one whole batch through
the timed path, as ``control.py``), both numbers for the served tokens and
for the tokens that the fp8 control puts first at their positions:

    python3 benchmarks/chip/jobs/serve_routed.py --workload <cell> \\
        --seeds 1,2,3

Needs a TPU, like a run.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

if __package__ in (None, ""):
    sys.path[:0] = [str(Path(__file__).resolve().parents[3]),
                    str(Path(__file__).resolve().parents[3] / "src")]

from benchmarks.chip.jobs import serve  # noqa: E402

CHECKS = ("mean_logit_gap", "max_logit_gap")


def token_gaps(reference, params, m: Dict, picks, prompt_len: int, *,
               control: bool = False):
    """Each served token's gap over the sampled requests, concatenated;
    with ``control`` also the gap of the token that the fp8 control puts
    first at each of those positions (else None)."""
    fwd = jax.jit(functools.partial(reference.forward, m=m,
                                    first=prompt_len - 1))
    ctl = jax.jit(functools.partial(reference.forward, m=m,
                                    first=prompt_len - 1, fp8=True))
    served_gaps, control_gaps = [], []
    with jax.default_matmul_precision("highest"):
        for batch, r in picks:
            served = batch.tokens[r]
            seq = jnp.asarray(np.concatenate([batch.prompts[r],
                                              served[:-1]]))
            ref = np.asarray(fwd(params, tokens=seq))
            best = ref.max(axis=-1)
            rows = np.arange(len(served))
            served_gaps.append(best - ref[rows, served])
            if control:
                top = np.asarray(ctl(params, tokens=seq)).argmax(axis=-1)
                control_gaps.append(best - ref[rows, top])
    return (np.concatenate(served_gaps),
            np.concatenate(control_gaps) if control else None)


def summary(gaps) -> Dict:
    return {"mean_logit_gap": float(np.mean(gaps)),
            "max_logit_gap": float(np.max(gaps))}


def run(ctx) -> Dict:
    """``serve.run``, its check made over every served token's gap."""
    kept = {}

    def gaps(reference, params, m, picks, prompt_len):
        kept["gaps"], _ = token_gaps(reference, params, m, picks,
                                     prompt_len)
        return {"max_logit_gap": float(np.max(kept["gaps"])),
                "tokens_checked": int(kept["gaps"].size)}

    # serve.run checks through its module's logit_gaps: lend it the
    # per-token one for this run, which keeps the gaps
    own = serve.logit_gaps
    serve.logit_gaps = gaps
    try:
        out = serve.run(ctx)
    finally:
        serve.logit_gaps = own
    limits = ctx.config["check"]
    values = (summary(kept["gaps"]) if "gaps" in kept
              else dict.fromkeys(CHECKS))
    out["check"] = {k: (values[k], limits[k]) for k in CHECKS}
    out["correct"] = "gaps" in kept and all(values[k] <= limits[k]
                                            for k in CHECKS)
    return out


def readings(ctx, seeds):
    """Per seed: both numbers for the served tokens and for the fp8
    control's, over one whole batch through the timed path."""
    traffic, m = ctx.traffic, ctx.config["model"]
    b, p, n = traffic["batch"], traffic["prompt_len"], traffic["new_tokens"]
    progs = None
    for seed in seeds:
        ctx.seed = seed
        params = ctx.weights()
        if progs is None:
            progs = serve.compile_program(ctx.model, params, b, p, n)
        batches = serve.stream(progs, params, traffic, m["vocab"], seed,
                               deadline=time.perf_counter() + 1e9,
                               max_batches=1)
        picks = serve.sample_requests(batches, traffic, seed)
        served, control = token_gaps(ctx.reference, params, m, picks, p,
                                     control=True)
        yield {"seed": seed, "served": summary(served),
               "control": summary(control), "tokens_checked": served.size}
        del params


def main(argv=None):
    from benchmarks.chip import harness
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    res = harness.resolve(harness.load_benchmark(), args.workload)
    harness.require_chips(res["cell"]["chips"])
    enable_compile_cache()
    ctx = harness.Context(res, 0, 0.0, False, time.perf_counter(), "")
    rows = []
    for row in readings(ctx, [int(s) for s in args.seeds.split(",")]):
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "lower": {k: max(r["served"][k] for r in rows) for k in CHECKS},
        "upper": {k: min(r["control"][k] for r in rows) for k in CHECKS}}))


if __name__ == "__main__":
    main()
