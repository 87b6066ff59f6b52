"""§Perf hillclimbing runner: named (cell, plan-override) experiments,
each re-lowers + re-accounts and prints before/after roofline terms.

    PYTHONPATH=src python -m benchmarks.hillclimb --exp mamba2_shardmap

Every experiment records: hypothesis, napkin-math prediction, change.
Results go into EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import argparse
import json

EXPERIMENTS = {
    # ---------------- mamba2-1.3b x train_4k (collective-bound) ----------
    "mamba2_shardmap": {
        "cell": ("mamba2-1.3b", "train_4k"),
        "hypothesis": (
            "GSPMD autodiff of the head-block SSD loop emits per-iteration "
            "(B,nc,L,L)-sized backward all-reduces (~1.4e9 B/chip each). "
            "shard_map-ing the SSD leaves only layer-boundary psums for "
            "dB/dC/dA (~0.5 GB global x 48 layers) + FSDP traffic. "
            "Napkin: collective term 5.32s -> ~0.5s (>10x)."),
        "override": {"cfg_overrides": {"ssd_shard_map": True}},
    },
    "mamba2_shardmap_bf16ssd": {
        "cell": ("mamba2-1.3b", "train_4k"),
        "hypothesis": (
            "After shard_map, memory term should dominate; SSD runs in "
            "fp32 (4 B/elem on every (L,L) tile). bf16 params already; "
            "keep fp32 SSD but drop accum dtype to bf16 and raise "
            "microbatches to 16: per-ubatch logits/carries halve. "
            "Napkin: memory term -15-25%."),
        "override": {"cfg_overrides": {"ssd_shard_map": True},
                     "microbatches": 16, "accum_dtype": "bfloat16"},
    },
    "mamba2_bf16_tiles": {
        "cell": ("mamba2-1.3b", "train_4k"),
        "hypothesis": (
            "Memory now dominates (4.49s); the XLA SSD fallback streams "
            "fp32 (L,L) tiles: ~B*S*L*H*4B x ~5 tensors/layer ~ 3.4e14 B "
            "of the 9.4e14 total. bf16 tiles (fp32 accumulation) halve "
            "that share. Napkin: memory term 4.49 -> ~3.6s; NOTE the "
            "Pallas kernel keeps these tiles in VMEM on real TPU, "
            "removing them entirely."),
        "override": {"cfg_overrides": {"ssd_shard_map": True,
                                       "ssd_tile_bf16": True},
                     "microbatches": 16, "accum_dtype": "bfloat16"},
    },
    # ---------------- deepseek-v3-671b x train_4k (worst fraction) -------
    "dsv3_mtp_share": {
        "cell": ("deepseek-v3-671b", "train_4k"),
        "hypothesis": (
            "MTP head re-runs the full 61-layer trunk forward: one extra "
            "fwd = +~33% flops at remat=full (fwd:bwd = 1:2). Sharing the "
            "trunk removes it. Napkin: HLO flops x~0.75, useful 0.25 -> "
            "~0.33; memory term down similarly."),
        "override": {"cfg_overrides": {"mtp_share_trunk": True}},
    },
    "dsv3_mtp_remat_block": {
        "cell": ("deepseek-v3-671b", "train_4k"),
        "hypothesis": (
            "remat=full recomputes the whole block in bwd (5/3 flop "
            "factor); with d_model sharded over 'model', block-level remat "
            "(4/3) fits. Napkin: flops x0.8 on top of MTP sharing; "
            "useful -> ~0.42."),
        "override": {"cfg_overrides": {"mtp_share_trunk": True},
                     "remat": "block"},
    },
    "dsv3_full_stack": {
        "cell": ("deepseek-v3-671b", "train_4k"),
        "hypothesis": (
            "int8 block-quantized Adam moments cut optimizer state from "
            "4 B/param (2x bf16) to ~2.05 B/param: argument bytes "
            "15.8 GB/chip -> ~10.6 GB/chip => the cell finally FITS "
            "single-pod HBM (the baseline's blocker). Terms roughly "
            "unchanged; memory_analysis is the metric."),
        "override": {"cfg_overrides": {"mtp_share_trunk": True},
                     "remat": "block", "moment_dtype": "int8"},
    },
    # ---------------- llama3-405b x train_4k (paper-representative) ------
    "llama405b_remat_block": {
        "cell": ("llama3-405b", "train_4k"),
        "hypothesis": (
            "remat=full pays 5/3 flops; block remat pays 4/3 and the "
            "per-ubatch carries (2.1 GB/chip) still fit. Napkin: compute "
            "term 65.5s -> ~52s, useful 0.77 -> ~0.96."),
        "override": {"remat": "block"},
    },
    "llama405b_unshard_embed": {
        "cell": ("llama3-405b", "train_4k"),
        "hypothesis": (
            "The rules['embed']='model' residual-stream sharding forces "
            "an all-gather of x per layer (fwd+bwd). With remat=block + "
            "microbatches=16 the unsharded carries fit; dropping the rule "
            "removes those gathers. Napkin: collective term down by the "
            "x-gather share (~126 x 134 MB x 3 / step ~ 5e13 B of 1.25e15 "
            "-> small) BUT memory term drops the gather-byte traffic too; "
            "mainly a memory-term test."),
        "override": {"remat": "block", "microbatches": 16,
                     "rules": {"embed": None}},
    },
    "llama405b_q8_u4": {
        "cell": ("llama3-405b", "train_4k"),
        "hypothesis": (
            "int8 moments free 3.2 GB/chip; spend it on microbatches=4 "
            "(fewer FSDP param re-gathers per step: gather volume scales "
            "with ubatch count at remat=block where bwd regathers). "
            "Napkin: collective term -30-50%, fits HBM."),
        "override": {"remat": "block", "microbatches": 4,
                     "moment_dtype": "int8"},
    },
}


def screen(names, json_out: str | None = None, *, jobs=None,
           chunk_size: int | None = None):
    """Napkin-math pre-screen: price every experiment's plan against its
    cell's baseline through ``autotune.enumerate_plans`` (no lowering — a
    full screen costs milliseconds vs minutes per compile).

    Experiments are grouped by cell so each cell's config/param maths is
    computed once; each cell prices all its plans in ONE columnar
    enumerate_plans call — ``opt_state_bytes`` (the int8-moments HBM-fit
    input) is passed per plan.  Kernel-level what-ifs ride the shared
    SweepEngine cache.  Model changes hidden behind ``cfg_overrides``
    (e.g. shard_map SSD) are not visible to the analytical plan model and
    are marked as such.

    ``jobs``/``chunk_size`` thread through to the sharded plan executor
    (``--jobs``/``--chunk-size``; auto-sized pools only engage once a cell
    has enough plans to amortize them, so small screens stay serial and
    millisecond-fast while arbitrarily large what-if grids scale out).
    """
    from repro.configs import SHAPES, get_config
    from repro.core import autotune, collectives

    mesh = collectives.MeshSpec(axes=(("data", 16), ("model", 16)))
    rows = []
    by_cell: dict = {}
    for name in names:
        by_cell.setdefault(EXPERIMENTS[name]["cell"], []).append(name)

    for (arch, shape_name), exp_names in by_cell.items():
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        n = cfg.param_count()
        tokens = shape.global_batch * shape.seq_len
        plans = [autotune.PlanCandidate(name="baseline", mesh=mesh,
                                        tp_degree=16, microbatches=8,
                                        remat="full")]
        opt_bytes = [4.0 * n]
        for name in exp_names:
            ov = EXPERIMENTS[name]["override"]
            plans.append(autotune.PlanCandidate(
                name=name, mesh=mesh, tp_degree=16,
                microbatches=int(ov.get("microbatches", 8)),
                remat=ov.get("remat", "full")))
            # int8 block-quantized moments: ~2.05 B/param vs 4 B/param
            opt_bytes.append(2.05 * n if ov.get("moment_dtype") == "int8"
                             else 4.0 * n)

        costs = autotune.enumerate_plans(
            plans,
            model_flops=6.0 * n * tokens,
            param_bytes=2.0 * n,
            activation_bytes=2.0 * tokens * cfg.d_model
            * cfg.n_layers * 4,
            opt_state_bytes=opt_bytes,
            activation_peak_bytes=2.0 * tokens * cfg.d_model * 2,
            chunk_size=chunk_size, jobs=jobs)
        base = costs[0]
        print(f"=== screen: {arch} x {shape_name} "
              f"(baseline step {base.total_s:.3f}s) ===")
        for c in costs[1:]:
            ov = EXPERIMENTS[c.plan.name]["override"]
            opaque = " [+cfg_overrides not priced]" \
                if ov.get("cfg_overrides") else ""
            fits = "fits" if c.detail.get("feasible") else "OOM "
            print(f"  {c.plan.name:24s} [{fits}] step {c.total_s:7.3f}s "
                  f"({c.total_s / base.total_s:5.2f}x baseline){opaque}")
            rows.append({"experiment": c.plan.name, "arch": arch,
                         "shape": shape_name, "screen_step_s": c.total_s,
                         "baseline_step_s": base.total_s,
                         "feasible": bool(c.detail.get("feasible"))})
    if json_out:
        with open(json_out, "a") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    return rows


def run(exp_name: str, json_out: str | None = None):
    """Lower one experiment's cell; needs the dry run's placeholder devices
    (``dryrun.use_host_devices()`` before the process's first JAX op)."""
    from repro.launch import dryrun

    exp = EXPERIMENTS[exp_name]
    arch, shape = exp["cell"]
    print(f"=== {exp_name}: {arch} x {shape} ===")
    print(f"hypothesis: {exp['hypothesis']}")
    art = dryrun.lower_cell(arch, shape, multi_pod=False,
                            plan_override=json.loads(
                                json.dumps(exp["override"])))
    rep = art["report"]
    mem = art["memory_analysis"]
    row = {
        "experiment": exp_name, "arch": arch, "shape": shape,
        "compute_term_s": rep.compute_term,
        "memory_term_s": rep.memory_term,
        "collective_term_s": rep.collective_term,
        "dominant": rep.dominant,
        "useful": rep.useful_flops_ratio,
        "roofline_fraction": rep.roofline_fraction,
        "hlo_flops": rep.hlo_flops,
        "hlo_bytes": rep.hlo_bytes,
        "collective_bytes": rep.collective_bytes,
        "arg_bytes": getattr(mem, "argument_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "compile_s": art["compile_seconds"],
    }
    print(json.dumps(row, indent=1))
    if json_out:
        with open(json_out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", required=True,
                    help="experiment name or 'all' or comma list")
    ap.add_argument("--json", default=None)
    ap.add_argument("--screen", action="store_true",
                    help="napkin-price the plans via the batched engine "
                         "instead of lowering (fast pre-screen)")
    ap.add_argument("--jobs", type=int, default=0,
                    help="screen worker processes (0 = auto from "
                         "os.cpu_count(); pools engage only when a cell "
                         "has enough plans to amortize them)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="plans per columnar pricing block "
                         "(0 = whole candidate list)")
    args = ap.parse_args()
    names = list(EXPERIMENTS) if args.exp == "all" else args.exp.split(",")
    if args.screen:
        screen(names, args.json, jobs=args.jobs,
               chunk_size=args.chunk_size or None)
        return
    from repro.launch import dryrun
    dryrun.use_host_devices()
    for n in names:
        run(n, args.json)


if __name__ == "__main__":
    main()
