"""Multi-pod dry run: lower and compile every (arch x shape) cell for the
production mesh on placeholder CPU devices, and price it.

    PYTHONPATH=src python -m repro.launch.dryrun --arch h2o-danube-1.8b \
        --shape decode_32k

Importing this module changes no JAX setting; ``main`` (or a caller, via
``use_host_devices``) selects the 512 placeholder devices.
"""
import argparse
import json
import sys
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs import SHAPES, all_cells, cell_applicable, get_config, \
    memory_len
from ..configs.base import ModelConfig
from ..core import tpu as tpu_model
from ..data import make_batch_specs
from ..distributed import sharding
from ..models import build
from ..optim.schedule import for_arch
from ..train.serve_step import make_prefill, make_serve_step
from ..train.train_step import init_state, make_train_step
from . import hlo_analysis
from .mesh import make_production_mesh

HOST_DEVICES = 512


def use_host_devices() -> None:
    """Run JAX on ``HOST_DEVICES`` placeholder CPU devices.  Call before
    the first JAX operation of the process: backends read this once."""
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", HOST_DEVICES)


# ---------------------------------------------------------------------------
# Per-cell execution plans (baseline).  §Perf hillclimbing edits these.
# ---------------------------------------------------------------------------

BIG = ("deepseek-67b", "llama3-405b", "deepseek-v3-671b",
       "qwen3-moe-235b-a22b", "llama-3.2-vision-90b")


def plan_for(arch: str, shape: str, cfg: ModelConfig) -> Dict:
    """Baseline execution plan: sharding-rule overrides + microbatches +
    optimizer dtypes, chosen to fit HBM (DESIGN.md §5)."""
    plan: Dict = {"rules": {}, "microbatches": 1,
                  "moment_dtype": None, "accum_dtype": "float32",
                  "remat": None}
    if cfg.d_model >= 7168:
        # shard the residual stream's hidden dim over "model" so scanned
        # layer-carry residuals stay O(D/16) per chip
        plan["rules"]["embed"] = "model"
    if arch in BIG:
        plan["moment_dtype"] = "bfloat16"
        plan["accum_dtype"] = "bfloat16"
    if shape == "train_4k":
        # global batch 256: grad-accumulate in 8 microbatches.  Dominant
        # temp buffers (fp32 logits chain + per-layer scan carries) scale
        # with live tokens; 1M tokens at once blows the 16 GB HBM.
        plan["microbatches"] = 8
    if shape == "long_500k":
        plan["rules"]["batch"] = None     # batch 1: DP axes idle
    return plan


def model_flops_for(cfg: ModelConfig, shape_name: str) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE), N excluding
    embeddings; D = tokens processed by the lowered step."""
    shape = SHAPES[shape_name]
    n_embed = cfg.vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    n = cfg.active_param_count() - n_embed
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch      # decode: 1 token per sequence


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

ACCOUNTING_ATTN_CHUNK = 4096   # same flop/byte totals, fewer bigger HLO ops


def _accounting_cfg(cfg: ModelConfig, groups: int) -> ModelConfig:
    """Reduced-depth UNROLLED config for cost accounting.

    XLA cost_analysis counts while-loop bodies once, so the deployed
    scanned lowering under-reports by the trip count.  We instead lower
    unrolled 1-group and 2-group variants; all depth-dependent costs are
    linear in the group count, so  total(G) = f1 + (G-1)*(f2-f1)  is exact
    for flops/bytes/collectives (embed/head/optimizer-on-prefix terms live
    in the intercept)."""
    plen = len(cfg.pattern)
    kw = dict(
        n_layers=cfg.first_dense + groups * plen,
        scan_layers=False,
        attn_chunk_unroll=True,
    )
    if cfg.attn_chunk > 0:
        kw["attn_chunk"] = ACCOUNTING_ATTN_CHUNK
    return cfg.replace(**kw)


def _lower_for(model, cfg, shape, mesh, plan, arch):
    if shape.kind == "train":
        return _lower_train(model, cfg, shape, mesh, plan, arch)
    if shape.kind == "prefill":
        return _lower_prefill(model, cfg, shape, mesh, plan)
    return _lower_decode(model, cfg, shape, mesh, plan)


def _cost_of(lowered, num_chips: int) -> Tuple[float, float, float, object]:
    """GLOBAL flop/byte/collective totals of one lowering.

    XLA cost_analysis on an SPMD executable reports PER-PARTITION numbers
    (verified empirically: an 8-way-sharded matmul reports 1/8 of the
    global flops), and HLO shard shapes are per-device — so scale by the
    chip count to match the task-spec global-form roofline terms."""
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    cost = cost or {}
    stats = hlo_analysis.analyze(compiled.as_text(),
                                 default_while_multiplier=1.0)
    nbytes = max(float(cost.get("bytes accessed", 0.0))
                 - stats.dus_overcount_bytes, 0.0)
    return (float(cost.get("flops", 0.0)) * num_chips,
            nbytes * num_chips,
            stats.total_bytes * num_chips, stats)


def account_cell(cfg, shape, mesh, plan, arch) -> Dict[str, float]:
    """Two-point group extrapolation of flops / bytes / collective bytes."""
    vals = []
    stats2 = None
    for g in (1, 2):
        cfg_g = _accounting_cfg(cfg, g)
        model_g = build(cfg_g)
        plan_g = dict(plan, microbatches=1)
        with sharding.use_mesh(mesh, plan["rules"]):
            art = _lower_for(model_g, cfg_g, shape, mesh, plan_g, arch)
        f, b, c, stats = _cost_of(art["lowered"], mesh.size)
        vals.append((f, b, c))
        stats2 = stats
    g_full = cfg.n_groups
    out = {}
    for key, (v1, v2) in zip(("flops", "bytes", "collective_bytes"),
                             zip(*vals)):
        out[key] = v1 + (g_full - 1) * (v2 - v1)
        out[f"{key}_g1"] = v1
        out[f"{key}_g2"] = v2
    out["per_op_collectives_g2"] = dict(stats2.totals) if stats2 else {}
    return out


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               plan_override: Optional[Dict] = None,
               accounting: bool = True):
    """Lower + compile one (arch x shape x mesh) cell.

    Two lowerings per cell:
      1. the DEPLOYED plan (scan + remat + microbatches) -> compile gate +
         memory_analysis ("proves it fits"),
      2. unrolled 1-/2-group accounting lowers -> exact flop/byte/
         collective totals via linear extrapolation (see _accounting_cfg).
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    plan = plan_for(arch, shape_name, cfg)
    if plan_override:
        plan["rules"].update(plan_override.pop("rules", {}))
        plan.update(plan_override)
    if plan.get("remat"):
        cfg = cfg.replace(remat=plan["remat"])
    if plan.get("cfg_overrides"):
        cfg = cfg.replace(**plan["cfg_overrides"])

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build(cfg)
    t0 = time.time()

    with sharding.use_mesh(mesh, plan["rules"]):
        artifacts = _lower_for(model, cfg, shape, mesh, plan, arch)

    lowered = artifacts["lowered"]
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    cost = cost or {}
    stats = hlo_analysis.analyze(
        compiled.as_text(),
        default_while_multiplier=max(cfg.n_groups, 1))

    if accounting:
        acct = account_cell(cfg, shape, mesh, plan, arch)
        eff_cost = {"flops": acct["flops"],
                    "bytes accessed": acct["bytes"]}
        coll_bytes = acct["collective_bytes"]
    else:
        acct = {}
        eff_cost = {k: float(v) * mesh.size for k, v in cost.items()
                    if isinstance(v, (int, float))}
        coll_bytes = stats.total_bytes * mesh.size

    report = tpu_model.report_from_artifacts(
        f"{arch}/{shape_name}/{'2x16x16' if multi_pod else '16x16'}",
        num_chips=mesh.size,
        cost_analysis=eff_cost,
        collective_bytes=coll_bytes,
        model_flops=model_flops_for(cfg, shape_name),
    )
    return {
        "compiled": compiled,
        "cost": cost,
        "accounting": acct,
        "memory_analysis": mem,
        "collectives": stats,
        "report": report,
        "compile_seconds": t_compile,
        "plan": plan,
        "mesh": mesh,
    }


def _batch_shardings(mesh, specs):
    pspecs = sharding.batch_specs_tree(specs, mesh=mesh)
    return sharding.tree_shardings(mesh, pspecs)


def _lower_train(model, cfg, shape, mesh, plan, arch):
    state_specs = jax.eval_shape(
        lambda k: init_state(model, k, moment_dtype=plan["moment_dtype"]),
        jax.random.PRNGKey(0))
    state_sh = sharding.tree_shardings(
        mesh, sharding.param_specs(state_specs, mesh=mesh))
    # per-device batch: global batch over DP axes
    batch_specs = make_batch_specs(cfg, batch=shape.global_batch,
                                   seq_len=shape.seq_len)
    batch_sh = _batch_shardings(mesh, batch_specs)

    lr = for_arch(arch, 3e-4, 2000, 100000)
    step = make_train_step(model, lr=lr,
                           microbatches=plan["microbatches"],
                           accum_dtype=plan.get("accum_dtype", "float32"),
                           q8_moments=plan["moment_dtype"] == "int8")
    lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                      donate_argnums=(0,)).lower(state_specs, batch_specs)
    return {"lowered": lowered}


def _lower_prefill(model, cfg, shape, mesh, plan):
    params_specs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = sharding.tree_shardings(
        mesh, sharding.param_specs(params_specs, mesh=mesh))
    batch_specs = make_batch_specs(cfg, batch=shape.global_batch,
                                   seq_len=shape.seq_len)
    batch_specs.pop("labels")
    batch_sh = _batch_shardings(mesh, batch_specs)

    prefill = make_prefill(model)
    kwargs = {}
    if "memory_embeds" in batch_specs:
        lowered = jax.jit(
            prefill, in_shardings=(params_sh, batch_sh["tokens"],
                                   batch_sh["memory_embeds"])).lower(
            params_specs, batch_specs["tokens"],
            batch_specs["memory_embeds"])
    else:
        lowered = jax.jit(
            prefill, in_shardings=(params_sh, batch_sh["tokens"])).lower(
            params_specs, batch_specs["tokens"])
    return {"lowered": lowered}


def _lower_decode(model, cfg, shape, mesh, plan):
    b = shape.global_batch
    params_specs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = sharding.tree_shardings(
        mesh, sharding.param_specs(params_specs, mesh=mesh))
    cache_specs = model.init_cache(b, shape.seq_len, abstract=True)
    cache_sh = sharding.tree_shardings(
        mesh, sharding.cache_specs_tree(cache_specs, mesh=mesh))
    tok_spec = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    tok_sh = sharding.tree_shardings(
        mesh, sharding.batch_specs_tree(tok_spec, mesh=mesh))
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    pos_sh = sharding.tree_shardings(
        mesh, sharding.batch_specs_tree(pos_spec, mesh=mesh))

    serve = make_serve_step(model)
    args = [params_specs, cache_specs, tok_spec, pos_spec]
    shs = [params_sh, cache_sh, tok_sh, pos_sh]
    mlen = memory_len(cfg, shape.seq_len)
    if mlen is not None:
        mem_spec = jax.ShapeDtypeStruct((b, mlen, cfg.d_model),
                                        jnp.dtype(cfg.dtype))
        mem_sh = sharding.tree_shardings(
            mesh, sharding.batch_specs_tree(mem_spec, mesh=mesh))
        args.append(mem_spec)
        shs.append(mem_sh)
    lowered = jax.jit(serve, in_shardings=tuple(shs),
                      donate_argnums=(1,)).lower(*args)
    return {"lowered": lowered}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, multi_pod: bool,
             json_out: Optional[str] = None, quiet: bool = False) -> Dict:
    ok, why = cell_applicable(arch, shape_name)
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    if not ok:
        row = {"arch": arch, "shape": shape_name, "mesh": mesh_tag,
               "status": "skipped", "reason": why}
        if not quiet:
            print(f"[dryrun] SKIP {arch} x {shape_name} x {mesh_tag}: {why}")
        if json_out:
            with open(json_out, "a") as f:
                f.write(json.dumps(row) + "\n")
        return row

    if not quiet:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_tag} ...",
              flush=True)
    art = lower_cell(arch, shape_name, multi_pod=multi_pod)
    rep = art["report"]
    mem = art["memory_analysis"]
    row = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "status": "ok",
        "chips": rep.num_chips,
        "hlo_flops": rep.hlo_flops,
        "hlo_bytes": rep.hlo_bytes,
        "collective_bytes": rep.collective_bytes,
        "model_flops": rep.model_flops,
        "compute_term_s": rep.compute_term,
        "memory_term_s": rep.memory_term,
        "collective_term_s": rep.collective_term,
        "dominant": rep.dominant,
        "useful_flops_ratio": rep.useful_flops_ratio,
        "roofline_fraction": rep.roofline_fraction,
        "compile_seconds": art["compile_seconds"],
        "collective_totals": dict(art["collectives"].totals),
        "plan": {k: v for k, v in art["plan"].items()},
    }
    # memory analysis: "proves it fits"
    try:
        row["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception:                                    # pragma: no cover
        row["memory"] = {"repr": repr(mem)}
    if not quiet:
        print(f"  compile {art['compile_seconds']:.1f}s | "
              f"flops {rep.hlo_flops:.3e} bytes {rep.hlo_bytes:.3e} "
              f"coll {rep.collective_bytes:.3e}")
        print(f"  terms: compute {rep.compute_term:.4e}s "
              f"memory {rep.memory_term:.4e}s "
              f"collective {rep.collective_term:.4e}s "
              f"-> {rep.dominant}-bound | useful {rep.useful_flops_ratio:.3f}")
        print(f"  memory_analysis: {row['memory']}")
    if json_out:
        with open(json_out, "a") as f:
            f.write(json.dumps(row) + "\n")
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell")
    ap.add_argument("--json", default=None, help="append JSONL rows here")
    args = ap.parse_args(argv)
    use_host_devices()

    cells: list
    if args.all:
        cells = [(a, s) for a, s, _, _ in all_cells()]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp, json_out=args.json)
            except Exception as e:                       # noqa: BLE001
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] FAIL {arch} x {shape} "
                      f"(multi_pod={mp}): {e}", file=sys.stderr)
    if failures:
        print(f"[dryrun] {len(failures)} failures", file=sys.stderr)
        sys.exit(1)
    print("[dryrun] all requested cells compiled")


if __name__ == "__main__":
    main()
