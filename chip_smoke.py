"""Bring-up smoke run of the model serving path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded serving path, four chips

One chip: serves h2o-danube-1.8b at its full published config (random
weights from a seed) through ``repro.launch.serve.serve``, checks the
output against the model's own forward pass, then runs each of the four
Pallas kernels compiled (``interpret=False``) at real widths against its
reference.  ``--chips 4`` runs only the sharded path: prefill and decode
on a (data=2, model=2) mesh, compared with the same run on one device.

Earlier lines are bring-up facts (times include compilation; they are not
metrics).  The last line is ``{"ok": true, "device": {...}}``, printed only
when every phase passed.  Without a TPU, or when a phase fails, the script
exits non-zero before that line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "h2o-danube-1.8b"
# max |a - b| over max |reference|: bf16 keeps 8 mantissa bits, and the
# compared paths round intermediates in different orders
BF16_TOL = 5e-2
KERNEL_TOL = 2e-2


class PhaseError(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseError(what)


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def require_tpu():
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX's default device is "
                         f"{devs[0].platform}")
    print(f"[device] platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", flush=True)
    return devs


# --------------------------------------------------------------- main phase
def main_phase(*, smoke: bool = False, batch: int = 8, prompt_len: int = 1024,
               max_new: int = 64, seed: int = 0) -> None:
    from repro.configs import get_config
    from repro.launch.serve import serve
    from repro.models import build

    cfg = get_config(ARCH, smoke=smoke)
    t0 = time.perf_counter()
    prompt, gen = serve(ARCH, smoke=smoke, batch=batch,
                        prompt_len=prompt_len, max_new=max_new, seed=seed)
    jax.block_until_ready(gen)
    wall = time.perf_counter() - t0
    print(f"[main] serve {cfg.name} batch={batch} prompt={prompt_len} "
          f"new={max_new}: wall {wall:.3f}s incl. compilation "
          f"(bring-up fact)", flush=True)

    toks = np.asarray(gen.tokens)
    check(toks.shape == (batch, max_new), f"tokens shape {toks.shape}")
    check(bool(np.all((toks >= 0) & (toks < cfg.vocab))),
          "generated token out of vocabulary")
    check(gen.logits.shape == (batch, max_new, cfg.vocab),
          f"logits shape {gen.logits.shape}")
    check(bool(jnp.all(jnp.isfinite(gen.logits))), "non-finite logits")
    print(f"[main] compilations in the decode loop after its first step: "
          f"{gen.compiles_after_first_step}", flush=True)
    check(gen.compiles_after_first_step == 0,
          "the decode loop compiled after its first step")

    model = build(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    ref = jax.jit(lambda p, t: model.forward(p, t)[0][:, -1])(params, prompt)
    err = rel_err(gen.logits[:, 0], ref)
    print(f"[main] prefill last-token logits vs forward: max|diff|/max|ref| "
          f"= {err:.3e} (tolerance {BF16_TOL})", flush=True)
    check(err <= BF16_TOL, "prefill disagrees with forward")


# ------------------------------------------------------------- kernel phase
def kernel_cases(tokens: int):
    """(name, kernel thunk, reference thunk) at the published widths of the
    main path's kernels; ``tokens`` is the row / sequence count."""
    from repro.configs import get_config
    from repro.kernels.flash_attention import flash_attention, \
        ref as fa_ref
    from repro.kernels.matmul import matmul, ref as mm_ref
    from repro.kernels.rmsnorm import rmsnorm, ref as rms_ref
    from repro.kernels.ssd import ref as ssd_ref, ssd_scan

    dan = get_config("h2o-danube-1.8b")
    mam = get_config("mamba2-1.3b")
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    bf = jnp.bfloat16

    a = jax.random.normal(ks[0], (tokens, dan.d_model), bf)
    w = jax.random.normal(ks[1], (dan.d_model, dan.d_ff), bf)
    g = 1.0 + 0.1 * jax.random.normal(ks[2], (dan.d_model,), bf)

    hd = dan.head_dim
    q = jax.random.normal(ks[3], (1, dan.n_heads, tokens, hd), bf)
    k = jax.random.normal(ks[4], (1, dan.n_kv_heads, tokens, hd), bf)
    v = jax.random.normal(ks[5], (1, dan.n_kv_heads, tokens, hd), bf)
    attn = dict(sm_scale=hd ** -0.5, causal=True, window=dan.window)

    h = mam.d_model * mam.ssm_expand // mam.ssm_headdim
    n = mam.ssm_state
    kx = jax.random.split(ks[6], 5)
    x = jax.random.normal(kx[0], (1, tokens, h, mam.ssm_headdim))
    dt = jax.nn.softplus(jax.random.normal(kx[1], (1, tokens, h)) - 2.0)
    a_log = 0.5 * jax.random.normal(kx[2], (h,))
    bm = jax.random.normal(kx[3], (1, tokens, n)) / np.sqrt(n)
    cm = jax.random.normal(kx[4], (1, tokens, n)) / np.sqrt(n)
    chunk = min(mam.ssm_chunk, tokens)

    return [
        ("matmul", lambda i: matmul(a, w, interpret=i),
         lambda: mm_ref.matmul(a, w)),
        ("rmsnorm", lambda i: rmsnorm(a, g, interpret=i),
         lambda: rms_ref.rmsnorm(a, g)),
        ("flash_attention",
         lambda i: flash_attention(q, k, v, interpret=i, **attn),
         lambda: fa_ref.attention(q, k, v, **attn)),
        ("ssd", lambda i: ssd_scan(x, dt, a_log, bm, cm, chunk=chunk,
                                   interpret=i),
         lambda: ssd_ref.ssd_scan_ref(x, dt, a_log, bm, cm)),
    ]


def kernel_phase(*, tokens: int = 4096, interpret: bool = False) -> None:
    for name, run, reference in kernel_cases(tokens):
        t0 = time.perf_counter()
        out = jax.block_until_ready(run(interpret))
        wall = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            ref = reference()
        check(out.shape == ref.shape, f"{name}: shape {out.shape}")
        err = rel_err(out, ref)
        print(f"[kernel] {name} {tuple(out.shape)} {out.dtype} "
              f"interpret={interpret}: max|diff|/max|ref| = {err:.3e} "
              f"(tolerance {KERNEL_TOL}); first call {wall:.3f}s "
              f"incl. compilation (bring-up fact)", flush=True)
        check(err <= KERNEL_TOL, f"{name} disagrees with its reference")


# --------------------------------------------------------------- mesh phase
def _agree(single, sharded, tol: float) -> int:
    """Logits agree while both runs saw the same tokens; the first token
    that differs in a row must be a near tie in the one-device logits.
    Returns the number of rows whose tokens diverged."""
    t1, t2 = np.asarray(single.tokens), np.asarray(sharded.tokens)
    l1 = np.asarray(single.logits, np.float32)
    l2 = np.asarray(sharded.logits, np.float32)
    scale = max(float(np.max(np.abs(l1))), 1e-30)
    diverged = 0
    for r in range(t1.shape[0]):
        diff = np.nonzero(t1[r] != t2[r])[0]
        upto = int(diff[0]) if diff.size else t1.shape[1] - 1
        err = float(np.max(np.abs(l1[r, :upto + 1] - l2[r, :upto + 1])))
        check(err <= tol * scale, f"row {r}: logits differ by {err:.3e}")
        if diff.size:
            diverged += 1
            row = l1[r, upto]
            gap = float(row[t1[r, upto]] - row[t2[r, upto]])
            check(gap <= 2 * tol * scale,
                  f"row {r}: token {upto} differs with a clear winner")
    return diverged


def mesh_phase(*, smoke: bool = False, batch: int = 8, prompt_len: int = 256,
               max_new: int = 16, seed: int = 0) -> None:
    from repro.configs import get_config
    from repro.distributed import sharding
    from repro.launch.mesh import make_mesh
    from repro.models import build
    from repro.train.serve_step import greedy_generate

    devs = jax.devices()
    check(len(devs) >= 4, f"the mesh phase needs 4 devices, has {len(devs)}")
    cfg = get_config(ARCH, smoke=smoke)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                (batch, prompt_len), 0, cfg.vocab)
    single = jax.block_until_ready(
        greedy_generate(model, params, prompt, max_new=max_new))

    mesh = make_mesh((2, 2), ("data", "model"), devices=devs[:4])
    t0 = time.perf_counter()
    with sharding.use_mesh(mesh):
        params_d = jax.device_put(params,
                                  sharding.param_shardings(mesh, params))
        prompt_d = jax.device_put(prompt, sharding.tree_shardings(
            mesh, sharding.batch_specs_tree(prompt, mesh=mesh)))
        sharded = jax.block_until_ready(
            greedy_generate(model, params_d, prompt_d, max_new=max_new))
    wall = time.perf_counter() - t0
    print(f"[mesh] (data=2, model=2) serve {cfg.name} batch={batch} "
          f"prompt={prompt_len} new={max_new}: wall {wall:.3f}s incl. "
          f"compilation (bring-up fact)", flush=True)

    held = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(params_d):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    for d, nbytes in held.items():
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"[mesh] device {d.id}: params {nbytes} B, peak in use "
              f"{'not reported' if peak is None else f'{peak} B'}",
              flush=True)
    all_hold = all(b > 0 for b in held.values())
    print(f"[mesh] all {len(held)} devices hold parameters: {all_hold}",
          flush=True)
    check(all_hold, "a device of the mesh holds no parameters")
    check(sharded.logits.sharding.device_set == set(held),
          "the sharded logits are not on the mesh")
    print(f"[mesh] compilations in the sharded decode loop after its first "
          f"step: {sharded.compiles_after_first_step}", flush=True)
    check(sharded.compiles_after_first_step == 0,
          "the sharded decode loop compiled after its first step")
    diverged = _agree(single, sharded, BF16_TOL)
    err = rel_err(sharded.logits[:, 0], single.logits[:, 0])
    print(f"[mesh] sharded vs one device: prefill logits max|diff|/max|ref| "
          f"= {err:.3e} (tolerance {BF16_TOL}); rows whose tokens diverged "
          f"at a near tie: {diverged}/{batch}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[cache] {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        mesh_phase()
    else:
        main_phase()
        kernel_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
