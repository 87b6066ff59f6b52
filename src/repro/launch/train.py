"""Production training driver.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On the real cluster this runs under SPMD with the production mesh; in this
container it runs single-host (smoke configs) with the same code path:
deterministic data, WSD/cosine schedule per arch, gradient clipping,
async checkpointing every N steps, exact resume, preemption-safe saves.
"""
from __future__ import annotations

import argparse
import signal
import time
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs import get_config
from ..data import SyntheticLMData
from ..models import build
from ..optim.schedule import for_arch
from ..train import checkpoint as ckpt
from ..train.train_step import init_state, make_train_step
from .compile_cache import enable_compile_cache


def train(arch: str, *, smoke: bool = True, steps: int = 50,
          batch: int = 8, seq: int = 128, lr: float = 3e-4,
          microbatches: int = 1, compress_grads: bool = False,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 25,
          log_every: int = 10, seed: int = 0,
          resume: bool = True) -> dict:
    cfg = get_config(arch, smoke=smoke)
    model = build(cfg)
    data = SyntheticLMData(cfg, batch=batch, seq_len=seq, seed=seed)
    schedule = for_arch(arch, lr, max(steps // 20, 5), steps)
    step_fn = jax.jit(make_train_step(
        model, lr=schedule, microbatches=microbatches,
        compress_grads=compress_grads))

    start_step = 0
    state = init_state(model, jax.random.PRNGKey(seed),
                       compress_grads=compress_grads)
    if ckpt_dir and resume:
        latest = ckpt.latest_step_dir(ckpt_dir)
        if latest:
            like = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
            state, manifest = ckpt.restore(latest, like)
            start_step = manifest["step"]
            print(f"[train] resumed from {latest} at step {start_step}")

    saver = ckpt.AsyncCheckpointer()
    interrupted = {"flag": False}

    def _on_signal(signum, frame):     # preemption-safe emergency save
        interrupted["flag"] = True
    old = signal.signal(signal.SIGTERM, _on_signal)

    losses = []
    t0 = time.time()
    try:
        for step in range(start_step, steps):
            b = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
            state, metrics = step_fn(state, b)
            losses.append(float(metrics["loss"]))
            if log_every and (step + 1) % log_every == 0:
                rate = (step + 1 - start_step) / (time.time() - t0)
                print(f"[train] step {step + 1}/{steps} "
                      f"loss {losses[-1]:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"({rate:.2f} it/s)")
            if ckpt_dir and ((step + 1) % ckpt_every == 0
                             or interrupted["flag"]):
                saver.save(f"{ckpt_dir}/ckpt_{step + 1:06d}", state,
                           step=step + 1)
            if interrupted["flag"]:
                print("[train] SIGTERM: emergency checkpoint written")
                break
    finally:
        saver.wait()
        signal.signal(signal.SIGTERM, old)
    if ckpt_dir:
        saver.save(f"{ckpt_dir}/ckpt_{steps:06d}", state, step=steps)
        saver.wait()
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "state": state}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = train(args.arch, smoke=args.smoke, steps=args.steps,
                batch=args.batch, seq=args.seq, lr=args.lr,
                microbatches=args.microbatches,
                compress_grads=args.compress_grads,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    print(f"[train] done; final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
