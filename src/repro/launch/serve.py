"""Serving driver: batched prefill + greedy decode.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b --smoke \
        --batch 4 --prompt-len 32 --max-new 16
"""
from __future__ import annotations

import argparse
import time
from typing import Tuple

import jax
import jax.numpy as jnp

from ..configs import get_config, memory_len
from ..models import build
from ..train.serve_step import Generation, greedy_generate
from .compile_cache import enable_compile_cache


def serve(arch: str, *, smoke: bool = True, batch: int = 4,
          prompt_len: int = 32, max_new: int = 16, seed: int = 0
          ) -> Tuple[jax.Array, Generation]:
    """Greedy-decode ``max_new`` tokens for a random prompt; returns the
    prompt and the ``Generation``.  Weights and prompt come from ``seed``."""
    cfg = get_config(arch, smoke=smoke)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    prompt = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)
    mem = None
    mlen = memory_len(cfg, prompt_len)
    if mlen is not None:
        mem = jax.random.normal(key, (batch, max(mlen, 4), cfg.d_model),
                                jnp.float32)
    t0 = time.perf_counter()
    out = greedy_generate(model, params, prompt, max_new=max_new,
                          memory_embeds=mem)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    toks = batch * max_new
    print(f"[serve] {arch}: generated {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. prefill and compilation)")
    return prompt, out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    # BooleanOptionalAction so --no-smoke actually reaches the full-size
    # configs (action="store_true" with default=True made every invocation
    # smoke mode, flag or not)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    return ap


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    serve(args.arch, smoke=args.smoke, batch=args.batch,
          prompt_len=args.prompt_len, max_new=args.max_new)


if __name__ == "__main__":
    main()
