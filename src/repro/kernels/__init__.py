"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel subpackage ships three layers:
  * kernel.py — pl.pallas_call body with explicit BlockSpec VMEM tiling
                (TPU is the TARGET; validated on CPU via interpret=True)
  * ops.py    — jit'd public wrapper (shape plumbing, GQA mapping, dtypes)
  * ref.py    — pure-jnp oracle for allclose validation

Kernels:
  flash_attention — row/column-blocked attention with online softmax,
                    causal + sliding-window masking, GQA
  ssd             — Mamba2 state-space-dual chunked scan
  rmsnorm         — fused RMSNorm * weight
  matmul          — tiled MXU matmul (the microbenchmark kernel: its block
                    sweep feeds the analytical model's tile-selection demo)

The paper's own hot-spots are GEMM/attention-class kernels (its validation
classes); ``matmul`` doubles as the tensor-throughput microbenchmark from
§V-A, adapted from CUDA tiles to MXU-aligned BlockSpecs.

Subpackages are imported by name (``repro.kernels.ssd`` ...), never here:
importing Pallas takes seconds of a process's start, which a model whose
path runs no kernel should not pay.
"""
