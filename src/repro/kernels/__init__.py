"""kernels — Pallas TPU kernels for the framework's compute hot spots.

Each kernel ships three artifacts:
  <name>.py   pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py      dispatch wrappers (TPU, or the interpreter when asked)
  ref.py      pure-jnp oracles the tests assert against

Kernels present:
  matmul          blocked MXU matmul (128-aligned tiles, f32 accumulator)
  flash_attention causal GQA flash attention (online softmax over KV tiles)
  rmsnorm         fused RMS-norm

These correspond to the recurring kernel signatures the paper's technique
models (gemm-like and normalization routines dominate the LM step's
critical path, exactly as BLAS kernels dominate the paper's factorization
schedules).
"""

from .ops import matmul, flash_attention, rmsnorm

__all__ = ["matmul", "flash_attention", "rmsnorm"]
