"""Kernels: the one flash backward kernel's share of its roofline at this
cell's shape (2 x 4096 x 16 heads of 256), in %. The kernel is found by its
name, `flash_bwd`. FLOPs: the five products the algorithm needs over the
causal half square (one score recomputation, dP, dV, dK, dQ); bytes: q, k, v
and dO read and dq, dk, dv written once in bf16, the log-sum-exp and delta
rows in float32. Compute-bound: 1.74 ms of FLOPs against 0.29 ms of bytes.
"""

from benchmarks.layer_metrics import hybrid_ops


def flops(bh: int, t: int, d: int) -> float:
  return 5 * 2.0 * bh * (t * t / 2.0) * d


def hbm_bytes(bh: int, t: int, d: int) -> float:
  return 7.0 * bh * t * d * 2 + 2 * bh * t * 4


def read(run):
  return hybrid_ops.flash_share(run, "flash_bwd", flops, hbm_bytes)
