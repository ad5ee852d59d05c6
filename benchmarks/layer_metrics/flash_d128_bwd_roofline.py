"""Kernels: the one flash backward kernel's share of its roofline at this
cell's shape (1 x 4096 x 32 heads of 128), in %. The kernel is found by its
name, `flash_bwd`. FLOPs and bytes as `flash_d256_bwd_roofline.py` counts
them: the five products the algorithm needs over the causal half square; q,
k, v and dO read and dq, dk, dv written once in bf16, the log-sum-exp and
delta rows in float32. Compute-bound: 1.74 ms of FLOPs against 0.29 ms of
bytes.
"""

from benchmarks.layer_metrics import flash_d256_bwd_roofline
from benchmarks.layer_metrics import nemotron_ops


def read(run):
  return flash_d256_bwd_roofline.read(nemotron_ops.as_hybrid(run))
