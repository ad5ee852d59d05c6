"""Kernels: the windowed flash backward kernel's share of its roofline at
this cell's shape (1 x 4096 x 64 heads of 128, window 512), in %. The kernel
is found by its name, `flash_window_bwd`. FLOPs: the five products the
algorithm needs over the band (one score recomputation, dP, dV, dK, dQ), as
`flash_d256_bwd_roofline.py` counts them over the causal half square; bytes:
q, k, v and dO read and dq, dk, dv written once in bf16, the log-sum-exp and
delta rows in float32. Compute-bound: 0.82 ms of FLOPs against 0.58 ms of
bytes a call on the v5e.
"""

from benchmarks.layer_metrics import laguna_ops


def read(run):
  return laguna_ops.window_share(run, "flash_window_bwd", products=5,
                                 operands=7, rows=2)
