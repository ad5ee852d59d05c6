"""Kernels: the one flash backward kernel's share of its roofline at the
sequence cell's shape (128 x 2048 x 8 heads of 64), in %. The kernel is
found by its name, `flash_bwd` (`flash_kernels.named_events`); a trace of
the two-kernel backward it replaced has none, and reads nothing. FLOPs and
bytes as `flash_d256_bwd_roofline.py` counts them: the five products the
algorithm needs over the causal half square (one score recomputation, dP,
dV, dK, dQ); q, k, v and dO read and dq, dk, dv written once in bf16, the
log-sum-exp and delta rows in float32. Compute-bound: 6.98 ms of FLOPs
against 2.31 ms of bytes a call on the v5e.
"""

from benchmarks.layer_metrics import flash_d256_bwd_roofline
from benchmarks.layer_metrics import flash_kernels

flops = flash_d256_bwd_roofline.flops
hbm_bytes = flash_d256_bwd_roofline.hbm_bytes


def read(run):
  return flash_kernels.roofline_share(
      run, lambda events: flash_kernels.named_events(events, "flash_bwd"),
      flops, hbm_bytes)
