"""Kernels: the flash forward kernel's share of its roofline, in %.

Least time of one call = the larger of FLOPs / bf16 peak and bytes / HBM
bandwidth, from this file's own counts for the call's shapes; the share is
calls x least time over the summed device time of the kernel's events in the
traced window. At T 4096 and head size 64 the bound is compute (2.8 ms of
FLOPs against 0.7 ms of bytes a call on the v5e).

The kernel is found by what it returns, which reads in a trace recorded
before the kernels had names as well as in one after (`flash_kernels.py`).
"""

from benchmarks.layer_metrics import flash_kernels


def flops(bh: int, t: int, d: int) -> float:
  """Causal QK^T and PV over half the square: 2 products x 2 FLOPs."""
  return 2 * 2.0 * bh * (t * t / 2.0) * d


def hbm_bytes(bh: int, t: int, d: int) -> float:
  """q, k, v read and o written once in bf16; the log-sum-exp written in
  float32."""
  return 4.0 * bh * t * d * 2 + bh * t * 4


def read(run):
  return flash_kernels.roofline_share(run, flash_kernels.forward_events,
                                      flops, hbm_bytes)
