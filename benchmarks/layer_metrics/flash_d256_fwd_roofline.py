"""Kernels: the flash forward kernel's share of its roofline at this cell's
shape (2 x 4096 x 16 heads of 256: one head a program, the reduced
denominator), in %. The kernel is found by its name, `flash_fwd`; a step
calls it twice a layer (the forward, and the forward again under
rematerialisation). FLOPs: the two products over the causal half square;
bytes: q, k, v read and o written once in bf16, the log-sum-exp in float32.
Compute-bound: 0.70 ms of FLOPs against 0.16 ms of bytes a call on the v5e.
"""

from benchmarks.layer_metrics import hybrid_ops


def flops(bh: int, t: int, d: int) -> float:
  return 2 * 2.0 * bh * (t * t / 2.0) * d


def hbm_bytes(bh: int, t: int, d: int) -> float:
  return 4.0 * bh * t * d * 2 + bh * t * 4


def read(run):
  return hybrid_ops.flash_share(run, "flash_fwd", flops, hbm_bytes)
