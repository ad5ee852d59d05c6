"""Kernels: the windowed flash forward kernel's share of its roofline at this
cell's shape (1 x 4096 x 64 heads of 128, window 512: the sliding layers), in
%. The kernel is found by its name, `flash_window_fwd`; a step calls it twice
a sliding layer (the forward, and the forward again under
rematerialisation). FLOPs: the two products over the band, 4 d sum_i
min(i + 1, 512) a head; bytes: q, k, v read and o written once in bf16, the
log-sum-exp in float32. At the balance point on the v5e: 0.327 ms of FLOPs
against 0.329 ms of bytes a call, so a share near 100 % needs both.
"""

from benchmarks.layer_metrics import laguna_ops


def read(run):
  return laguna_ops.window_share(run, "flash_window_fwd", products=2,
                                 operands=4, rows=1)
