"""Kernels: the flash forward kernel's share of its roofline at this cell's
shape (1 x 4096 x 32 heads of 128: one head a program), in %. The kernel is
found by its name, `flash_fwd`; a step calls it twice (the forward, and the
forward again under rematerialisation). FLOPs and bytes as
`flash_d256_fwd_roofline.py` counts them: the two products over the causal
half square; q, k, v read and o written once in bf16, the log-sum-exp in
float32. Compute-bound: 0.70 ms of FLOPs against 0.16 ms of bytes a call.
"""

from benchmarks.layer_metrics import flash_d256_fwd_roofline
from benchmarks.layer_metrics import nemotron_ops


def read(run):
  return flash_d256_fwd_roofline.read(nemotron_ops.as_hybrid(run))
