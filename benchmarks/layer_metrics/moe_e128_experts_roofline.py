"""Kernels: the grouped expert products' share of their roofline at this
configuration's sizes (8 un-gated relu^2 experts of 2688 x 1856 held), in %.

The products are XLA's `ragged-dot` kernels (`jax.lax.ragged_dot`; eight a
layer and step: up and down, each forward, forward again under
rematerialisation, and backward for the rows and for the weights). Least
time of a call, FLOPs and bytes as `moe_experts_roofline.py` counts them:
FLOPs 2 x rows held x the expert's matrix (the rows the router really sent,
by the program's counter `moe_rows_held`); bytes the expert weights read or
written whole and the row operands at the share of the buffer that is
filled. The share is the calls' summed least time over their summed device
time. At 192 rows an expert the weights' bytes (80 MB a call) bound it.
"""

from benchmarks.layer_metrics import moe_experts_roofline
from benchmarks.layer_metrics import nemotron_ops


def read(run):
  return moe_experts_roofline.read(nemotron_ops.as_hybrid(run))
