"""Kernels: the grouped expert products' share of their roofline at this
configuration's sizes (8 un-gated relu^2 experts of 2688 x 1856 held), in %.

The products are the program's Pallas kernels `grouped_matmul` and
`grouped_matmul_t` (or XLA's `ragged-dot`), found, read and counted as
`moe_experts_roofline.py` says: FLOPs 2 x the rows held (`moe_rows_held`) x
the expert's matrix; bytes the weights whole and the row operands at the
buffer's fill; empty tiles that are visited count nothing. Here the op hands
the [8, 2688, 1856] weights over as [8, 1856, 2688] (the lane-aligned 2688
last), which changes no count. At 192 rows an expert the weights' bytes (80
MB a call) bound it.
"""

from benchmarks.layer_metrics import moe_experts_roofline
from benchmarks.layer_metrics import nemotron_ops


def read(run):
  return moe_experts_roofline.read(nemotron_ops.as_hybrid(run))
