"""Kernels: the grouped expert products' share of their roofline, in %.

The products are XLA's `ragged-dot` kernels (`jax.lax.ragged_dot`; eight a
layer and step: gate-and-up and down, each forward, forward again under
rematerialisation, and backward for the rows and for the weights). Least
time of a call = the larger of FLOPs / bf16 peak and bytes / HBM bandwidth:
FLOPs 2 x rows held x the expert's matrix (the rows the router really sent,
by the program's counter `moe_rows_held`, not the buffer's rows: tiles that
hold no pair are visited and count nothing); bytes the expert weights read
or written whole and the row operands at the share of the buffer that is
filled. The share is the calls' summed least time over their summed device
time. At 160 rows an expert the weights' bytes and the FLOPs are of one size.
"""

import statistics

from benchmarks.layer_metrics import hybrid_ops


def call_flops(rows_held: float, matrix_elements: float) -> float:
  return 2.0 * rows_held * matrix_elements


def call_bytes(weight_bytes: float, row_bytes: float, fill: float) -> float:
  return weight_bytes + row_bytes * fill


def read(run):
  sizes, peaks = hybrid_ops.sizes_of(run), run.get("peaks")
  ops, _ = hybrid_ops.step_ops(run)
  held = hybrid_ops.counter_records(run, "moe_rows_held")
  if not sizes or not peaks or not ops or not held:
    return None
  calls = [e for e in ops if hybrid_ops.is_grouped_product(e[2])]
  if not calls:
    return None
  rows_held = statistics.mean(v for record in held for v in record)
  experts = sizes["num_experts"]
  least = 0.0
  for e in calls:
    tensors = [s for s in hybrid_ops.shapes(e[2]) if len(s[1]) >= 2]
    weights = [s for s in tensors if len(s[1]) == 3 and s[1][0] == experts]
    rows = [s for s in tensors if len(s[1]) == 2]
    if not weights or not rows:
      return None  # not the products this reader knows
    buffer_rows = max(s[1][0] for s in rows)
    matrix = weights[0][1][1] * weights[0][1][2]
    seconds = max(
        call_flops(rows_held, matrix) / peaks["bf16_flops_per_s"],
        call_bytes(sum(map(hybrid_ops.nbytes, weights)),
                   sum(map(hybrid_ops.nbytes, rows)),
                   min(1.0, rows_held / buffer_rows))
        / peaks["hbm_bytes_per_s"])
    least += seconds
  return 100.0 * least / (sum(e[4] for e in calls) / 1e9)
