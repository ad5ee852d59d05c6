"""Kernels: the grouped expert products' share of their roofline, in %.

The products are the program's Pallas kernels (`ops/grouped_matmul.py`),
found by their own names, or XLA's `ragged-dot` where a program still runs
`jax.lax.ragged_dot` (`hybrid_ops.is_grouped_product`): eight a layer and
step, each of the layer's two matrices in its forward, its forward again
under rematerialisation and its backward for the rows and for the weights.
What a kernel's instruction names, and what is read of it (every shape of
two dimensions or more; the three 1-D `s32` scalar-prefetch operands, the
groups' offsets and the visit lists, are left out by that rule):

    grouped_matmul    lhs bf16 [rows, K], weights [G, K, N] (or [G, N, K]
                      where the op hands them over swapped so that the
                      lane-aligned width is last) -> f32 [rows, N]; the
                      rows' cotangent is the same kernel with the weights
                      read transposed, -> [rows, K] in the rows' dtype
    grouped_matmul_t  lhs [rows, K], the cotangent [rows, N] -> weights'
                      cotangent [G, K, N]

The weights are the shapes of three dimensions that lead with the G
experts held (read, or in `grouped_matmul_t` written); the rows are the
shapes of two, all of the static buffer's `rows`.

Least time of a call = the larger of FLOPs / bf16 peak and bytes / HBM
bandwidth. FLOPs: 2 x the rows held x the expert's matrix (K x N), the rows
held being the program's counter `moe_rows_held` (the pairs the router
really sent), not the buffer's rows. Bytes: the weights whole plus the row
operands at the buffer's fill (rows held / buffer rows). Tiles that hold no
pair are visited all the same and count nothing, so a change that stops
visiting them gains here. The share is the calls' summed least time over
their summed device time. At 160 rows an expert the weights' bytes and the
FLOPs are of one size.
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
