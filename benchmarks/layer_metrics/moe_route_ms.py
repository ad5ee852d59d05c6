"""Experts: device time a step in routing, in ms: the router's softmax and
top-k, the sort of the (token, expert) pairs, the gather into the row buffer
and the scatter-add back by token, forward, recomputed forward and backward.

Matches, among the top-level device ops: every `sort`, and every op that is
not a grouped product and whose instruction names a tensor only routing has:
the router's `[N, router_width]` outputs, the `[N, k]` picks or the `[N x k]`
pairs, or the `[rows, hidden]` buffer (gathered from the tokens, scaled by
the pairs' weights, scatter-added). N tokens a step, k experts a token, `rows`
the static buffer.
"""

import math

from benchmarks.layer_metrics import hybrid_ops

ROW_TILE = 128


def buffer_rows(sizes, tokens: int) -> int:
  """The program's `ShardedExpertsMoE.buffer_rows`."""
  pairs = tokens * sizes["num_experts_per_tok"]
  balanced = pairs * sizes["num_experts"] / sizes["router_width"]
  tiles = max(1, math.ceil(sizes["expert_buffer_factor"] * balanced
                           / ROW_TILE))
  return min(tiles, -(-pairs // ROW_TILE)) * ROW_TILE


def read(run):
  sizes = hybrid_ops.sizes_of(run)
  ops, steps = hybrid_ops.step_ops(run)
  if not sizes or not ops:
    return None
  n = run["batch_size"] * sizes["sequence_length"]
  k = sizes["num_experts_per_tok"]
  marks = (f"[{n},{sizes['router_width']}]", f"[{n},{k}]", f"[{n * k}]",
           f"[{buffer_rows(sizes, n)},{sizes['hidden_size']}]")
  chosen = [e for e in ops
            if not hybrid_ops.is_grouped_product(e[2])
            and (hybrid_ops.opcode(e[2]) == "sort"
                 or any(m in e[2] for m in marks))]
  if not chosen:
    return None
  return sum(e[4] for e in chosen) / 1e6 / steps
