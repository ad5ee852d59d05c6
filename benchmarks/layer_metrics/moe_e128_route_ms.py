"""Experts: device time a step in routing at this configuration's sizes, in
ms: the router's sigmoid and top-k, the sort of the (token, expert) pairs,
the gather into the row buffer and the scatter-add back by token, forward,
recomputed forward and backward.

Matches, among the top-level device ops: every `sort`, and every op that is
not a grouped product and whose instruction names a tensor only routing has:
the router's `[N, 128]` outputs, the `[N, 6]` picks or the `[N x 6]` pairs,
or the `[rows, 2688]` buffer (gathered from the tokens, scaled by the pairs'
weights, scatter-added). N tokens a step, `rows` the static buffer
(`moe_route_ms.buffer_rows`, the program's own arithmetic).
"""

from benchmarks.layer_metrics import moe_route_ms
from benchmarks.layer_metrics import nemotron_ops


def read(run):
  return moe_route_ms.read(nemotron_ops.as_hybrid(run))
