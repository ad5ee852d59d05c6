"""State space: device time a step in the chunked Mamba-2 scan, forward,
recomputed forward and backward, in ms.

Matches, among the top-level device ops: every `while` whose carried tuple
holds the scan's state `f32[B, G, R, P, N]` (H = G x R heads of P, state N:
the scan over chunks and its backward), and every op whose instruction names
a tensor in the chunked layout, which leads with the N chunks, the batch and
the G groups: `[N, B, G, ...` (the cumulative decays, C B^T, the in-chunk
scores, the chunks' states and all their cotangents: all that is computed
for all chunks at once; XLA drops a batch of 1 from some of these shapes, so
at one sequence a step `[N, G, ...` counts too, and it turns some of the
`[N, B, G, R, C]` vectors into `[N, C, G, R]`). The convolution, the gated
norm and the projections around the scan are not in it.
"""

from benchmarks.layer_metrics import hybrid_ops
from benchmarks.layer_metrics import nemotron_ops


def read(run):
  sizes = nemotron_ops.sizes_of(run)
  ops, steps = hybrid_ops.step_ops(run)
  if not sizes or not ops:
    return None
  b, g = run["batch_size"], sizes["n_groups"]
  r = sizes["mamba_num_heads"] // g
  chunk = min(sizes["chunk_size"], sizes["sequence_length"])
  n = -(-sizes["sequence_length"] // chunk)
  state = (f"f32[{b},{g},{r},{sizes['mamba_head_dim']},"
           f"{sizes['ssm_state_size']}]")
  layouts = [f"[{n},{b},{g},", f"[{n},{chunk},{g},{r}]"] + (
      [f"[{n},{g},"] if b == 1 else [])
  chosen = [e for e in ops
            if (hybrid_ops.opcode(e[2]) == "while" and state in e[2])
            or any(layout in e[2] for layout in layouts)]
  if not chosen:
    return None
  return sum(e[4] for e in chosen) / 1e6 / steps
