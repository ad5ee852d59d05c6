"""Linear attention: device time a step in the chunked gated delta rule,
forward, recomputed forward and backward, in ms.

Matches, among the top-level device ops: every `while` whose carried tuple
holds the rule's state `f32[B, H_v, d_k, d_v]` (the scan over chunks and its
backward), and every op whose instruction names a tensor in the chunked
layout `[N, B, H_v, C, ...]` (the cumulative gates, the triangular system and
its inverse, the in-chunk scores: all that is computed for all chunks at
once; XLA drops a batch of 1 from some of these shapes, so at one sequence a
step `[N, H_v, C, ...]` counts too). The convolution and the projections
around the rule are not in it.
"""

from benchmarks.layer_metrics import hybrid_ops

CHUNK = 64


def read(run):
  sizes = hybrid_ops.sizes_of(run)
  ops, steps = hybrid_ops.step_ops(run)
  if not sizes or not ops:
    return None
  b, h = run["batch_size"], sizes["linear_num_value_heads"]
  chunk = min(CHUNK, sizes["sequence_length"])
  n = -(-sizes["sequence_length"] // chunk)
  state = (f"f32[{b},{h},{sizes['linear_key_head_dim']},"
           f"{sizes['linear_value_head_dim']}]")
  layouts = [f"[{n},{b},{h},{chunk}"] + ([f"[{n},{h},{chunk}"] if b == 1
                                          else [])
  chosen = [e for e in ops
            if (hybrid_ops.opcode(e[2]) == "while" and state in e[2])
            or any(layout in e[2] for layout in layouts)]
  if not chosen:
    return None
  return sum(e[4] for e in chosen) / 1e6 / steps
