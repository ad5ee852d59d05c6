"""Kernels: the gated delta rule, the Pallas kernels `gdn_scan` (forward)
and `gdn_scan_bwd` (backward) of `ops/linear_attention.py`, taken together
as a share of their roofline, in %. The hybrid cell only; the kernels are
found by their names.

The count comes from the cell's sizes: R = batch x T rows, H_k key heads
and H_v value heads of d_k and d_v, chunks of C rows (T / C of them a
sequence), and the element size of the convolution's result `mixed`
[B, T, 2 H_k d_k + H_v d_v] that the kernels read q, k and v from (its
dtype as the trace shows it). A forward call, per value head and chunk:
A = (k beta) k^T and q k^T, triangles at half (C^2 d_k each); the solve of
(I + A) against [v beta | k beta e^G] as a substitution (C^2 (d_k + d_v));
u = W - R S, (q e^G) S and the state's update (2 C d_k d_v each); P u at
half (C^2 d_v). It reads `mixed`, g and beta (float32) and writes o
(float32) once. A backward call takes two products for each of the
forward's (twice its FLOPs); it reads `mixed`, g, beta and dO (float32)
and writes the cotangent of `mixed` (in its dtype), dg and d beta
(float32) once. Nothing counts for the states and inverses the forward
keeps for the backward, nor for their read: a kernel that stores or
recomputes them pays that from its own share. So this is the least the
algorithm needs, and no kernel can read over 100 %. Least time of a call
= the larger of FLOPs / bf16 peak and bytes / HBM bandwidth (bytes at the
cell's sizes: 0.165 ms a forward, 0.249 ms a backward call). The share is
the calls' summed least time over their summed device time.
"""

from benchmarks.layer_metrics import gdn_scan_ms
from benchmarks.layer_metrics import hybrid_ops

KERNELS = ("gdn_scan", "gdn_scan_bwd")


def call_cost(kernel: str, sizes, batch: int, element: float):
  """(FLOPs, bytes) of one call of `kernel` at the cell's sizes."""
  t, c = sizes["sequence_length"], gdn_scan_ms.CHUNK
  k_heads, v_heads = (sizes["linear_num_key_heads"],
                      sizes["linear_num_value_heads"])
  d_k, d_v = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
  rows, chunks = batch * t, batch * t // c
  flops = chunks * v_heads * (c * c * (3.0 * d_k + 2.0 * d_v)
                              + 6.0 * c * d_k * d_v)
  mixed = rows * (2 * k_heads * d_k + v_heads * d_v) * element
  gates, o = 2.0 * rows * v_heads * 4.0, rows * v_heads * d_v * 4.0
  if kernel == "gdn_scan":
    return flops, mixed + gates + o
  return 2.0 * flops, 2.0 * mixed + 2.0 * gates + o


def read(run):
  sizes, peaks = hybrid_ops.sizes_of(run), run.get("peaks")
  ops, _ = hybrid_ops.step_ops(run)
  if not sizes or not peaks or not ops:
    return None
  batch = run["batch_size"]
  width = (2 * sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
           + sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"])
  least = seconds = 0.0
  for e in ops:
    kernel = hybrid_ops.kernel_name(e[2])
    if kernel in KERNELS:
      mixed = [s for s in hybrid_ops.shapes(e[2])
               if s[1] == (batch, sizes["sequence_length"], width)]
      if not mixed:
        return None  # not the kernels this reader knows
      flops, nbytes = call_cost(kernel, sizes, batch,
                                hybrid_ops.nbytes((mixed[0][0], ())))
      least += max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])
      seconds += e[4] / 1e9
  return 100.0 * least / seconds if seconds else None
