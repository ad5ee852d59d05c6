"""Kernels: the Mamba-2 chunked scan, the Pallas kernels `ssd_scan`
(forward) and `ssd_scan_bwd` (backward) of `ops/state_space.py`, taken
together as a share of their roofline, in %. The Mamba-2 / attention cell
only; the kernels are found by their names.

The count comes from the cell's sizes: R = batch x T rows, H heads of P,
G groups sharing B and C of the state size N, chunks of C rows (T / C of
them a sequence), and the element size of the convolution's result
`mixed` [B, T, H P + 2 G N] that the kernels read x, B and C from (its
dtype as the trace shows it). A forward call computes C B^T once a group
and chunk (2 C^2 N), and per head and chunk the chunk's own product, the
read of the entering state and the state's update (2 C^2 P + 4 C P N):
FLOPs B (T / C) (G 2 C^2 N + H (2 C^2 P + 4 C P N)); it reads x, B and C
and dt (float32) and writes y (float32) once. A backward call takes two
products for each of the forward's (twice its FLOPs); it reads x, B, C,
dt and dy (float32) and writes the cotangent of x, B and C (in `mixed`'s
dtype) and ddt (float32) once. Nothing counts for the states the forward
keeps for the backward, nor for their read: a kernel that stores or
recomputes them pays that from its own share. Least time of a call = the
larger of FLOPs / bf16 peak and bytes / HBM bandwidth (bytes at the cell's
sizes: 0.145 ms a forward, 0.207 ms a backward call). The share is the
calls' summed least time over their summed device time.
"""

from benchmarks.layer_metrics import hybrid_ops
from benchmarks.layer_metrics import nemotron_ops

KERNELS = ("ssd_scan", "ssd_scan_bwd")


def call_cost(kernel: str, sizes, batch: int, element: float):
  """(FLOPs, bytes) of one call of `kernel` at the cell's sizes."""
  t, h = sizes["sequence_length"], sizes["mamba_num_heads"]
  p, g = sizes["mamba_head_dim"], sizes["n_groups"]
  n, c = sizes["ssm_state_size"], sizes["chunk_size"]
  rows, chunks = batch * t, batch * t // c
  flops = chunks * (g * 2.0 * c * c * n
                    + h * (2.0 * c * c * p + 4.0 * c * p * n))
  mixed = rows * (h * p + 2 * g * n) * element
  dt, y = rows * h * 4.0, rows * h * p * 4.0
  if kernel == "ssd_scan":
    return flops, mixed + dt + y
  return 2.0 * flops, 2.0 * mixed + 2.0 * dt + y


def read(run):
  sizes, peaks = nemotron_ops.sizes_of(run), run.get("peaks")
  ops, _ = hybrid_ops.step_ops(run)
  if not sizes or not peaks or not ops:
    return None
  batch = run["batch_size"]
  width = (sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
           + 2 * sizes["n_groups"] * sizes["ssm_state_size"])
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
