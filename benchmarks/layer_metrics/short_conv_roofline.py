"""Kernels: the two linear mixers' short convolution, the Pallas kernels
`short_conv` (forward) and `short_conv_bwd` (`ops/short_conv.py`), taken
together as a share of their roofline, in %. Both expert cells: the gated
DeltaNet's convolution over q, k and v (2 x 16 x 128 + 32 x 128 = 8,192
channels, no bias) and Mamba-2's over x, B and C (64 x 64 + 2 x 8 x 128 =
6,144 channels, with a bias), 4 taps each. The kernels are found by their
names.

The channels come from the cell's sizes, not from an operand's shape: the
kernels read their columns of the in-projection's result in place, so the
operand the trace shows is the whole projection. With R = batch x T rows,
C channels, w taps and the element size of the kernel's first result (x's
dtype), a forward call reads x and the taps (and bias) and writes y once:
(2 R C + w C (+ C)) elements; a backward call reads x, dy and the taps (and
bias) and writes dx, dk (and db) once: (3 R C + 2 w C (+ 2 C)). The rows
beside a tile that a kernel reads again count nothing. There is no MXU
product: the least time of a call is its bytes over HBM bandwidth. The
share is the calls' summed least time over their summed device time.
"""

from benchmarks.layer_metrics import hybrid_ops
from benchmarks.layer_metrics import nemotron_ops

KERNELS = ("short_conv", "short_conv_bwd")


def shape_of(run):
  """(channels, taps, bias) of the cell's convolution, or None."""
  sizes = nemotron_ops.sizes_of(run)
  if sizes:
    return (sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
            + 2 * sizes["n_groups"] * sizes["ssm_state_size"],
            sizes["conv_kernel"], True)
  sizes = hybrid_ops.sizes_of(run)
  if sizes:
    return (2 * sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
            + sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"],
            sizes["linear_conv_kernel_dim"], False)
  return None


def call_elements(kernel: str, rows: int, channels: int, taps: int,
                  bias: bool) -> float:
  if kernel == "short_conv":
    return 2.0 * rows * channels + taps * channels + bias * channels
  return 3.0 * rows * channels + 2 * taps * channels + 2 * bias * channels


def read(run):
  shape, peaks = shape_of(run), run.get("peaks")
  ops, _ = hybrid_ops.step_ops(run)
  if not shape or not peaks or not ops:
    return None
  rows = run["batch_size"] * run["sizes"]["sequence_length"]
  least = seconds = 0.0
  for e in ops:
    kernel = hybrid_ops.kernel_name(e[2])
    if kernel in KERNELS:
      results = hybrid_ops.shapes(e[2])
      if not results:
        return None  # not the kernels this reader knows
      size = hybrid_ops.nbytes((results[0][0], ()))
      least += (call_elements(kernel, rows, *shape) * size
                / peaks["hbm_bytes_per_s"])
      seconds += e[4] / 1e9
  return 100.0 * least / seconds if seconds else None
