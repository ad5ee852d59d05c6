"""Kernels: the delta rule's triangular inverse, the Pallas kernel
`gdn_inverse` (`ops/linear_attention.py`), as a share of its roofline at the
hybrid cell's sizes, in %. The kernel is found by its name.

A call inverts (I + A) for every chunk and head at once: A is f32
[N, B, H_v, C, C], N = T / C chunks of C = 64. By the op's docstring it is
read once and the inverse written once (bytes: 2 x N x B x H_v x C x C x 4),
and the inverse is the doubling product (I + N)(I + N^2)(I + N^4)... of
log2(C) factors: 2 x (log2(C) - 1) [C, C] products a head and chunk, ten at
C 64, at float32 accuracy (`highest`), which the v5e's MXU makes of six
bfloat16 passes; so FLOPs 6 x 2 x C^3 a product, against the bf16 peak.
What the kernel's layout adds (two heads side by side against their powers
laid block-diagonally, half of whose blocks are zeros) counts nothing, so a
kernel that stops multiplying zeros gains here. Least time of a call = the
larger of FLOPs / peak and bytes / HBM bandwidth: compute-bound at the
cell's sizes, 0.33 ms of products against 0.08 ms of bytes a call. The
share is calls x least time over their summed device time.
"""

import math

from benchmarks.layer_metrics import gdn_scan_ms
from benchmarks.layer_metrics import hybrid_ops

MXU_PASSES = 6  # a float32 product at `highest` on the bfloat16 MXU


def flops(blocks: int, c: int) -> float:
  products = 2 * (math.ceil(math.log2(c)) - 1)
  return MXU_PASSES * products * 2.0 * c ** 3 * blocks


def hbm_bytes(blocks: int, c: int) -> float:
  return 2.0 * blocks * c * c * 4


def read(run):
  sizes, peaks = hybrid_ops.sizes_of(run), run.get("peaks")
  ops, _ = hybrid_ops.step_ops(run)
  if not sizes or not peaks or not ops:
    return None
  calls = [e for e in ops if hybrid_ops.kernel_name(e[2]) == "gdn_inverse"]
  if not calls:
    return None
  c = min(gdn_scan_ms.CHUNK, sizes["sequence_length"])
  blocks = (-(-sizes["sequence_length"] // c) * run["batch_size"]
            * sizes["linear_num_value_heads"])
  least = max(flops(blocks, c) / peaks["bf16_flops_per_s"],
              hbm_bytes(blocks, c) / peaks["hbm_bytes_per_s"])
  return 100.0 * len(calls) * least / (sum(e[4] for e in calls) / 1e9)
