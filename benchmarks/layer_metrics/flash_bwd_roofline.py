"""Kernels: the two flash backward kernels' (dq, and dk with dv) share of
their roofline, in %, taken together: the algorithm has one backward.

FLOPs: the five products the backward needs over half the square (the scores
recomputed once, dP, dV, dQ, dK). The repository's two kernels each recompute
the scores and dP, seven products in all; the two extra are recomputation and
count nothing, so a backward that streams q once can gain here. Bytes: q, k,
v, o, do read and dq, dk, dv written once in bf16; log-sum-exp and delta read
in float32.
"""

from benchmarks.layer_metrics import flash_kernels


def flops(bh: int, t: int, d: int) -> float:
  return 5 * 2.0 * bh * (t * t / 2.0) * d


def hbm_bytes(bh: int, t: int, d: int) -> float:
  return 8.0 * bh * t * d * 2 + 2.0 * bh * t * 4


def read(run):
  return flash_kernels.roofline_share(run, ("dq", "dkv"), flops, hbm_bytes,
                                      calls_of="dq")
