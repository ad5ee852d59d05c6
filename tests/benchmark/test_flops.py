"""`model_flops` and the flash kernels' FLOP and byte counts against numbers
worked by hand."""

import json
import os

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import peaks
from benchmarks.references import qtopt_grasping44_472 as g44
from benchmarks.references import seq_trunk_h512 as seq


def _model(name):
  with open(os.path.join(manifest.BENCH_DIR, "configs", f"{name}.json")) as f:
    return json.load(f)["model"]


def test_grasping44_flops_by_hand():
  sizes = g44.sizes_from_bindings(_model("qtopt_grasping44_472"))
  # 472 -> stem /2 -> 236 -> pool /3 -> 79 -> pool /3 -> 27 -> pool /2 -> 14
  # -> three VALID 3x3 -> 12, 10, 8.
  macs = (236 * 236 * 64 * (6 * 6 * 3)
          + 6 * 79 * 79 * 64 * (5 * 5 * 64)
          + 6 * 27 * 27 * 64 * (3 * 3 * 64)
          + (12 * 12 + 10 * 10 + 8 * 8) * 64 * (3 * 3 * 64)
          + 3 * 256 + 2 * 256 + 256 * 64
          + 8 * 8 * 64 * 64 + 64 * 64 + 64)
  assert g44.model_flops(sizes, 1) == pytest.approx(6.0 * macs)
  assert g44.model_flops(sizes, 256) == pytest.approx(6.0 * macs * 256)
  # about 4.39 G multiply-adds a row, 6.75 TFLOP a step of 256 rows
  assert macs == pytest.approx(4.392e9, rel=1e-3)
  _, last = g44.layer_shapes(sizes)
  assert last == 8


def test_sequence_trunk_flops_by_hand():
  sizes = seq.sizes_from_bindings(
      {**_model("seq_trunk_h512"), "sequence_length": 4096})
  t, h = 4096, 512
  per_token = 16 * h + 2 * (4 * h * h + 2 * h * 2 * h) + h * 7
  attention = 2 * 2 * 2.0 * (t * t / 2) * h   # 2 blocks, QK^T and PV
  forward = 2.0 * per_token * t + attention
  assert seq.attention_flops_forward(sizes) == pytest.approx(attention)
  assert seq.model_flops(sizes, 1) == pytest.approx(3 * forward)
  # 206 GFLOP a sequence, of which attention is half (103)
  assert seq.model_flops(sizes, 1) == pytest.approx(206.4e9, rel=1e-3)
  assert 3 * attention == pytest.approx(103.1e9, rel=1e-3)


def test_flash_kernel_counts_by_hand():
  from benchmarks.layer_metrics import flash_bwd_roofline as bwd
  from benchmarks.layer_metrics import flash_fwd_roofline as fwd

  # one call: 64 sequences x 8 heads, T 4096, head size 64, bf16
  bh, t, d = 64 * 8, 4096, 64
  square = bh * t * t / 2.0                 # causal: half the square
  assert fwd.flops(bh, t, d) == pytest.approx(2 * 2 * square * d)
  # q, k, v read and o written once (bf16), the log-sum-exp written (f32)
  assert fwd.hbm_bytes(bh, t, d) == pytest.approx(
      4 * bh * t * d * 2 + bh * t * 4)
  # backward: the 5 products the algorithm needs over the half square (dV,
  # dP, dQ, dK and one score recomputation), however many a kernel runs.
  assert bwd.flops(bh, t, d) == pytest.approx(5 * 2 * square * d)
  # q, k, v, do read; dq, dk, dv written (bf16); lse and delta read (f32)
  assert bwd.hbm_bytes(bh, t, d) == pytest.approx(
      7 * bh * t * d * 2 + 2 * bh * t * 4)


def test_peaks_table():
  v5e = peaks.peaks_for("TPU v5 lite")
  assert v5e["bf16_flops_per_s"] == 197e12
  assert v5e["hbm_bytes_per_s"] == 819e9
  assert v5e["hbm_bytes"] == 16e9
  with pytest.raises(KeyError):
    peaks.peaks_for("TPU v9 imaginary")
