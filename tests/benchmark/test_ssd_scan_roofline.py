"""`ssd_scan_roofline` on nothing, on a step written by hand around
instruction texts of the two kernels at the nemotron cell's shape (the
forward twice, as rematerialisation runs it, the backward once), and on a
step of the XLA form, which has no such kernel."""

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import peaks
from benchmarks.harness import trace_reduce as tr
from tests.benchmark import test_nemotron_cell

D0 = "/device:TPU:0"
V5E = peaks.peaks_for("TPU v5 lite")
CELL = test_nemotron_cell.CELL
_MIXED = "bf16[1,4096,6144]{2,1,0:T(8,128)(2,1)}"
FORWARD = (
    "%ssd_scan.3 = (f32[1,4096,4096]{2,1,0:T(8,128)}, "
    "f32[1,8,512,128]{3,2,1,0:T(8,128)}) custom-call("
    f"{_MIXED} %fusion.1, {_MIXED} %fusion.1, {_MIXED} %fusion.1, "
    "f32[1,8,8,4096]{3,2,1,0:T(8,128)} %fusion.2, "
    "f32[8,8,1]{2,1,0:T(8,128)} %fusion.3, "
    "f32[8,1,512]{2,1,0:T(1,128)} %fusion.4), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={'
    "bf16[1,4096,6144]{2,1,0}}")
BACKWARD = (
    f"%ssd_scan_bwd.1 = ({_MIXED}, f32[1,8,8,4096]{{3,2,1,0:T(8,128)}}, "
    "f32[1,8,8,1]{3,2,1,0:T(8,128)}, f32[1,8,8,1]{3,2,1,0:T(8,128)}) "
    f"custom-call({_MIXED} %fusion.1, {_MIXED} %fusion.1, {_MIXED} "
    "%fusion.1, f32[1,8,8,4096]{3,2,1,0:T(8,128)} %fusion.2, "
    "f32[1,4096,4096]{2,1,0:T(8,128)} %dy.1, "
    "f32[1,8,32,512,128]{4,3,2,1,0:T(8,128)} %ssd_scan.4), "
    'custom_call_target="tpu_custom_call"')
# The XLA form's loop over chunks (the parent's program).
LOOP = ("%while.80 = (s32[], f32[1,8,8,64,128]{4,3,2,1,0}) while("
        "(s32[], f32[1,8,8,64,128]{4,3,2,1,0}) %tuple.9), condition=%c, "
        "body=%b")


def _run(ops):
  """One 20 ms step holding each (text, ns)."""
  events = [(D0, tr.MODULE_LINE, "jit_t2r_train_step(1)", 0.0, 20e6)]
  t = 0.0
  for text, ns in ops:
    events.append((D0, tr.OPS_LINE, text, t, ns))
    t += ns
  return {"events": events, "sizes": test_nemotron_cell._sizes(),
          "batch_size": 1, "peaks": V5E}


def test_the_count_by_hand():
  """32 chunks of 128, 8 groups, 64 heads of 64, N 128. A forward call:
  C B^T 32 x 8 x 2 x 128^2 x 128 = 1.07 GFLOP, and 32 x 64 x (2 x 128^2 x
  64 + 4 x 128 x 64 x 128) = 12.88 GFLOP; x, B, C read at 2 bytes
  (4096 x 6144 x 2), dt at 4 (4096 x 64 x 4), y written at 4 (4096 x 4096
  x 4). A backward call: twice the FLOPs; x, B, C, dt and dy (4 bytes)
  read, their cotangents written (2 and 4 bytes). Both bound by bytes."""
  fwd_flops = 32 * 8 * 2 * 128 ** 2 * 128 + 32 * 64 * (
      2 * 128 ** 2 * 64 + 4 * 128 * 64 * 128)
  assert fwd_flops == 13_958_643_712
  fwd_bytes = 4096 * 6144 * 2 + 4096 * 64 * 4 + 4096 * 4096 * 4
  bwd_bytes = 2 * 4096 * 6144 * 2 + 2 * 4096 * 64 * 4 + 4096 * 4096 * 4
  assert (fwd_bytes, bwd_bytes) == (118_489_088, 169_869_312)
  bw, peak = V5E["hbm_bytes_per_s"], V5E["bf16_flops_per_s"]
  assert fwd_bytes / bw > fwd_flops / peak
  assert bwd_bytes / bw > 2 * fwd_flops / peak
  ns = {"forward": 0.6e6, "backward": 1.1e6}
  run = _run([(FORWARD, ns["forward"]), (LOOP, 0.4e6),
              (FORWARD.replace("ssd_scan.3", "ssd_scan.5"), ns["forward"]),
              (BACKWARD, ns["backward"])])
  least = (2 * fwd_bytes + bwd_bytes) / bw
  seconds = (2 * ns["forward"] + ns["backward"]) / 1e9
  read = manifest.layer_metric_reader("ssd_scan_roofline")
  assert read(run) == pytest.approx(100.0 * least / seconds, rel=1e-9)
  assert 0 < read(run) <= 100


def test_nothing_to_read_is_no_reading():
  """No run, the XLA form's step (the parent's: no such kernel), another
  cell's sizes: no number, and no error."""
  read = manifest.layer_metric_reader("ssd_scan_roofline")
  assert read({}) is None
  assert read(_run([(LOOP, 0.4e6)])) is None
  assert read(dict(_run([(FORWARD, 0.6e6)]), sizes={"num_heads": 8})) is None


def test_listed_for_the_nemotron_cell():
  per_layer = {m["name"]: m for m in manifest.load_benchmark()["per_layer"]}
  metric = per_layer["ssd_scan_roofline"]
  assert (metric["unit"], metric["better"], metric["layer"],
          metric["moves"], metric["source"], metric["workloads"]) == (
              "%", "higher", "kernels", "examples_per_s", "device_trace",
              [CELL])
