"""`gdn_scan_roofline` on nothing, on a step written by hand around
instruction texts of the two kernels at the hybrid cell's shape (the
forward twice, as rematerialisation runs it, the backward once), and on a
step of the XLA form, which has no such kernel."""

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import peaks
from benchmarks.harness import trace_reduce as tr
from tests.benchmark import test_hybrid_cell
from tests.benchmark import test_nemotron_cell

D0 = "/device:TPU:0"
V5E = peaks.peaks_for("TPU v5 lite")
CELL = test_hybrid_cell.CELL
_MIXED = "bf16[1,4096,8192]{2,1,0:T(8,128)(2,1)}"
_GATES = "f32[1,64,32,64]{3,2,1,0:T(8,128)}"
FORWARD = (
    "%gdn_scan.1 = (f32[1,4096,4096]{2,1,0:T(8,128)}, "
    "f32[1,32,128,128]{3,2,1,0:T(8,128)}, "
    "f32[1,32,64,128,128]{4,3,2,1,0:T(8,128)}, "
    "bf16[1,32,64,64,64]{4,3,2,1,0:T(8,128)(2,1)}) custom-call("
    f"{_MIXED} %m.1, {_MIXED} %m.1, {_MIXED} %m.1, {_GATES} %fusion.1, "
    f"{_GATES} %fusion.2), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints={'
    "bf16[1,4096,8192]{2,1,0}}")
BACKWARD = (
    f"%gdn_scan_bwd.1 = ({_MIXED}, {_GATES}, {_GATES}) custom-call("
    f"{_MIXED} %m.1, {_MIXED} %m.1, {_MIXED} %m.1, {_GATES} %fusion.1, "
    f"{_GATES} %fusion.2, f32[1,4096,4096]{{2,1,0:T(8,128)}} %do.1, "
    "f32[1,32,64,128,128]{4,3,2,1,0:T(8,128)} %gdn_scan.2, "
    "bf16[1,32,64,64,64]{4,3,2,1,0:T(8,128)(2,1)} %gdn_scan.3, "
    "f32[1,32,128,128]{3,2,1,0:T(8,128)} %dlast.1), "
    'custom_call_target="tpu_custom_call"')
# The XLA form's backward loop over chunks (the parent's program).
LOOP = ("%while.80 = (s32[], f32[1,32,128,128]{3,2,1,0}) while("
        "(s32[], f32[1,32,128,128]{3,2,1,0}) %tuple.9), condition=%c, "
        "body=%b")


def _run(ops):
  """One 20 ms step holding each (text, ns)."""
  events = [(D0, tr.MODULE_LINE, "jit_t2r_train_step(1)", 0.0, 20e6)]
  t = 0.0
  for text, ns in ops:
    events.append((D0, tr.OPS_LINE, text, t, ns))
    t += ns
  return {"events": events, "sizes": test_hybrid_cell._sizes(),
          "batch_size": 1, "peaks": V5E}


def test_the_count_by_hand():
  """64 chunks of 64, 16 key heads and 32 value heads of 128. A forward
  call, per value head and chunk: A and q k^T at half (2 x 64^2 x 128),
  the substitution against 256 columns (64^2 x 256), u, (q e^G) S and the
  state's update (3 x 2 x 64 x 128^2), P u at half (64^2 x 128): 18.25
  GFLOP; `mixed` read at 2 bytes (4096 x 8192 x 2), g and beta at 4, o
  written at 4 (4096 x 4096 x 4). A backward call: twice the FLOPs;
  `mixed`, g, beta and dO (4 bytes) read, their cotangents written. Both
  bound by bytes: 0.165 and 0.249 ms."""
  fwd_flops = 64 * 32 * (2 * 64 ** 2 * 128 + 64 ** 2 * 256
                         + 6 * 64 * 128 ** 2 + 64 ** 2 * 128)
  assert fwd_flops == 18_253_611_008
  fwd_bytes = 4096 * 8192 * 2 + 2 * 4096 * 32 * 4 + 4096 * 4096 * 4
  bwd_bytes = 2 * 4096 * 8192 * 2 + 4 * 4096 * 32 * 4 + 4096 * 4096 * 4
  assert (fwd_bytes, bwd_bytes) == (135_266_304, 203_423_744)
  bw, peak = V5E["hbm_bytes_per_s"], V5E["bf16_flops_per_s"]
  assert fwd_bytes / bw > fwd_flops / peak
  assert bwd_bytes / bw > 2 * fwd_flops / peak
  assert (fwd_bytes / bw * 1e3, bwd_bytes / bw * 1e3) == pytest.approx(
      (0.165, 0.249), abs=1e-3)
  ns = {"forward": 0.9e6, "backward": 1.8e6}
  run = _run([(FORWARD, ns["forward"]), (LOOP, 0.4e6),
              (FORWARD.replace("gdn_scan.1", "gdn_scan.5"), ns["forward"]),
              (BACKWARD, ns["backward"])])
  least = (2 * fwd_bytes + bwd_bytes) / bw
  seconds = (2 * ns["forward"] + ns["backward"]) / 1e9
  read = manifest.layer_metric_reader("gdn_scan_roofline")
  assert read(run) == pytest.approx(100.0 * least / seconds, rel=1e-9)
  assert 0 < read(run) <= 100


def test_nothing_to_read_is_no_reading():
  """No run, the XLA form's step (the parent's: neither kernel), another
  cell's sizes: no number, and no error."""
  read = manifest.layer_metric_reader("gdn_scan_roofline")
  assert read({}) is None
  assert read(_run([(LOOP, 0.4e6)])) is None
  assert read(dict(_run([(FORWARD, 0.6e6)]),
                   sizes=test_nemotron_cell._sizes())) is None


def test_listed_for_the_hybrid_cell():
  per_layer = {m["name"]: m for m in manifest.load_benchmark()["per_layer"]}
  metric = per_layer["gdn_scan_roofline"]
  assert (metric["unit"], metric["better"], metric["layer"],
          metric["moves"], metric["source"], metric["workloads"]) == (
              "%", "higher", "kernels", "examples_per_s", "device_trace",
              [CELL])
