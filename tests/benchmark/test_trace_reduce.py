"""The trace reduction on a list of events written by hand."""

import pytest

from benchmarks.harness import trace_reduce as tr

D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS, MOD = tr.OPS_LINE, tr.MODULE_LINE


def ev(plane, line, name, start_us, dur_us):
  return (plane, line, name, start_us * 1e3, dur_us * 1e3)


EVENTS = [
    # device 0: two steps of 100 us with a 50 us gap between them
    ev(D0, MOD, "jit_step_fn(1)", 0, 100),
    ev(D0, MOD, "jit_step_fn(1)", 150, 100),
    ev(D0, MOD, "jit_copy(2)", 260, 10),
    ev(D0, OPS, "fusion.1", 0, 60),
    ev(D0, OPS, "fusion.2", 40, 60),       # overlaps fusion.1 by 20 us
    ev(D0, OPS, "while.3", 150, 100),
    ev(D0, OPS, "fusion.1", 160, 30),      # nested inside while.3
    ev(D0, OPS, "copy.4", 260, 10),
    # device 1: busy throughout
    ev(D1, MOD, "jit_step_fn(1)", 0, 270),
    ev(D1, OPS, "fusion.1", 0, 270),
    # host: a span that covers the gap, one that does not
    ev(HOST, "thread-1", "bench/after_step", 95, 60),
    ev(HOST, "thread-1", "bench/next_batch", 10, 5),
]


def test_planes():
  assert tr.device_planes(EVENTS) == [D0, D1]


def test_union_of_overlapping_ops():
  ops = tr.select(EVENTS, plane=D0, line=OPS)
  assert tr.merged_intervals(ops) == [[0.0, 100e3], [150e3, 250e3],
                                      [260e3, 270e3]]
  assert tr.busy_ns(ops) == pytest.approx(210e3)


def test_device_busy_averages_the_planes():
  busy = tr.device_busy(EVENTS)
  assert busy["window_s"] == pytest.approx(270e-6)
  assert busy["per_device"][D0] == pytest.approx(210e-6)
  assert busy["per_device"][D1] == pytest.approx(270e-6)
  assert busy["busy_s"] == pytest.approx(240e-6)


def test_module_durations_and_heaviest():
  name, durations = tr.heaviest_module(EVENTS, D0)
  assert name == "jit_step_fn"
  assert durations == [pytest.approx(100e-6)] * 2
  assert tr.module_durations(EVENTS, D0)["jit_copy"] == [
      pytest.approx(10e-6)]


def test_per_name_sums_skip_nested_ops():
  ops = tr.top_level(tr.select(EVENTS, plane=D0, line=OPS))
  sums = tr.sum_by_name(ops)
  assert sums["while.3"] == pytest.approx(100e-6)
  assert sums["fusion.1"] == pytest.approx(60e-6)  # the nested one is left out


def test_gap_is_laid_to_the_host_span_that_covers_it():
  host = [e for e in EVENTS if e[0] == HOST]
  gaps = tr.idle_gaps(EVENTS, host, plane=D0, top=2)
  assert gaps[0] == ("bench/after_step", pytest.approx(50e-6))
  # nothing covers the second gap: it is laid to what the host did last
  assert gaps[1] == ("after bench/after_step", pytest.approx(10e-6))
  assert tr.idle_gaps(EVENTS, [], plane=D0, top=1) == [
      ("(no span)", pytest.approx(50e-6))]


def test_breakdown_shape():
  out = tr.breakdown(EVENTS)
  assert out["device_ops"][0] == ["fusion.2", pytest.approx(60e-6)] or \
      out["device_ops"][0][1] == pytest.approx(100e-6)
  assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
  assert out["idle_gaps"][0][0] == "bench/after_step"


def test_clip_and_window():
  ops = tr.select(EVENTS, plane=D0, line=OPS)
  assert tr.busy_ns(tr.clip(ops, 50e3, 200e3)) == pytest.approx(100e3)
  assert tr.device_busy(EVENTS, window=(0.0, 100e3))["busy_s"] == \
      pytest.approx(100e-6)


def test_no_device_plane_reads_nothing():
  host_only = [e for e in EVENTS if e[0] == HOST]
  assert tr.device_busy(host_only) is None
  assert tr.breakdown(host_only) == {"device_ops": [], "idle_gaps": []}


# -- a small trace recorded on the chip (PR 25, TPU v5 lite): two steps of
# each cell, as `read_xplane` gave them ---------------------------------------


def _recorded(name):
  import gzip
  import json
  import os

  from benchmarks.harness import manifest

  path = os.path.join(manifest.BENCH_DIR, "traces", f"{name}.json.gz")
  with gzip.open(path, "rt") as f:
    return [tuple(e) for e in json.load(f)]


def test_recorded_sequence_trace():
  from benchmarks.layer_metrics import flash_kernels

  events = _recorded("seq_train_T2048_two_steps")
  assert tr.device_planes(events) == [D0]
  name, durations = tr.heaviest_module(events, D0)
  assert name == "jit_step_fn" and len(durations) == 2
  assert durations[0] == pytest.approx(0.1868, rel=2e-3)
  busy = tr.device_busy(events)
  assert busy["busy_s"] / busy["window_s"] > 0.999   # back-to-back steps
  # 2 blocks x 2 steps of each kernel, told apart by what they return: the
  # forward (o, log-sum-exp), dq, and dk with dv
  for outputs, per_call_ms in ((flash_kernels.FORWARD_OUTPUTS, 13.8),
                               (["bf16"], 13.5), (["bf16", "bf16"], 24.0)):
    calls = [e for e in tr.select(events, plane=D0, line=OPS,
                                  name_has=flash_kernels.PALLAS_TARGET)
             if tr.output_shapes(e[2]) == outputs]
    assert len(calls) == 4
    assert sum(e[4] for e in calls) / 4 / 1e6 == pytest.approx(per_call_ms,
                                                              rel=0.02)
  top = tr.breakdown(events)["device_ops"]
  assert top[0][0] in ("attn_0.5 custom-call", "attn_1.5 custom-call")
  assert all(len(name) <= 96 for name, _ in top)


def test_recorded_flash_rooflines():
  from benchmarks.harness import peaks
  from benchmarks.layer_metrics import flash_bwd_roofline, flash_fwd_roofline

  events = _recorded("seq_train_T2048_two_steps")
  run = {"events": events, "peaks": peaks.peaks_for("TPU v5 lite"),
         "batch_size": 128,
         "sizes": {"num_heads": 8, "hidden_size": 512,
                   "sequence_length": 2048}}
  # forward: 0.55 TFLOP a call = 2.79 ms at the peak, against 13.8 ms
  assert flash_fwd_roofline.read(run) == pytest.approx(20.2, abs=0.3)
  # The two-kernel backward of that program has no kernel named `flash_bwd`:
  # nothing to read.
  assert flash_bwd_roofline.read(run) is None
  assert flash_fwd_roofline.read(dict(run, events=_recorded(
      "g44_train_b256_two_steps"))) is None   # no kernel: nothing to read


def test_recorded_named_kernels_trace():
  """Two steps of the sequence cell's program with its one backward kernel
  (TPU v5 lite), each kernel named by its `name=`."""
  from benchmarks.harness import peaks
  from benchmarks.layer_metrics import flash_bwd_roofline
  from benchmarks.layer_metrics import flash_fwd_roofline
  from benchmarks.layer_metrics import flash_kernels

  events = _recorded("seq_train_T2048_named_kernels_two_steps")
  name, durations = tr.heaviest_module(events, D0)
  assert name == "jit_t2r_train_step"
  assert durations == [pytest.approx(0.1315, rel=2e-3)] * 2
  busy = tr.device_busy(events)
  assert busy["busy_s"] / busy["window_s"] > 0.999
  # 2 blocks x 2 steps of each kernel, found by name
  for kernel, per_call_ms in (("flash_fwd", 11.64), ("flash_bwd", 22.65)):
    calls = flash_kernels.named_events(events, kernel)
    assert len(calls) == 4
    assert sum(e[4] for e in calls) / 4 / 1e6 == pytest.approx(per_call_ms,
                                                              rel=0.01)
  assert len(flash_kernels.forward_events(events)) == 4
  run = {"events": events, "peaks": peaks.peaks_for("TPU v5 lite"),
         "batch_size": 128,
         "sizes": {"num_heads": 8, "hidden_size": 512,
                   "sequence_length": 2048}}
  # backward: 5 x 2 x 1024 x 2048^2 / 2 x 64 FLOPs = 6.98 ms at the peak,
  # against 22.65 ms a call; forward 2.79 ms against 11.64
  assert flash_bwd_roofline.read(run) == pytest.approx(30.8, abs=0.1)
  assert flash_fwd_roofline.read(run) == pytest.approx(23.97, abs=0.1)


def test_recorded_grasping44_trace():
  events = _recorded("g44_train_b256_two_steps")
  name, durations = tr.heaviest_module(events, D0)
  assert name == "jit_step_fn"
  assert durations == [pytest.approx(0.1005, rel=2e-3)] * 2
  top = dict(tr.breakdown(events)["device_ops"])
  assert "select_and_scatter.29 select-and-scatter" in top  # max-pool backward
