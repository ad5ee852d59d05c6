"""The span metrics on a ring of program events written by hand, and on the
ring a rehearsal of each cell leaves behind."""

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import program_spans as ps
from tests.benchmark.rehearse import CELLS, rehearse

LOOP, WORKER = 1, 2
D0, HOST = "/device:TPU:0", "/host:CPU"
OFFSET_NS = 5e9  # what the trace's clock is ahead of the ring's


def _ring():
  """Eight iterations, a window of the last four (steps 5..8), times in ms.
  Step 5's record stalls the device for 11 ms, step 6's for 90, of which
  the benchmark's own hook takes 5; step 4's record lies before the window
  and step 8's has no dispatch after it."""
  events, ids = [], iter(range(1, 10_000))

  def span(name, start_ms, dur_ms, parent=None, step=None, tid=LOOP, **args):
    event = {"name": name, "ph": "X", "ts": start_ms * 1e3,
             "dur": dur_ms * 1e3, "tid": tid, "id": next(ids)}
    if parent is not None:
      event["parent"] = parent["id"]
    if step is not None:
      event["step"] = step
    if args:
      event["args"] = args
    events.append(event)
    return event

  events.append({"name": "thread_name", "ph": "M", "tid": LOOP,
                 "args": {"name": "MainThread"}})
  span("setup/writer", -13000, 12500)
  span("setup/first_batch", 0, 1)
  span("setup/create_state", 10, 2000)
  span("setup/restore", 2010, 5)
  starts = {1: 10000, 2: 10100, 3: 10230, 4: 10320, 5: 10480, 6: 10580,
            7: 10700, 8: 10800}
  dispatch_ms = {1: 4000, 2: 1, 3: 1, 4: 1, 5: 1, 6: 3, 7: 2, 8: 2}
  for step, t in starts.items():
    if step == 1:
      t -= 4000  # the first dispatch loads the step program
    it = span("train/iteration", t, dispatch_ms[step] + 89, step=step, k=1)
    span("train/dispatch", t, dispatch_ms[step], it, step)
    t += dispatch_ms[step] - 1
    span("train/data_wait", t + 2, 0.5, it, step)
    if step != 6:
      span("train/hook", t + 5, 2, it, step, hook="BenchHook",
           method="after_step")
    if step == 4:
      span("train/barrier", t + 10, 10, it, step)  # ends at +20
    if step == 5:
      span("train/barrier", t + 10, 79, it, step)  # ends at +89
    if step == 8:
      span("train/barrier", t + 10, 20, it, step)
    if step == 6:
      t = starts[6]
      span("train/barrier", t + 10, 20, it, step)  # ends at +30
      record = span("train/record", t + 30, 10, it, step)
      span("train/record/gauges", t + 31, 3, record, step)
      span("train/record/observer", t + 35, 4, record, step,
           observer="Sentinel.observe_step_record")
      # an externally timed window, recorded under the record: longer than it
      span("train/step_window", t - 900, 930, record, step)
      hook = span("train/hook", t + 41, 40, it, step, hook="StepStatsHook",
                  method="after_step")
      write = span("summary/write", t + 42, 38, hook, step)
      span("summary/jsonl", t + 42, 2, write, step)
      span("summary/tensorboard", t + 44, 35, write, step)
      # the benchmark's own hook (a traced run stops its trace here): 5 ms
      # of the stretch that are no stall of the program's
      span("train/hook", t + 82, 5, it, step, hook="BenchHook",
           method="after_step")
  for start_ms, dur_ms in ((9000, 500), (10490, 30), (10600, 40),
                           (10790, 50)):
    span("data/place", start_ms, dur_ms, tid=WORKER, bytes=171_000_000)
  return events


RING = _ring()
RUN = {"steps": 4, "program_events": RING}


def _by(name, step=None):
  return [e for e in ps.named(RING, name)
          if step is None or e.get("step") == step]


def test_window_is_the_last_steps_iterations():
  assert [e["step"] for e in ps.window_iterations(RING, 4)] == [5, 6, 7, 8]
  assert [e["step"] for e in ps.window_iterations(RING, 100)] == list(
      range(1, 9))
  assert ps.window_iterations([], 4) == []


def test_children_and_self_time():
  (record,) = _by("train/record")
  # The step window was recorded under the record but began long before
  # it: no child. 10 ms less the 3 + 4 that the two children cover.
  assert [c["name"] for c in ps.children(RING, record)] == [
      "train/record/gauges", "train/record/observer"]
  assert ps.self_ms(RING, record) == pytest.approx(3.0)
  (hook,) = [e for e in _by("train/hook", 6)
             if e["args"]["hook"] == "StepStatsHook"]
  assert ps.self_ms(RING, hook) == pytest.approx(2.0)
  (write,) = _by("summary/write")
  assert ps.self_ms(RING, write) == pytest.approx(1.0)


def test_covered_counts_an_overlap_once():
  assert ps.covered_us([(0, 10), (5, 20), (30, 40)], 2, 35) == 23


def test_stall_runs_from_the_barriers_end_to_the_next_dispatch():
  stalls = ps.record_stalls(RING, 4)
  assert [(step, pytest.approx(ms)) for step, ms, _ in stalls] == [
      (5, 11.0), (6, 85.0)]  # step 4: not in the window; step 8: the last
  between = stalls[1][2]
  assert [e["name"] for e in between] == ["train/record", "train/hook"]
  leaves = ps.leaf_self_times(RING, between)
  assert leaves == {
      "train/record": pytest.approx(3.0),
      "train/record/gauges": pytest.approx(3.0),
      "train/record/observer[Sentinel.observe_step_record]":
          pytest.approx(4.0),
      "train/hook[StepStatsHook]": pytest.approx(2.0),
      "summary/write": pytest.approx(1.0),
      "summary/jsonl": pytest.approx(2.0),
      "summary/tensorboard": pytest.approx(35.0)}


def _trace():
  """Device ops around step 6's stall, on a clock OFFSET_NS ahead, and the
  benchmark's own spans of steps 6 and 7."""
  def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * 1e6 + OFFSET_NS, dur_ms * 1e6)

  hooks = {e["step"]: e for e in ps.bench_hook_spans(RING)}
  events = [
      ev(D0, tr.OPS_LINE, "fusion.1", 10580, 30),      # until the barrier ends
      ev(D0, tr.OPS_LINE, "fusion.1", 10701, 79),      # 91 ms later
      ev(D0, tr.OPS_LINE, "fusion.2", 10780.5, 49.5),  # 0.5 ms later: launch
  ]
  for step in (6, 7):
    start_ms = hooks[step]["ts"] / 1e3 + 0.002  # 2 us inside the hook's span
    events.append(ev(HOST, "python", "bench/after_step", start_ms, 1.9))
  return events


def test_offset_comes_from_the_benchmarks_own_spans():
  offset = ps.offset_from_pairs(RING, _trace())
  assert offset == pytest.approx(OFFSET_NS + 2000, abs=1)
  device_only = [e for e in _trace() if e[0] == D0]
  assert ps.offset_from_pairs(RING, device_only) is None  # nothing to pair
  assert ps.offset_from_pairs([], _trace()) is None


def test_unattributed_share_of_the_long_gaps():
  gaps = ps.device_gaps(_trace())
  (gap,) = gaps  # the 0.5 ms gap is launch latency
  assert (gap[1] - gap[0]) / 1e6 == pytest.approx(91.0)
  # Named: the record (10 ms), the stepstats hook (40), the benchmark's
  # hook (5) and the first millisecond of step 7's dispatch. The
  # iteration's own time is not.
  share = ps.unattributed_share(RING, gaps, OFFSET_NS)
  assert share == pytest.approx(100.0 * 35 / 91)
  busy = [(D0, tr.OPS_LINE, "fusion.1", OFFSET_NS, 5e9)]
  assert ps.device_gaps(busy) == []
  assert ps.unattributed_share(RING, [], OFFSET_NS) == 0.0
  assert ps.unattributed_share(RING, gaps, None) is None
  assert ps.unattributed_share([], gaps, OFFSET_NS) is None


EXPECTED = {
    "record_stall_ms": 11.0,          # the lower median of 11 and 85
    "record_stall_max_ms": 85.0,
    "dispatch_ms": 2.0,               # 1, 3, 2, 2 over steps 5..8
    "place_ms": 40.0,                 # 30, 40, 50: the 500 came earlier
    "first_batch_s": 0.001,
    "state_init_s": 2.005,
    "step_load_s": 4.0,
    "idle_unattributed_share": 100.0 * 35 / 91,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_the_ring(name, capsys):
  read = manifest.layer_metric_reader(name)
  run = dict(RUN, events=_trace())
  assert read(run) == pytest.approx(EXPECTED[name], rel=1e-3)
  # A program without the spans (a parent commit), a run without a window.
  bare = [e for e in RING if not e["name"].startswith(("train/", "setup/",
                                                       "data/"))]
  assert read(dict(run, program_events=bare)) is None
  assert read({"events": _trace()}) is None
  capsys.readouterr()


def test_longest_stall_is_named_on_standard_error(capsys):
  manifest.layer_metric_reader("record_stall_max_ms")(RUN)
  err = capsys.readouterr().err
  assert "steps [5, 6]" in err and "step 6" in err
  assert "summary/tensorboard 35.000" in err


def test_new_metrics_are_listed_with_their_cells():
  per_layer = {m["name"]: m for m in manifest.load_benchmark()["per_layer"]}
  for name in EXPECTED:
    assert per_layer[name]["source"] == "program_span"
    assert per_layer[name]["workloads"]
  assert per_layer["idle_unattributed_share"]["workloads"] == [
      "seq_train_T2048"]


EVENTS_PER_ITERATION_CEILING = 40
SETUP = ["setup/writer", "setup/first_batch", "setup/create_state",
         "setup/restore", "setup/memory_accounting", "setup/hooks_begin",
         "setup/make_steps"]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_leaves_one_span_tree(capsys, cell):
  from tensor2robot_tpu.obs import trace as trace_lib

  result, _ = rehearse(capsys, cell, trace=0, seed=2_147_483_659)
  warmup = manifest.Cell(cell).traffic["warmup_steps"]
  steps = result["counts"]["steps"] + warmup
  events = ps.spans(trace_lib.get_tracer().events())
  by_id = {e["id"]: e for e in events}
  iterations = ps.named(events, "train/iteration")
  assert [e["step"] for e in iterations] == list(range(1, steps + 1))
  dispatches = ps.named(events, "train/dispatch")
  assert [e["step"] for e in dispatches] == list(range(1, steps + 1))
  assert [by_id[e["parent"]]["name"] for e in dispatches] == [
      "train/iteration"] * steps
  for e in events:
    if "parent" in e and e["name"] != "train/step_window":
      parent = by_id[e["parent"]]
      assert parent["ts"] <= e["ts"] + 1e-3, (e, parent)
      assert ps.end(e) <= ps.end(parent) + 1e-3, (e, parent)
  assert [e["name"] for e in sorted(events, key=lambda e: e["ts"])
          if e["name"].startswith("setup/")] == SETUP
  hooks = {(e["args"]["hook"], e["args"]["method"])
           for e in ps.named(events, "train/hook")}
  assert ("BenchHook", "after_step") in hooks and ("BenchHook",
                                                   "begin") in hooks
  # The CPU stand-in records every step, so every iteration is the long
  # kind; the ceiling is per iteration, the prefetcher's thread included.
  in_loop = [e for e in events if e["ts"] >= iterations[0]["ts"]]
  assert len(in_loop) / steps < EVENTS_PER_ITERATION_CEILING
  # The readers find the window's iterations in this very ring.
  run = {"steps": result["counts"]["steps"]}
  assert len(ps.window_iterations(ps.program_events(run),
                                  run["steps"])) == run["steps"]
  assert manifest.layer_metric_reader("dispatch_ms")(run) > 0
  assert manifest.layer_metric_reader("step_load_s")(run) > 0
  assert manifest.layer_metric_reader("state_init_s")(run) > 0
