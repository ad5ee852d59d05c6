"""Tests for graftscope (`tensor2robot_tpu/obs/`): tracer, metrics,
step stats, hardened SummaryWriter, the device-timing lint rule, the
train-loop integration, and the reader CLI.

Contracts:

* spans nest correctly and export VALID Chrome trace-event JSON
  (Perfetto-loadable: `traceEvents` list of `ph: X` events with
  name/ts/dur/pid/tid);
* histogram percentiles match numpy exactly while the reservoir holds
  every observation;
* a CPU-mesh `train_eval_model` run writes per-step `data_wait_ms`,
  `device_wait_ms` and `examples_per_sec` records to `metrics.jsonl`, saves
  a trace, and `python -m tensor2robot_tpu.bin.graftscope <model_dir>`
  renders a non-empty report from them;
* `tensor2robot_tpu.obs` (and the CLI) import and run under a poisoned
  JAX_PLATFORMS without touching a backend — the `analysis/`
  discipline (tier-1).
"""

import json
import math
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tensor2robot_tpu import train_eval
from tensor2robot_tpu.analysis import tracer_check
from tensor2robot_tpu.bin import graftscope
from tensor2robot_tpu.hooks import profiler as profiler_lib
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import runlog as runlog_lib
from tensor2robot_tpu.obs import stepstats as stepstats_lib
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.obs import xray as xray_lib
from tensor2robot_tpu.utils import config, mocks
from tensor2robot_tpu.utils import summaries as summaries_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_global_obs_state():
  """Hermetic graftscope state per test: the process-wide metrics
  registry is snapshot/SWAPPED for a fresh one (`metrics.isolated` —
  unlike reset(), other suites' counters in the shared singleton
  survive untouched and nothing this test records can leak out), and
  the global tracer + xray compile collector are cleared both ways."""
  with metrics_lib.isolated():
    trace_lib.clear()
    trace_lib.disable()
    xray_lib.clear_records()
    yield
  trace_lib.clear()
  trace_lib.disable()
  xray_lib.clear_records()


# ---------------------------------------------------------------------------
# Tracer: span semantics + Chrome-trace JSON validity.
# ---------------------------------------------------------------------------


class TestTracer:

  def test_nested_spans_contained_and_ordered(self):
    tracer = trace_lib.Tracer()
    tracer.enable()
    with tracer.span("outer"):
      time.sleep(0.002)
      with tracer.span("inner"):
        time.sleep(0.002)
      time.sleep(0.002)
    events = [e for e in tracer.events() if e["ph"] == "X"]
    by_name = {e["name"]: e for e in events}
    assert set(by_name) == {"outer", "inner"}
    outer, inner = by_name["outer"], by_name["inner"]
    # Chrome-trace nesting: the child window lies inside the parent's.
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert inner["dur"] >= 1e3  # at least the 2 ms sleep, in us
    assert outer["dur"] > inner["dur"]

  def test_save_writes_perfetto_loadable_json(self, tmp_path):
    tracer = trace_lib.Tracer()
    tracer.enable()
    with tracer.span("a", cat="test", detail=1):
      pass
    tracer.instant("marker", note="hi")
    path = tracer.save(str(tmp_path / "trace.json"))
    with open(path) as f:
      payload = json.load(f)  # strict JSON — what Perfetto parses
    assert isinstance(payload["traceEvents"], list)
    phases = {e["ph"] for e in payload["traceEvents"]}
    assert "X" in phases and "M" in phases and "i" in phases
    for event in payload["traceEvents"]:
      assert "name" in event and "pid" in event and "tid" in event
      if event["ph"] == "X":
        assert event["ts"] >= 0 and event["dur"] >= 0
        assert event["cat"] == "test"
        assert event["args"] == {"detail": 1}

  def test_thread_awareness(self):
    tracer = trace_lib.Tracer()
    tracer.enable()

    def work():
      with tracer.span("worker_span"):
        pass

    t = threading.Thread(target=work, name="obs-worker")
    t.start()
    t.join()
    with tracer.span("main_span"):
      pass
    events = tracer.events()
    spans = {e["name"]: e for e in events if e["ph"] == "X"}
    assert spans["worker_span"]["tid"] != spans["main_span"]["tid"]
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert "obs-worker" in names

  def test_disabled_tracer_records_nothing(self):
    tracer = trace_lib.Tracer()
    with tracer.span("nope"):
      pass
    tracer.instant("nope")
    tracer.add_complete("nope", 0, 10)
    assert tracer.events() == []

  def test_ring_buffer_bounds_memory(self):
    tracer = trace_lib.Tracer(max_events=10)
    tracer.enable()
    for i in range(50):
      with tracer.span(f"s{i}"):
        pass
    spans = [e for e in tracer.events() if e["ph"] == "X"]
    assert len(spans) == 10
    assert spans[-1]["name"] == "s49"  # oldest dropped, newest kept

  def test_events_carry_id_parent_and_step(self):
    tracer = trace_lib.Tracer()
    tracer.enable()
    with tracer.span("root"):
      pass
    with tracer.span("train/iteration", step=7, k=1):
      with tracer.span("train/dispatch"):
        with tracer.span("xray/analyze"):
          pass
      tracer.add_complete("train/compile_dispatch", 10, 5)
      tracer.instant("mark")
    events = {e["name"]: e for e in tracer.events() if e["ph"] != "M"}
    ids = [e["id"] for e in events.values()]
    assert len(set(ids)) == len(ids) == 6
    assert "parent" not in events["root"] and "step" not in events["root"]
    iteration = events["train/iteration"]
    assert "parent" not in iteration and iteration["step"] == 7
    assert iteration["args"] == {"step": 7, "k": 1}
    assert events["train/dispatch"]["parent"] == iteration["id"]
    assert events["xray/analyze"]["parent"] == events["train/dispatch"]["id"]
    # Externally timed windows and instants hang under the open span too.
    assert events["train/compile_dispatch"]["parent"] == iteration["id"]
    assert events["mark"]["parent"] == iteration["id"]
    for name in ("train/dispatch", "xray/analyze", "train/compile_dispatch",
                 "mark"):
      assert events[name]["step"] == 7, name

  def test_a_thread_starts_at_its_own_root(self):
    tracer = trace_lib.Tracer()
    tracer.enable()

    def work():
      with tracer.span("data/place", bytes=3):
        with tracer.span("data/inner"):
          pass

    with tracer.span("train/iteration", step=1):
      t = threading.Thread(target=work, name="device-prefetch")
      t.start()
      t.join()
    events = {e["name"]: e for e in tracer.events() if e["ph"] == "X"}
    # The worker's span began while the loop's was open, on another
    # thread: it is a root there, and has no step.
    assert "parent" not in events["data/place"]
    assert "step" not in events["data/place"]
    assert events["data/inner"]["parent"] == events["data/place"]["id"]

  def test_open_close_and_a_child_left_open(self):
    tracer = trace_lib.Tracer()
    tracer.enable()
    iteration = tracer.open("train/iteration", step=2)
    tracer.open("train/dispatch")      # an exception skipped its close
    iteration.close()
    iteration.close()                  # the loop's finally: no second event
    with tracer.span("next"):
      pass
    events = [e for e in tracer.events() if e["ph"] == "X"]
    assert sorted(e["name"] for e in events) == [
        "next", "train/dispatch", "train/iteration"]
    by_name = {e["name"]: e for e in events}
    assert by_name["train/dispatch"]["parent"] == \
        by_name["train/iteration"]["id"]
    assert "parent" not in by_name["next"]   # the stack is clean again
    # A disabled tracer hands out the shared no-op span.
    tracer.disable()
    tracer.open("off").close()
    assert len([e for e in tracer.events() if e["ph"] == "X"]) == 3

  def test_clear_forgets_spans_left_open(self):
    tracer = trace_lib.Tracer()
    tracer.enable()
    tracer.open("abandoned")
    tracer.clear()
    with tracer.span("fresh"):
      pass
    (event,) = [e for e in tracer.events() if e["ph"] == "X"]
    assert event["name"] == "fresh" and "parent" not in event

  def test_save_writes_the_clock_anchor(self, tmp_path):
    tracer = trace_lib.Tracer()
    assert tracer.anchor is None
    before = (time.perf_counter_ns(), time.time_ns())
    tracer.enable()
    after = (time.perf_counter_ns(), time.time_ns())
    with tracer.span("a"):
      pass
    with open(tracer.save(str(tmp_path / "trace.json"))) as f:
      payload = json.load(f)
    anchor = payload["metadata"]["clock_anchor"]
    assert anchor == tracer.anchor
    assert before[0] <= anchor["perf_counter_ns"] <= after[0]
    assert before[1] <= anchor["time_ns"] <= after[1]
    tracer.clear()
    assert tracer.anchor == anchor  # the anchor is the tracer's, not the ring's

  def test_profiler_annotation_only_while_a_session_runs(self, tmp_path):
    import glob

    import jax

    tracer = trace_lib.Tracer()
    tracer.enable()
    with tracer.span("before/session"):
      pass
    jax.profiler.start_trace(str(tmp_path))
    try:
      with tracer.span("train/iteration", step=3):
        with tracer.span("train/dispatch"):
          pass
      worker = threading.Thread(
          target=lambda: tracer.open("data/place", bytes=8).close())
      worker.start()
      worker.join()
    finally:
      jax.profiler.stop_trace()
    with tracer.span("after/session"):
      pass
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    host = {e.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert {"train/iteration", "train/dispatch", "data/place"} <= host
    assert "before/session" not in host and "after/session" not in host
    # The ring holds all five either way.
    assert len([e for e in tracer.events() if e["ph"] == "X"]) == 5

  def test_traced_decorator(self):
    tracer = trace_lib.Tracer()
    tracer.enable()

    @tracer.traced("fn_span")
    def fn(x):
      return x + 1

    assert fn(1) == 2
    assert [e["name"] for e in tracer.events() if e["ph"] == "X"] \
        == ["fn_span"]


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------


class TestMetrics:

  def test_counter_and_gauge(self):
    reg = metrics_lib.Registry()
    reg.counter("a/b").inc()
    reg.counter("a/b").inc(4)
    reg.gauge("g").set(2.5)
    assert reg.counter("a/b").value == 5
    assert reg.gauge("g").value == 2.5
    snap = reg.snapshot()
    assert snap["counter/a/b"] == 5.0
    assert snap["gauge/g"] == 2.5

  @pytest.mark.parametrize("dist", ["uniform", "lognormal", "constant"])
  def test_histogram_percentiles_match_numpy(self, dist):
    rng = np.random.RandomState(0)
    values = {"uniform": rng.uniform(0, 100, 500),
              "lognormal": rng.lognormal(1.0, 2.0, 500),
              "constant": np.full(500, 7.0)}[dist]
    hist = metrics_lib.Histogram("h")  # reservoir (4096) holds all 500
    for v in values:
      hist.record(v)
    stats = hist.stats()
    for pct, key in ((50, "p50"), (90, "p90"), (99, "p99")):
      np.testing.assert_allclose(stats[key], np.percentile(values, pct),
                                 rtol=1e-12)
    np.testing.assert_allclose(stats["mean"], values.mean(), rtol=1e-9)
    assert stats["count"] == 500
    assert stats["min"] == values.min() and stats["max"] == values.max()

  def test_histogram_reservoir_bounds_memory_keeps_exact_extremes(self):
    hist = metrics_lib.Histogram("h", reservoir_size=64)
    for v in range(10_000):
      hist.record(float(v))
    assert len(hist._sample) == 64
    stats = hist.stats()
    assert stats["count"] == 10_000
    assert stats["min"] == 0.0 and stats["max"] == 9999.0
    # Reservoir percentiles are estimates; they must land inside the
    # observed range and be ordered.
    assert 0.0 <= stats["p50"] <= stats["p90"] <= stats["p99"] <= 9999.0

  def test_histogram_timer_records_elapsed_ms(self):
    hist = metrics_lib.Histogram("h")
    with hist.time_ms():
      time.sleep(0.005)
    assert hist.count == 1
    assert hist.percentile(50) >= 4.0  # >= the 5 ms sleep, some slack

  def test_snapshot_prefix_filter_and_empty_hist_omitted(self):
    reg = metrics_lib.Registry()
    reg.counter("bench/ok").inc()
    reg.counter("other/x").inc()
    reg.histogram("bench/empty")  # zero observations -> omitted
    snap = reg.snapshot(prefix="bench/")
    assert snap == {"counter/bench/ok": 1.0}

  def test_global_registry_reset(self):
    metrics_lib.counter("x").inc()
    assert metrics_lib.snapshot()["counter/x"] == 1.0
    metrics_lib.reset()
    assert metrics_lib.snapshot() == {}

  def test_record_many_identical_to_sequential_records(self):
    """The hot-path amortization primitive (one lock per block, ISSUE 5
    telemetry-overhead satellite) must be statistically INVISIBLE:
    count/mean/min/max and the reservoir RNG stream match a per-value
    `record` sequence exactly, including past the reservoir bound."""
    rng = np.random.RandomState(3)
    values = rng.lognormal(0.0, 2.0, 5000).tolist()
    one_by_one = metrics_lib.Histogram("h", reservoir_size=256)
    blocked = metrics_lib.Histogram("h", reservoir_size=256)
    for v in values:
      one_by_one.record(v)
    for start in range(0, len(values), 64):
      blocked.record_many(values[start:start + 64])
    assert one_by_one.stats() == blocked.stats()
    assert one_by_one._sample == blocked._sample

  def test_prefetch_flushes_exact_totals_at_stream_end(self):
    """data/pipeline.prefetch buffers wait observations in blocks; the
    end-of-stream flush must keep counter/histogram totals exact for
    ANY item count (a partial last block must not be dropped)."""
    from tensor2robot_tpu.data import pipeline as pipeline_lib

    for n in (0, 1, 63, 64, 65, 200):
      with metrics_lib.isolated() as registry:
        assert list(pipeline_lib.prefetch(iter(range(n)), size=4)) \
            == list(range(n))
        snap = registry.snapshot()
      assert snap.get("counter/data/batches", 0.0) == float(n)
      if n:
        assert snap["hist/data/prefetch_wait_ms/count"] == float(n)


# ---------------------------------------------------------------------------
# Hardened SummaryWriter.
# ---------------------------------------------------------------------------


class TestSummaryWriter:

  def _read(self, path):
    with open(path) as f:
      return [json.loads(line) for line in f if line.strip()]

  def test_context_manager_and_fsync_close(self, tmp_path):
    with summaries_lib.SummaryWriter(str(tmp_path),
                                     use_tensorboard=False) as writer:
      writer.write_scalars(1, {"loss": 0.5})
      path = writer.path
    assert writer._file.closed
    records = self._read(path)
    assert records[0]["step"] == 1 and records[0]["loss"] == 0.5
    writer.close()  # idempotent

  def test_non_finite_and_non_scalar_skipped_not_fatal(self, tmp_path):
    writer = summaries_lib.SummaryWriter(str(tmp_path),
                                         use_tensorboard=False)
    writer.write_scalars(3, {
        "good": 1.25,
        "nan": float("nan"),
        "inf": np.inf,
        "vector": np.zeros(4),
        "string": "not-a-number",
    })
    writer.close()
    (record,) = self._read(writer.path)
    assert record["good"] == 1.25
    for key in ("nan", "inf", "vector", "string"):
      assert key not in record
    snap = metrics_lib.snapshot()
    assert snap["counter/summaries/dropped_non_finite"] == 2.0
    assert snap["counter/summaries/dropped_non_scalar"] == 2.0
    # The file must stay STRICT JSON (no NaN/Infinity literals) so the
    # graftscope reader needs no lenient parser.
    with open(writer.path) as f:
      text = f.read()
    assert "NaN" not in text and "Infinity" not in text

  def test_tensorboard_first_use_is_paid_at_open(self, tmp_path):
    """Opening the writer resolves TensorFlow's lazy summary ops (so the
    first write imports nothing), records no span of its own (the trainer
    wraps it in `setup/writer`) and writes nothing; a write is
    `summary/write` with one child for the JSONL line and one for the
    TensorBoard mirror."""
    pytest.importorskip("tensorflow")
    from tensorflow.python.summary.summary_iterator import summary_iterator

    trace_lib.enable()
    with summaries_lib.SummaryWriter(str(tmp_path)) as writer:
      assert trace_lib.get_tracer().events() == []
      loaded = set(sys.modules)
      writer.write_scalars(3, {"loss": 0.5})
      assert set(sys.modules) == loaded
    events = {e["name"]: e for e in trace_lib.get_tracer().events()
              if e["ph"] == "X"}
    write = events["summary/write"]
    assert events["summary/jsonl"]["parent"] == write["id"]
    assert events["summary/tensorboard"]["parent"] == write["id"]
    tags = [value.tag for path in tmp_path.glob("events.out.tfevents.*")
            for event in summary_iterator(str(path))
            for value in event.summary.value]
    assert tags == ["loss"]

  def test_scalar_shapes_still_accepted(self, tmp_path):
    writer = summaries_lib.SummaryWriter(str(tmp_path),
                                         use_tensorboard=False)
    writer.write_scalars(1, {"a": np.float32(2.0), "b": np.array([3.0]),
                             "c": np.array(4.0), "d": True})
    writer.close()
    (record,) = self._read(writer.path)
    assert (record["a"], record["b"], record["c"], record["d"]) \
        == (2.0, 3.0, 4.0, 1.0)


# ---------------------------------------------------------------------------
# StepStatsRecorder protocol (fake barrier: no device involved).
# ---------------------------------------------------------------------------


class TestStepStats:

  def _run_steps(self, rec, n):
    for i in range(n):
      with rec.data_wait():
        time.sleep(0.002)
      rec.before_dispatch()
      time.sleep(0.001)
      rec.after_dispatch()
      rec.end_step(i + 1, state="fake-state")

  def test_per_step_records_have_required_fields(self):
    barriers = []
    rec = stepstats_lib.StepStatsRecorder(
        batch_size=8, every_n_steps=1, barrier=barriers.append,
        device_gauges=False)
    rec.start()
    self._run_steps(rec, 3)
    records = rec.drain()
    assert [step for step, _ in records] == [1, 2, 3]
    assert barriers == ["fake-state"] * 3
    for _, r in records:
      for key in ("data_wait_ms", "device_wait_ms", "examples_per_sec",
                  "step_ms", "host_ms", "dispatch_ms", "compile"):
        assert key in r, r
      assert r["data_wait_ms"] >= 1.5      # the 2 ms staging sleep
      assert r["device_wait_ms"] >= 0.5    # the 1 ms dispatch sleep
      assert r["step_ms"] >= r["data_wait_ms"]
      assert r["examples_per_sec"] > 0
    # Nothing compiled in these dispatches, the first included: the flag
    # is counted from compiles, not read off a dispatch's duration.
    assert [r["compile"] for _, r in records] == [0.0, 0.0, 0.0]
    assert rec.drain() == []  # drained

  def test_windowed_cadence_averages_over_n_steps(self):
    rec = stepstats_lib.StepStatsRecorder(
        batch_size=4, every_n_steps=2, barrier=lambda s: None,
        device_gauges=False)
    rec.start()
    self._run_steps(rec, 4)
    records = rec.drain()
    assert [step for step, _ in records] == [2, 4]
    for _, r in records:
      assert r["steps_in_window"] == 2.0
      # Per-step averages: one window covers two 2 ms staging sleeps.
      assert 1.5 <= r["data_wait_ms"] <= 50.0

  @pytest.mark.parametrize("what,expected", [
      ("slow", 0.0), ("compile", 1.0), ("cache_hit", 1.0)])
  def test_compile_marker_is_counted_not_inferred(self, what, expected):
    """A dispatch is a compile event where it compiled (jax's
    `backend_compile_duration` event) or took an executable from the
    cache (`cache/hits`): a dispatch that blocks for 60 ms on a full
    device queue, 60 x the others, is none."""
    trace_lib.enable()
    rec = stepstats_lib.StepStatsRecorder(
        batch_size=1, every_n_steps=1, barrier=lambda s: None,
        device_gauges=False)
    rec.start()
    self._run_steps(rec, 3)
    rec.drain()
    before = metrics_lib.counter("stepstats/compile_events").value
    rec.before_dispatch()
    if what == "slow":
      time.sleep(0.06)
    elif what == "compile":
      import jax
      jax.jit(lambda x: x * 3.0 + 1.0)(np.ones((3,), np.float32))
    else:
      metrics_lib.counter("cache/hits").inc()
    rec.after_dispatch()
    rec.end_step(4, state=None)
    ((_, record),) = rec.drain()
    assert record["compile"] == expected
    assert metrics_lib.counter("stepstats/compile_events").value \
        == before + int(expected)
    markers = [e for e in trace_lib.get_tracer().events()
               if e["name"] == "train/compile_dispatch"]
    assert len(markers) == int(expected)

  def test_disabled_recorder_noops(self):
    rec = stepstats_lib.StepStatsRecorder(batch_size=8, every_n_steps=0,
                                          barrier=None)
    assert not rec.enabled
    rec.start()
    self._run_steps(rec, 2)  # barrier=None would raise if called
    assert rec.drain() == []

  def test_registry_and_trace_feeds(self):
    trace_lib.enable()
    rec = stepstats_lib.StepStatsRecorder(
        batch_size=8, every_n_steps=1, barrier=lambda s: None,
        device_gauges=False)
    rec.start()
    self._run_steps(rec, 2)
    snap = metrics_lib.snapshot()
    assert snap["hist/stepstats/step_ms/count"] == 2.0
    assert snap["hist/stepstats/examples_per_sec/count"] == 2.0
    names = {e["name"] for e in trace_lib.get_tracer().events()}
    assert {"train/step_window", "train/data_wait"} <= names

  def test_record_path_is_a_span_tree(self):
    trace_lib.enable()
    rec = stepstats_lib.StepStatsRecorder(
        batch_size=8, every_n_steps=2, barrier=lambda s: None,
        device_gauges=False)

    def watcher(step, record):
      del step, record

    rec.add_observer(watcher)
    rec.start()
    tracer = trace_lib.get_tracer()
    for step in (1, 2):
      with tracer.span("train/iteration", step=step):
        self._run_steps(rec, 1)
    events = [e for e in tracer.events() if e["ph"] == "X"]
    by_id = {e["id"]: e for e in events}

    def parent_name(event):
      return by_id[event["parent"]]["name"] if "parent" in event else None

    names = [e["name"] for e in events]
    # Two dispatches and two waits, one barrier and one record (cadence 2).
    assert names.count("train/dispatch") == names.count(
        "train/data_wait") == 2
    assert names.count("train/barrier") == names.count("train/record") == 1
    for event in events:
      if event["name"] in ("train/dispatch", "train/data_wait",
                           "train/barrier", "train/record"):
        assert parent_name(event) == "train/iteration", event
      if event["name"] in ("train/record/gauges", "train/record/observer"):
        assert parent_name(event) == "train/record", event
    (observer,) = [e for e in events if e["name"] == "train/record/observer"]
    assert observer["args"]["observer"].endswith("watcher")
    assert observer["step"] == 2


# ---------------------------------------------------------------------------
# device-timing lint rule.
# ---------------------------------------------------------------------------


_BAD_TIMING = """
import time
import jax.numpy as jnp

def f(x):
  t0 = time.perf_counter()
  y = jnp.dot(x, x)
  return time.perf_counter() - t0
"""


class TestDeviceTimingRule:

  def _rules(self, findings):
    return {f.rule for f in findings}

  def test_flags_unbarriered_device_window(self):
    out = tracer_check.check_python_source(_BAD_TIMING, "x.py")
    assert self._rules(out) == {"device-timing"}
    assert "dispatch, not execution" in out[0].message

  def test_barrier_in_window_passes(self):
    for barrier in ("np.asarray(y)", "backend.sync(y)",
                    "jax.device_get(y)", "y.item()",
                    "jax.block_until_ready(y)"):
      src = _BAD_TIMING.replace(
          "  return time.perf_counter() - t0",
          f"  import numpy as np\n"
          f"  import jax\n"
          f"  from tensor2robot_tpu.utils import backend\n"
          f"  {barrier}\n"
          f"  return time.perf_counter() - t0")
      out = tracer_check.check_python_source(src, "x.py")
      assert self._rules(out) == set(), (barrier, out)

  def test_host_only_window_passes(self):
    src = ("import time\n\ndef f(stream):\n"
           "  t0 = time.perf_counter()\n"
           "  batch = next(stream)\n"
           "  return time.perf_counter() - t0\n")
    assert tracer_check.check_python_source(src, "x.py") == []

  def test_two_variable_close_detected(self):
    src = ("import time\nimport jax\n\ndef f(x):\n"
           "  start = time.time()\n"
           "  y = jax.device_put(x)\n"
           "  now = time.time()\n"
           "  return now - start\n")
    out = tracer_check.check_python_source(src, "x.py")
    assert self._rules(out) == {"device-timing"}

  def test_suppressible(self):
    src = _BAD_TIMING.replace(
        "return time.perf_counter() - t0",
        "return time.perf_counter() - t0"
        "  # graftlint: disable=device-timing")
    assert tracer_check.check_python_source(src, "x.py") == []

  def test_obs_paths_exempt(self, tmp_path):
    """obs/ owns the instrumentation clocks; utils/backend.py holds no
    clock any more and is held to the rule like any other file."""
    target = tmp_path / "tensor2robot_tpu/obs/timing.py"
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(_BAD_TIMING)
    assert tracer_check.check_python_file(str(target)) == []
    for rel in ("plain.py", "utils/backend.py"):
      plain = tmp_path / rel
      plain.parent.mkdir(parents=True, exist_ok=True)
      plain.write_text(_BAD_TIMING)
      assert self._rules(tracer_check.check_python_file(str(plain))) \
          == {"device-timing"}

  @pytest.mark.parametrize(
      "timer", ["time_op", "time_train_steps", "time_train_steps_halves"])
  def test_a_deleted_backend_timer_closes_no_window(self, timer):
    """The three timers left `utils/backend` (PR 31); a call by one of
    their names is no barrier, so a window that ends in one is a
    finding like any other unbarriered window."""
    src = _BAD_TIMING.replace(
        "  return time.perf_counter() - t0",
        f"  from tensor2robot_tpu.utils import backend\n"
        f"  backend.{timer}(f, y)\n"
        f"  return time.perf_counter() - t0")
    out = tracer_check.check_python_source(src, "x.py")
    assert self._rules(out) == {"device-timing"}

  def test_message_names_only_what_backend_exports(self):
    """Whoever trips the rule is sent to functions that exist."""
    import re

    from tensor2robot_tpu.utils import backend

    (finding,) = tracer_check.check_python_source(_BAD_TIMING, "x.py")
    named = re.findall(r"\bbackend\.(\w+)", finding.message)
    assert named == ["sync"]
    assert all(callable(getattr(backend, name)) for name in named)
    assert "jax.block_until_ready" in finding.message
    assert "time_" not in finding.message

  def test_nested_function_body_not_part_of_window(self):
    src = ("import time\nimport jax.numpy as jnp\n\ndef f(x):\n"
           "  t0 = time.perf_counter()\n"
           "  def g():\n"
           "    return jnp.dot(x, x)\n"
           "  return time.perf_counter() - t0\n")
    assert tracer_check.check_python_source(src, "x.py") == []


# ---------------------------------------------------------------------------
# ProfilerHook: a trace that cannot start is an error, not a downgrade.
# ---------------------------------------------------------------------------


class TestProfilerGuard:

  def test_start_trace_failure_raises(self, tmp_path, monkeypatch):
    """The user configured a trace: training on without it would hide
    that the profiler is missing."""
    import jax

    def boom(log_dir):
      raise RuntimeError("profiler service unreachable")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    hook = profiler_lib.ProfilerHook(start_step=1, num_steps=2)
    ctx = type("Ctx", (), {"model_dir": str(tmp_path)})()
    hook.after_step(ctx, 0, {})  # before the window: nothing starts
    with pytest.raises(RuntimeError, match="profiler service"):
      hook.after_step(ctx, 1, {})
    hook.end(ctx)  # nothing is active: no stop, no trace reported
    assert metrics_lib.snapshot()["gauge/profiler/trace_captured"] == 0.0


# ---------------------------------------------------------------------------
# End-to-end: CPU-mesh train run -> per-step records, trace, CLI report.
# ---------------------------------------------------------------------------


@pytest.fixture(autouse=True)
def _clean_config():
  config.clear_config()
  yield
  config.clear_config()


class TestTrainLoopStepStats:

  def _train(self, model_dir, **kwargs):
    kwargs.setdefault("checkpoint_every_n_steps", 100)
    return train_eval.train_eval_model(
        model=mocks.MockT2RModel(device_type="cpu"),
        model_dir=model_dir,
        mode="train",
        max_train_steps=6,
        input_generator_train=mocks.MockInputGenerator(batch_size=8),
        log_every_n_steps=2,
        **kwargs)

  def _stepstats_records(self, model_dir):
    path = os.path.join(model_dir, "train", "metrics.jsonl")
    assert os.path.isfile(path)
    with open(path) as f:
      records = [json.loads(line) for line in f if line.strip()]
    return records, [r for r in records
                     if all(k in r for k in ("data_wait_ms", "device_wait_ms",
                                             "examples_per_sec"))]

  def test_train_run_emits_per_step_stepstats_trace_and_report(
      self, tmp_path, capsys):
    model_dir = str(tmp_path / "run")
    self._train(model_dir)
    records, step_records = self._stepstats_records(model_dir)
    # Acceptance: per-step data_wait_ms / device_wait_ms / examples_per_sec.
    assert [r["step"] for r in step_records] == [1, 2, 3, 4, 5, 6]
    for r in step_records:
      assert r["data_wait_ms"] >= 0 and r["device_wait_ms"] >= 0
      assert r["examples_per_sec"] > 0
      assert math.isfinite(r["step_ms"])
    assert step_records[0]["compile"] == 1.0  # first dispatch compiles
    # Final registry snapshot rides the same JSONL stream.
    assert any("hist/stepstats/step_ms/p50" in r for r in records)
    # Perfetto-loadable trace with the step windows.
    trace_path = os.path.join(model_dir, "train", "trace.graftscope.json")
    assert os.path.isfile(trace_path)
    with open(trace_path) as f:
      payload = json.load(f)
    names = [e["name"] for e in payload["traceEvents"]
             if e.get("ph") == "X"]
    assert names.count("train/step_window") == 6
    assert "train/data_wait" in names and "train/barrier" in names
    # graftscope-xray: the run appended a schema-versioned record with
    # compile telemetry and a memory watermark to runs.jsonl.
    (run_record,) = runlog_lib.load_records(
        os.path.join(model_dir, runlog_lib.RUNS_FILENAME))
    assert run_record["schema"] == runlog_lib.SCHEMA
    assert run_record["schema_version"] == runlog_lib.SCHEMA_VERSION
    names = [r["name"] for r in run_record["compile"]]
    assert "train_step" in names
    assert run_record["memory"]["hbm_watermark_bytes"] > 0
    assert run_record["step_stats"]["examples_per_sec_mean"] > 0
    # Reader CLI renders a non-empty report from exactly these files.
    assert graftscope.main([model_dir]) == 0
    out = capsys.readouterr().out
    assert "step-time breakdown" in out
    assert "data_wait_ms" in out and "device_wait_ms" in out
    assert "train/step_window" in out  # slowest-spans table
    assert "compile events: " in out
    assert "run history" in out and "xray compile telemetry" in out

  def test_train_run_saves_one_span_tree(self, tmp_path):
    """The saved trace is a tree: bring-up under `setup/*` once and in
    order, one `train/iteration` a step with the loop's work below it,
    the hooks and the summary writer named, the anchor beside it."""
    model_dir = str(tmp_path / "run")
    self._train(model_dir, checkpoint_every_n_steps=4)
    with open(os.path.join(model_dir, "train",
                           "trace.graftscope.json")) as f:
      payload = json.load(f)
    assert set(payload["metadata"]["clock_anchor"]) == {
        "perf_counter_ns", "time_ns"}
    events = [e for e in payload["traceEvents"] if e.get("ph") == "X"]
    by_id = {e["id"]: e for e in events}
    setup = [e["name"] for e in sorted(events, key=lambda e: e["ts"])
             if e["name"].startswith("setup/")]
    assert setup == ["setup/writer", "setup/first_batch",
                     "setup/create_state", "setup/restore",
                     "setup/memory_accounting", "setup/hooks_begin",
                     "setup/make_steps"]
    iterations = [e for e in events if e["name"] == "train/iteration"]
    assert [e["step"] for e in iterations] == [1, 2, 3, 4, 5, 6]
    assert all("parent" not in e for e in iterations)
    for name in ("train/dispatch", "train/barrier", "train/record"):
      chosen = [e for e in events if e["name"] == name]
      assert [e["step"] for e in chosen] == [1, 2, 3, 4, 5, 6], name
      assert all(by_id[e["parent"]]["name"] == "train/iteration"
                 for e in chosen), name
    # Every child lies inside its parent (the step window is an
    # externally timed span over several iterations: the one exception).
    for e in events:
      if "parent" in e and e["name"] != "train/step_window":
        parent = by_id[e["parent"]]
        assert parent["ts"] <= e["ts"] + 1e-3, (e, parent)
        assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 1e-3, (
            e, parent)
    # Step 1's dispatch holds xray's compile-or-cache-load as its child
    # (loaded where an earlier test of this process stored it).
    first = next(e for e in events
                 if e["name"] == "train/dispatch" and e["step"] == 1)
    analyze = next(e for e in events if e["name"] == "xray/analyze")
    assert analyze["parent"] == first["id"] and analyze["step"] == 1
    assert analyze["args"]["executable"] == "train_step"
    (record,) = [r for r in xray_lib.records() if r["name"] == "train_step"]
    assert analyze["args"]["cache_hit"] == bool(
        (record.get("cache") or {}).get("hit"))
    hooks = [e for e in events if e["name"] == "train/hook"
             and e["args"]["method"] == "after_step"]
    assert {e["args"]["hook"] for e in hooks} == {"StepStatsHook",
                                                  "SentinelHook"}
    assert len(hooks) == 2 * 6
    # The log (every 2 steps) fetches, then writes: jsonl and the mirror.
    logs = [e for e in events if e["name"] == "train/log"]
    assert [e["step"] for e in logs] == [2, 4, 6]
    for log in logs:
      below = {e["name"] for e in events if e.get("parent") == log["id"]}
      assert {"train/log/fetch", "summary/write"} <= below
    writes = [e for e in events if e["name"] == "summary/write"]
    assert all({"summary/jsonl"} <= {c["name"] for c in events
                                    if c.get("parent") == w["id"]}
               for w in writes)
    (checkpoint,) = [e for e in events if e["name"] == "train/checkpoint"]
    assert checkpoint["step"] == 4
    # The prefetcher's thread has its own roots.
    places = [e for e in events if e["name"] == "data/place"]
    assert places and all("parent" not in e for e in places)
    assert all(e["args"]["bytes"] > 0 for e in places)
    loop_tid = iterations[0]["tid"]
    assert all(e["tid"] != loop_tid for e in places)

  def test_step_stats_disabled_leaves_stream_clean(self, tmp_path):
    model_dir = str(tmp_path / "off")
    self._train(model_dir, step_stats_every_n_steps=0)
    _, step_records = self._stepstats_records(model_dir)
    assert step_records == []
    assert not os.path.isfile(
        os.path.join(model_dir, "train", "trace.graftscope.json"))
    # Telemetry off means no run record and no xray wrap either.
    assert not os.path.isfile(
        os.path.join(model_dir, runlog_lib.RUNS_FILENAME))
    assert xray_lib.records() == []

  def test_windowed_cadence_with_iterations_per_loop(self, tmp_path):
    """K-step loop dispatch + cadence 3: windows close on loop
    boundaries (steps 3 and 6), averaging per step."""
    model_dir = str(tmp_path / "loop")
    self._train(model_dir, iterations_per_loop=3,
                step_stats_every_n_steps=3)
    _, step_records = self._stepstats_records(model_dir)
    assert [r["step"] for r in step_records] == [3, 6]
    for r in step_records:
      assert r["steps_in_window"] == 3.0
      assert r["examples_per_sec"] > 0

  def test_graftscope_cli_exit_codes(self, tmp_path, capsys):
    assert graftscope.main([str(tmp_path / "missing")]) == 2
    err = capsys.readouterr().err
    assert "no such directory" in err and "missing" in err
    empty = tmp_path / "empty"
    empty.mkdir()
    assert graftscope.main([str(empty)]) == 1
    assert graftscope.main(["history", str(empty)]) == 2
    capsys.readouterr()

  @pytest.mark.parametrize("key", ["device_ms", "device_wait_ms"])
  def test_graftscope_reads_step_records_across_the_rename(
      self, tmp_path, capsys, key):
    """Stepstats' `device_ms` became `device_wait_ms`: a `metrics.jsonl`
    written before the rename reads under the new name."""
    log_dir = tmp_path / "run" / "train"
    log_dir.mkdir(parents=True)
    (log_dir / "metrics.jsonl").write_text("".join(
        json.dumps({"step": step, "data_wait_ms": 1.0, key: 2.5,
                    "examples_per_sec": 3.0, "step_ms": 4.0}) + "\n"
        for step in (1, 2)))
    assert graftscope.main([str(tmp_path / "run")]) == 0
    out = capsys.readouterr().out
    assert "step-time breakdown (2 records" in out
    (row,) = [line for line in out.splitlines()
              if line.strip().startswith("device_wait_ms")]
    assert "2.50" in row

  def test_run_record_step_stats_read_under_the_new_name(self, tmp_path):
    runs = str(tmp_path / "runs.jsonl")
    runlog_lib.append_record(runs, runlog_lib.make_record(
        "train", step_stats={"device_ms_mean": 7.0, "step_ms_mean": 9.0}))
    runlog_lib.append_record(runs, runlog_lib.make_record(
        "train", step_stats=runlog_lib.step_stats_summary(
            {"hist/stepstats/device_wait_ms/mean": 8.0,
             "hist/stepstats/step_ms/mean": 9.0})))
    old, new = runlog_lib.load_records(runs)
    assert old["step_stats"]["device_wait_ms_mean"] == 7.0
    assert "device_ms_mean" not in old["step_stats"]
    assert new["step_stats"]["device_wait_ms_mean"] == 8.0
    assert runlog_lib.diff_records(old, new)  # still comparable

  def test_graftscope_tolerates_corrupt_telemetry(self, tmp_path, capsys):
    """ISSUE 3 satellite: truncated/corrupt metrics.jsonl and
    trace.json content is skipped with a warning counter — the reader
    must still render a report from the surviving records."""
    log_dir = tmp_path / "run" / "train"
    log_dir.mkdir(parents=True)
    good = {"step": 1, "data_wait_ms": 1.0, "device_wait_ms": 2.0,
            "examples_per_sec": 3.0, "step_ms": 4.0}
    (log_dir / "metrics.jsonl").write_text(
        json.dumps(good) + "\n"
        + '{"torn": \n'          # torn tail line of a live run
        + "\x00\xff garbage\n"   # binary garbage
        + json.dumps(dict(good, step=2)) + "\n")
    (log_dir / "trace.graftscope.json").write_text('{"traceEvents": [')
    rc = graftscope.main([str(tmp_path / "run")])
    captured = capsys.readouterr()
    assert rc == 0
    assert "step-time breakdown (2 records" in captured.out
    assert "corrupt/truncated line(s) skipped" in captured.out
    assert "skipped 2 corrupt line(s)" in captured.err
    assert "skipping corrupt trace" in captured.err
    snap = metrics_lib.snapshot()
    assert snap["counter/graftscope/corrupt_lines"] == 2.0
    assert snap["counter/graftscope/corrupt_trace_files"] == 1.0


# ---------------------------------------------------------------------------
# Tier-1: obs + reader CLI are backend-free (poisoned-platform trap).
# ---------------------------------------------------------------------------


def test_obs_imports_and_cli_run_backend_free(tmp_path):
  """`tensor2robot_tpu.obs` (xray/runlog included) must import — and
  trace/metrics/runlog/CLI (report AND diff/history) must RUN — without
  initializing any JAX backend (same two-layer proof as the analysis
  suite: poisoned JAX_PLATFORMS + empty backend cache)."""
  code = """
import json, sys
from tensor2robot_tpu import obs
from tensor2robot_tpu.obs import metrics, runlog, trace, xray
trace.enable()
with trace.span("smoke"):
    metrics.counter("smoke/count").inc()
    metrics.histogram("smoke/ms").record(1.5)
trace.save(sys.argv[1] + "/t/trace.graftscope.json")
from tensor2robot_tpu.utils import summaries
w = summaries.SummaryWriter(sys.argv[1] + "/t", use_tensorboard=False)
w.write_scalars(1, dict(metrics.snapshot(),
                        data_wait_ms=1.0, device_wait_ms=2.0,
                        examples_per_sec=3.0))
w.close()
runs = sys.argv[1] + "/runs.jsonl"
runlog.append_record(runs, runlog.make_record(
    "train", step_stats={"examples_per_sec_mean": 100.0}))
runlog.append_record(runs, runlog.make_record(
    "train", step_stats={"examples_per_sec_mean": 50.0}))
from tensor2robot_tpu.bin import graftscope
rc = graftscope.main([sys.argv[1]])
assert rc == 0, rc
rc = graftscope.main(["history", sys.argv[1]])
assert rc == 0, rc
rc = graftscope.main(["diff", runs + "#0", runs + "#1"])
assert rc == 3, rc  # the 50% throughput drop must flag, backend-free
from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("OBS_NO_BACKEND_OK")
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "graftscope_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", code, str(tmp_path)],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "OBS_NO_BACKEND_OK" in result.stdout
