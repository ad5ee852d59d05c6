"""graftrace tests: trace contexts, stage decomposition, shard export,
cross-process aggregation, and the causal chain through serving + loop.

Pins the ISSUE 18 semantics:

* contexts (trace_id, span_id, parent_id) mint/propagate on the
  thread-local and auto-inject into every `obs.trace` event via the
  context-provider hook;
* per-request stage histograms reconcile against `serve/request_ms`
  (`stage_breakdown`), with `pad`/`device` excluded from the sum;
* the tracer ring is byte-bounded (oldest dropped, drops counted) and
  `serve/request_ms` carries a worst-sample trace_id exemplar per
  snapshot window;
* `flush()` writes clock-stamped `trace-<pid>-<gen>.json` shards,
  ring-bounded to `max_gens`, and NEVER raises;
* `obs.aggregate` merges shards across skewed wall clocks: epoch
  alignment, happened-before skew repair, Perfetto flow synthesis, and
  `has_causal_chain` walks parent/links edges;
* a router-minted context flows through `MicroBatcher` /
  `SessionBatcher` to the per-request events; the replay sink links
  episodes into shards and the publisher parents `loop/publish` on the
  learner round's context;
* the `trace-context-dropped` lint rule flags an accepted-then-dropped
  `trace_ctx` parameter;
* two REAL subprocesses with deliberately skewed clocks emit shards
  that merge into one causally ordered timeline, and the whole
  graftrace surface runs under a poisoned JAX_PLATFORMS without
  touching a backend (tier-1).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tensor2robot_tpu import serving
from tensor2robot_tpu.analysis import trace_check
from tensor2robot_tpu.bin import graftscope
from tensor2robot_tpu.obs import aggregate as aggregate_lib
from tensor2robot_tpu.obs import graftrace
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import trace as trace_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_trace_state():
  """Every test starts and ends with a disabled, empty tracer and a
  disarmed exporter (the global-tracer equivalent of
  `metrics.isolated`)."""
  trace_lib.disable()
  trace_lib.clear()
  graftrace._reset_for_tests()
  yield
  trace_lib.disable()
  trace_lib.clear()
  graftrace._reset_for_tests()


def _timed_events():
  return [e for e in trace_lib.get_tracer().events()
          if e.get("ph") in ("X", "i")]


def _events_named(name):
  return [e for e in _timed_events() if e["name"] == name]


# ---------------------------------------------------------------------------
# Trace contexts
# ---------------------------------------------------------------------------


class TestTraceContext:

  def test_mint_child_args(self):
    root = graftrace.mint()
    assert root.parent_id is None
    assert "parent_id" not in root.args()
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.span_id != root.span_id
    assert child.parent_id == root.span_id
    assert child.args() == {"trace_id": root.trace_id,
                            "span_id": child.span_id,
                            "parent_id": root.span_id}

  def test_ids_unique_across_threads(self):
    ids = []
    lock = threading.Lock()

    def mint_many():
      local = [graftrace.mint().span_id for _ in range(200)]
      with lock:
        ids.extend(local)

    threads = [threading.Thread(target=mint_many) for _ in range(4)]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    assert len(set(ids)) == len(ids)

  def test_request_context_children_under_activation(self):
    # No active context: a fresh root.
    assert graftrace.current() is None
    orphan = graftrace.request_context()
    assert orphan.parent_id is None
    # Router-minted context active: requests become its children.
    root = graftrace.mint()
    with graftrace.activate(root):
      assert graftrace.current() is root
      req = graftrace.request_context()
      assert req.trace_id == root.trace_id
      assert req.parent_id == root.span_id
      with graftrace.activate(req):
        assert graftrace.current() is req
      assert graftrace.current() is root
    assert graftrace.current() is None

  def test_provider_injects_context_into_events(self):
    trace_lib.enable()
    ctx = graftrace.mint()
    with graftrace.activate(ctx):
      with trace_lib.span("inner", cat="t", foo=1):
        pass
      # Explicit args win over the provider on key collision.
      trace_lib.instant("explicit", span_id="mine")
    inner = _events_named("inner")[0]
    assert inner["args"]["trace_id"] == ctx.trace_id
    assert inner["args"]["span_id"] == ctx.span_id
    assert inner["args"]["foo"] == 1
    assert _events_named("explicit")[0]["args"]["span_id"] == "mine"
    # Outside any activation: no ids injected.
    trace_lib.instant("bare")
    assert "args" not in _events_named("bare")[0]


# ---------------------------------------------------------------------------
# Stage decomposition
# ---------------------------------------------------------------------------


class TestStageBreakdown:

  def test_reconciles_summed_stages_against_request_window(self):
    with metrics_lib.isolated():
      for _ in range(10):
        graftrace.record_stage("queue_wait", 2.0)
        graftrace.record_stage("batch_form", 1.0)
        graftrace.record_stage("dispatch", 5.0)
        graftrace.record_stage("split", 2.0)
        # Sub-stages INSIDE dispatch: reported, never summed (summing
        # them would double-count the dispatch window).
        graftrace.record_stage("pad", 1.0)
        graftrace.record_stage("device", 4.0)
        metrics_lib.histogram("serve/request_ms").record(10.0)
      block = graftrace.stage_breakdown()
    assert block["summed"] == ["queue_wait", "batch_form", "dispatch",
                               "split"]
    assert block["stage_sum_mean_ms"] == pytest.approx(10.0)
    assert block["request_mean_ms"] == pytest.approx(10.0)
    assert block["reconciliation_ratio"] == pytest.approx(1.0)
    assert block["stages"]["device"]["p99_ms"] == pytest.approx(4.0)
    assert block["stages"]["queue_wait"]["count"] == 10.0

  def test_none_when_no_stage_recorded(self):
    with metrics_lib.isolated():
      assert graftrace.stage_breakdown() is None

  def test_record_stage_emits_trace_event_when_timed(self):
    trace_lib.enable()
    ctx = graftrace.mint()
    with metrics_lib.isolated():
      start_ns = time.perf_counter_ns()
      graftrace.record_stage("queue_wait", 1.5, ctx=ctx,
                             start_ns=start_ns)
      graftrace.record_stage("queue_wait", 2.5)  # histogram-only
    events = _events_named("serve/stage/queue_wait")
    assert len(events) == 1
    assert events[0]["args"]["span_id"] == ctx.span_id
    assert events[0]["dur"] == pytest.approx(1500.0)


# ---------------------------------------------------------------------------
# Tracer ring bounds + histogram exemplars
# ---------------------------------------------------------------------------


class TestRingAndExemplars:

  def test_byte_bound_evicts_oldest_and_counts_drops(self):
    tracer = trace_lib.Tracer(max_events=10_000, max_bytes=2_000)
    tracer.enable()
    for i in range(100):
      tracer.instant(f"event-{i:04d}", payload="x" * 64)
    assert tracer.dropped_events > 0
    assert tracer.buffered_bytes <= 2_000
    kept = [e["name"] for e in tracer.events() if e["ph"] == "i"]
    # Oldest dropped first: the newest event always survives.
    assert kept[-1] == "event-0099"
    assert "event-0000" not in kept

  def test_worst_sample_exemplar_per_window(self):
    with metrics_lib.isolated() as registry:
      hist = registry.histogram("serve/request_ms")
      hist.record(5.0, exemplar="trace-fast")
      hist.record(50.0, exemplar="trace-slow")
      hist.record(20.0, exemplar="trace-mid")
      ex = registry.exemplars(clear=True)
      assert ex["serve/request_ms"] == {"value": 50.0,
                                       "trace_id": "trace-slow"}
      # `clear` started a fresh window: a new worst takes over even
      # though it is smaller than the previous window's.
      assert registry.exemplars() == {}
      hist.record(7.0, exemplar="trace-next")
      assert registry.exemplars()["serve/request_ms"]["trace_id"] == (
          "trace-next")


# ---------------------------------------------------------------------------
# Shard export
# ---------------------------------------------------------------------------


class TestShardExport:

  def test_flush_unconfigured_is_noop(self):
    assert not graftrace.is_configured()
    assert graftrace.export_dir() is None
    assert graftrace.flush() is None

  def test_flush_writes_clock_stamped_shards_and_prunes(self, tmp_path):
    root = str(tmp_path / "trace")
    with metrics_lib.isolated():
      graftrace.configure(root, role="test-role", max_gens=2)
      assert graftrace.export_dir() == root
      assert trace_lib.get_tracer().enabled  # configure arms the tracer
      paths = []
      for gen in range(3):
        trace_lib.instant(f"gen-{gen}")
        paths.append(graftrace.flush())
    pid = os.getpid()
    assert paths[-1].endswith(f"trace-{pid}-000002.json")
    names = sorted(os.listdir(root))
    # Ring-bounded: generation 0 pruned, 1 and 2 (trace + metrics) kept.
    assert names == [f"metrics-{pid}-000001.json",
                     f"metrics-{pid}-000002.json",
                     f"trace-{pid}-000001.json",
                     f"trace-{pid}-000002.json"]
    shard = aggregate_lib.load_shard(paths[-1])
    assert shard["role"] == "test-role" and shard["gen"] == 2
    assert shard["clock"]["perf_ns"] > 0 and shard["clock"]["epoch_ns"] > 0
    # Flush DRAINS: each generation holds exactly its own window.
    gen2_names = [e["name"] for e in shard["traceEvents"]
                  if e.get("ph") == "i"]
    assert gen2_names == ["gen-2"]

  def test_flush_never_raises(self, tmp_path, monkeypatch):
    graftrace.configure(str(tmp_path / "t"))
    monkeypatch.setattr(json, "dump",
                        lambda *a, **k: (_ for _ in ()).throw(OSError()))
    assert graftrace.flush() is None  # swallowed: teardown telemetry

  def test_skew_knob_read_from_env(self, tmp_path, monkeypatch):
    monkeypatch.setenv("GRAFTRACE_EPOCH_SKEW_NS", "-5000000000")
    graftrace.configure(str(tmp_path / "t"))
    path = graftrace.flush()
    shard = aggregate_lib.load_shard(path)
    # The stamped epoch is ~5 s behind the real clock.
    behind_ns = time.time_ns() - shard["clock"]["epoch_ns"]
    assert behind_ns > 4_000_000_000


# ---------------------------------------------------------------------------
# Aggregation: clock alignment, skew repair, flows, chain walk
# ---------------------------------------------------------------------------


def _shard(path, pid, events, perf_ns=0, epoch_ns=0, role="worker"):
  payload = {"graftrace": "v1", "role": role, "pid": pid, "gen": 0,
             "clock": {"perf_ns": perf_ns, "epoch_ns": epoch_ns},
             "traceEvents": events, "displayTimeUnit": "ms"}
  with open(path, "w") as f:
    json.dump(payload, f)


def _evt(name, ts, pid, span_id, parent_id=None, links=None, dur=100.0):
  args = {"trace_id": "t1", "span_id": span_id}
  if parent_id is not None:
    args["parent_id"] = parent_id
  if links is not None:
    args["links"] = links
  return {"name": name, "cat": "t", "ph": "X", "ts": ts, "dur": dur,
          "pid": pid, "tid": 1, "args": args}


class TestAggregate:

  def test_merge_aligns_clocks_and_repairs_skew(self, tmp_path):
    # Process A (pid 1111): honest clock. Process B (pid 2222): wall
    # clock 3 s BEHIND, so its causally-downstream event would land
    # before its cause — the happened-before repair must shift B.
    _shard(str(tmp_path / "trace-1111-000000.json"), 1111,
           [_evt("proc/a", ts=1000.0, pid=1111, span_id="sA")],
           perf_ns=0, epoch_ns=10_000_000_000, role="parent")
    _shard(str(tmp_path / "trace-2222-000000.json"), 2222,
           [_evt("proc/b", ts=2000.0, pid=2222, span_id="sB",
                 parent_id="sA")],
           perf_ns=0, epoch_ns=7_000_000_000, role="child")
    merged = aggregate_lib.merge_timeline(str(tmp_path))
    stats = merged["stats"]
    assert stats["shards"] == 2 and stats["skipped"] == 0
    assert stats["processes"] == 2
    assert "2222" in stats["skew_corrected_pids"]
    timed = [e for e in merged["payload"]["traceEvents"]
             if e.get("ph") == "X"]
    by_name = {e["name"]: e for e in timed}
    # Causal order restored despite the skew.
    assert by_name["proc/b"]["ts"] >= by_name["proc/a"]["ts"]
    # One flow pair (s/f, shared id) synthesized along the edge.
    flows = [e for e in merged["payload"]["traceEvents"]
             if e.get("ph") in ("s", "f")]
    assert stats["flow_links"] == 1 and len(flows) == 2
    assert flows[0]["id"] == flows[1]["id"]
    # Process names surfaced from shard roles.
    meta = [e for e in merged["payload"]["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"]
    assert {m["args"]["name"] for m in meta} == {"parent (pid 1111)",
                                                "child (pid 2222)"}

  def test_corrupt_and_foreign_shards_skipped_not_raised(self, tmp_path):
    (tmp_path / "trace-1-000000.json").write_text("{truncated")
    (tmp_path / "trace-2-000000.json").write_text(
        json.dumps({"some": "other tool"}))
    _shard(str(tmp_path / "trace-3-000000.json"), 3,
           [_evt("ok", ts=0.0, pid=3, span_id="s1")],
           epoch_ns=1_000_000_000)
    stats = aggregate_lib.merge_timeline(str(tmp_path))["stats"]
    assert stats["shards"] == 1 and stats["skipped"] == 2
    assert stats["events"] == 1

  def test_has_causal_chain_walk(self):
    events = [
        _evt("episode", 0.0, 1, "e1"),
        _evt("episode", 1.0, 1, "e2"),
        _evt("shard", 2.0, 1, "sh1", links=["e2"]),
        _evt("round", 3.0, 1, "r1", links=["sh1"]),
        _evt("publish", 4.0, 1, "p1", parent_id="r1"),
    ]
    chain = aggregate_lib.has_causal_chain
    assert chain(events, ["episode", "shard", "round", "publish"])
    assert chain(events, ["shard", "round"])
    assert chain(events, [])
    # e1 reaches no shard; a broken hop fails the walk.
    assert not chain(events, ["episode", "round"])
    assert not chain(events, ["publish", "episode"])
    assert not chain(events, ["missing"])


# ---------------------------------------------------------------------------
# End-to-end: router context through the batchers
# ---------------------------------------------------------------------------


class _RowBackend:

  def __call__(self, features):
    x = np.asarray(features["x"])
    return {"out": x * 2.0}


class TestServingPropagation:

  def test_router_context_flows_through_micro_batcher(self):
    trace_lib.enable()
    root = graftrace.mint()
    with metrics_lib.isolated() as registry:
      with serving.MicroBatcher(backend=_RowBackend(),
                                max_batch_size=4,
                                max_delay_ms=2.0) as batcher:
        with graftrace.activate(root):
          batcher.predict({"x": np.ones((1, 2), np.float32)})
      snap = registry.snapshot()
      exemplars = registry.exemplars()
      # Every summed stage recorded exactly once for the one request.
      for stage in graftrace.SUMMED_STAGES:
        assert snap[f"hist/serve/stage/{stage}_ms/count"] == 1.0
      # The worst-request exemplar IS this request's trace id.
      assert exemplars["serve/request_ms"]["trace_id"] == root.trace_id
    requests = _events_named("serve/request")
    assert len(requests) == 1
    # Admission minted a CHILD of the router context: same trace, and
    # the parent chain walks back to the router span.
    assert requests[0]["args"]["trace_id"] == root.trace_id
    assert requests[0]["args"]["parent_id"] == root.span_id
    # The batch-dispatch span links the member request spans.
    batches = _events_named("serve/batcher/dispatch")
    assert batches and requests[0]["args"]["span_id"] in (
        batches[0]["args"]["links"])
    # Per-request stage events carry the same ids.
    queue_waits = _events_named("serve/stage/queue_wait")
    assert queue_waits[0]["args"]["trace_id"] == root.trace_id

  def test_session_batcher_records_tick_stages(self):
    class _StubEngine:
      _max_tick_batch = 8

      def open(self):
        return 7

      def close_session(self, sid):
        pass

      def step_many(self, items):
        return [{"out": np.zeros((1,), np.float32)} for _ in items]

    trace_lib.enable()
    root = graftrace.mint()
    with metrics_lib.isolated() as registry:
      with serving.SessionBatcher(engine=_StubEngine(),
                                  max_delay_ms=1.0) as front:
        sid = front.open()
        with graftrace.activate(root):
          for _ in range(3):
            front.step(sid, {"observation": np.zeros((2,), np.float32)})
        front.close_session(sid)
      snap = registry.snapshot()
      assert snap["hist/serve/stage/queue_wait_ms/count"] == 3.0
      assert snap["hist/serve/stage/dispatch_ms/count"] == 3.0
    batches = _events_named("serve/session/batch")
    assert batches
    linked = set()
    for batch in batches:
      linked.update(batch["args"].get("links", []))
    ticks = _events_named("serve/stage/queue_wait")
    assert ticks and all(t["args"]["trace_id"] == root.trace_id
                         for t in ticks)
    assert any(t["args"]["span_id"] in linked for t in ticks)


# ---------------------------------------------------------------------------
# Loop causality: episode -> shard -> publish
# ---------------------------------------------------------------------------


class TestLoopCausality:

  def test_replay_shard_links_episode_spans(self, tmp_path):
    from tensor2robot_tpu.loop import replay as replay_lib

    trace_lib.enable()
    ep1, ep2 = graftrace.mint(), graftrace.mint()
    with metrics_lib.isolated():
      sink = replay_lib.ReplayRecordSink(str(tmp_path / "r"),
                                         episodes_per_shard=2)
      with sink:
        with graftrace.activate(ep1):
          assert sink.append_episode([b"x" * 64])
        # Explicit carrier beats the thread-local (the cross-thread
        # hand-off path).
        assert sink.append_episode([b"y" * 64], trace_ctx=ep2)
        shards = sink.finished_shards()
      assert len(shards) == 1
      spans = sink.shard_spans()
      assert set(spans) == {shards[0]}
    shard_events = _events_named("loop/replay/shard")
    assert len(shard_events) == 1
    args = shard_events[0]["args"]
    assert args["span_id"] == spans[shards[0]]
    assert set(args["links"]) == {ep1.span_id, ep2.span_id}
    # The chain is walkable from either episode to the shard event.
    episode_evt = _evt("loop/episode", 0.0, os.getpid(), ep1.span_id)
    assert aggregate_lib.has_causal_chain(
        [episode_evt] + shard_events, ["loop/episode",
                                       "loop/replay/shard"])

  def test_publish_parented_on_learner_round_context(self, tmp_path):
    from tensor2robot_tpu import checkpoints as checkpoints_lib
    from tensor2robot_tpu.loop import publish as publish_lib

    class _Fleet:
      # The publisher records the span under what the fleet ACTUALLY
      # serves after rollout (fleet.global_step), not the intent.
      global_step = 10

      def rollout(self, probe_request=None, verify=None,
                  drain_timeout_s=0.0):
        return {"swapped": 1, "aborted": None, "parity_ok": True,
                "fresh_compiles": 0, "canary_index": 0}

    ckpt = str(tmp_path / "ckpt")
    step_dir = os.path.join(ckpt, "10")
    os.makedirs(step_dir)
    with open(os.path.join(step_dir, "state.bin"), "wb") as f:
      f.write(b"params10")
    checkpoints_lib.write_manifest(ckpt, 10)

    trace_lib.enable()
    round_ctx = graftrace.mint()
    with metrics_lib.isolated():
      pub = publish_lib.CheckpointPublisher(_Fleet(), ckpt)
      # The learner requests publication INSIDE its round activation —
      # exactly what loop._learner does around train_eval_model.
      with graftrace.activate(round_ctx):
        pub.request_publish(10)
      report = pub.publish(10)
      assert report["published"]
    events = _events_named("loop/publish")
    assert len(events) == 1
    args = events[0]["args"]
    assert args["trace_id"] == round_ctx.trace_id
    assert args["parent_id"] == round_ctx.span_id
    assert args["step"] == 10 and args["ordinal"] == 1
    assert pub.publish_span_id(10) == args["span_id"]
    assert pub.publish_span_id(99) is None


# ---------------------------------------------------------------------------
# graftscope timeline CLI
# ---------------------------------------------------------------------------


class TestTimelineCli:

  def test_merges_real_shards_to_perfetto_json(self, tmp_path, capsys):
    root = str(tmp_path / "run")
    with metrics_lib.isolated():
      graftrace.configure(root, role="cli-test")
      ctx = graftrace.mint()
      with graftrace.activate(ctx):
        with trace_lib.span("serve/request", cat="serve"):
          pass
      graftrace.flush()
    out = str(tmp_path / "merged.json")
    assert graftscope.main(["timeline", root, "--out", out]) == 0
    report = capsys.readouterr().out
    assert "1 shard(s)" in report
    with open(out) as f:
      payload = json.load(f)
    names = [e.get("name") for e in payload["traceEvents"]]
    assert "serve/request" in names
    assert payload["displayTimeUnit"] == "ms"

  def test_exit_codes(self, tmp_path):
    assert graftscope.main(
        ["timeline", str(tmp_path / "missing")]) == 2
    empty = tmp_path / "empty"
    empty.mkdir()
    assert graftscope.main(["timeline", str(empty)]) == 1


# ---------------------------------------------------------------------------
# Lint rule: trace-context-dropped
# ---------------------------------------------------------------------------


class TestTraceContextDroppedRule:

  def test_dropped_parameter_flagged(self):
    findings = trace_check.check_python_source("m.py", (
        "def append(self, items, trace_ctx=None):\n"
        "  return list(items)\n"))
    assert [f.rule for f in findings] == ["trace-context-dropped"]
    assert "append" in findings[0].message

  def test_async_and_kwonly_flagged(self):
    findings = trace_check.check_python_source("m.py", (
        "async def handle(batch, *, trace_ctx):\n"
        "  await process(batch)\n"))
    assert len(findings) == 1

  def test_referenced_parameter_clean(self):
    assert not trace_check.check_python_source("m.py", (
        "def append(self, items, trace_ctx=None):\n"
        "  if trace_ctx is None:\n"
        "    trace_ctx = current()\n"
        "  return trace_ctx\n"))

  def test_closure_forwarding_counts_as_use(self):
    assert not trace_check.check_python_source("m.py", (
        "def submit(pool, trace_ctx):\n"
        "  def work():\n"
        "    record(trace_ctx)\n"
        "  pool.submit(work)\n"))

  def test_functions_without_the_param_ignored(self):
    assert not trace_check.check_python_source("m.py", (
        "def plain(a, b):\n"
        "  return a + b\n"))

  def test_suppression_honored(self):
    import ast

    from tensor2robot_tpu.analysis import findings as findings_lib

    source = ("def stub(trace_ctx=None):"
              "  # graftlint: disable=trace-context-dropped\n"
              "  pass\n")
    raw = trace_check.check_python_tree("m.py", ast.parse(source))
    assert raw  # found, then filtered by the suppression
    assert not findings_lib.filter_findings(
        raw, findings_lib.load_suppressions(source))


# ---------------------------------------------------------------------------
# Tier-1: cross-process merge under skewed clocks, backend-free
# ---------------------------------------------------------------------------


_CHILD_CODE = """
import os, sys
from tensor2robot_tpu.obs import graftrace
from tensor2robot_tpu.obs import trace as obs_trace
root, role, parent_span = sys.argv[1], sys.argv[2], sys.argv[3]
graftrace.configure(root, role=role)
ctx = graftrace.mint()
if parent_span != "-":
  ctx = graftrace.TraceContext("shared-trace", ctx.span_id, parent_span)
obs_trace.instant("proc/" + role, cat="test", **ctx.args())
path = graftrace.flush()
assert path is not None, "flush produced no shard"
from jax._src import xla_bridge
assert not getattr(xla_bridge, "_backends", None), "backend initialized"
print("SPAN=" + ctx.span_id)
"""


def _run_child(tmp_path, role, parent_span, skew_ns):
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "graftrace_trap",
         "GRAFTRACE_EPOCH_SKEW_NS": str(skew_ns)}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", _CHILD_CODE, str(tmp_path), role,
       parent_span],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
      env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  for line in result.stdout.splitlines():
    if line.startswith("SPAN="):
      return line[len("SPAN="):]
  raise AssertionError(f"no span id printed: {result.stdout!r}")


def test_two_subprocesses_with_skewed_clocks_merge_causally(tmp_path):
  """Two REAL processes, the second's wall clock an hour behind, the
  second's event causally parented on the first's. (An hour, not
  seconds: the skew has to exceed the real time between the two events,
  and under six test workers a child takes seconds to start — at 3 s the
  downstream event could land AFTER the upstream one even skewed, and
  there was nothing to repair.) The merged timeline
  must (a) come out causally ordered (the skew repair), (b) carry the
  synthesized flow link, (c) never have touched a JAX backend in
  either child (poisoned platform)."""
  upstream = _run_child(tmp_path, "upstream", "-", skew_ns=0)
  time.sleep(0.05)  # real elapsed time between cause and effect
  _run_child(tmp_path, "downstream", upstream,
             skew_ns=-3_600_000_000_000)
  merged = aggregate_lib.merge_timeline(str(tmp_path))
  stats = merged["stats"]
  assert stats["shards"] == 2 and stats["processes"] == 2
  assert stats["flow_links"] >= 1
  assert stats["skew_corrected_pids"]  # the skewed child was shifted
  events = [e for e in merged["payload"]["traceEvents"]
            if e.get("ph") == "i"]
  by_name = {e["name"]: e for e in events}
  assert by_name["proc/downstream"]["ts"] >= by_name["proc/upstream"]["ts"]
  assert aggregate_lib.has_causal_chain(
      events, ["proc/upstream", "proc/downstream"])


def test_graftrace_surface_is_backend_free(tmp_path):
  """graftrace + aggregate + the timeline CLI run end to end under a
  poisoned JAX_PLATFORMS without initializing any backend (the obs/
  tier-1 discipline)."""
  code = """
import json, os, sys
from tensor2robot_tpu.obs import aggregate, graftrace
from tensor2robot_tpu.obs import trace as obs_trace
root = sys.argv[1]
graftrace.configure(root, role="trap")
ctx = graftrace.mint()
with graftrace.activate(ctx):
  with obs_trace.span("serve/request", cat="serve"):
    graftrace.record_stage("queue_wait", 1.0)
graftrace.flush()
from tensor2robot_tpu.bin import graftscope
rc = graftscope.main(["timeline", root])
assert rc == 0, rc
payload = json.load(open(os.path.join(root, "timeline.json")))
assert any(e.get("name") == "serve/request"
           for e in payload["traceEvents"])
from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("GRAFTRACE_NO_BACKEND_OK")
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "graftrace_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", code, str(tmp_path / "run")],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
      env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "GRAFTRACE_NO_BACKEND_OK" in result.stdout
