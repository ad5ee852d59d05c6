"""graftscope-sentinel: online detectors, flight recorder, postmortems.

Semantic coverage (not just shapes/files):

* synthetic step streams with injected spikes / starvation / NaN /
  HBM drift produce EXACTLY the expected `graftscope-incident-v1`
  records (and barrier-dominated records are excluded from spike
  detection — the ADVICE round-5 clamp contract);
* the stepstats barrier piggyback flags non-finite params with zero
  extra fetches;
* a synthetic NaN-loss run and a synthetic (watchdog) hang each dump a
  flight-recorder bundle that `graftscope postmortem` renders with the
  last N steps and the incident timeline;
* SIGTERM dumps a bundle from the signal handler — proven in a
  subprocess under a poisoned JAX_PLATFORMS (the handler touches
  host-side state only, no backend);
* bench's headline mode measures the chip or fails: without one it
  exits non-zero and prints no number under any metric name, and an
  unknown `device_kind` is an error, never a default peak;
* a crashing train_eval run dumps a bundle; a healthy run does not,
  and its run record carries the sentinel block;
* tier-1 poisoned-platform trap over sentinel/flightrec imports,
  detectors, dump, and the postmortem CLI.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from tensor2robot_tpu import train_eval
from tensor2robot_tpu.bin import graftscope
from tensor2robot_tpu.hooks import core as hooks_lib
from tensor2robot_tpu.obs import flightrec as flightrec_lib
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import runlog as runlog_lib
from tensor2robot_tpu.obs import sentinel as sentinel_lib
from tensor2robot_tpu.obs import stepstats as stepstats_lib
from tensor2robot_tpu.utils import config, mocks

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_obs_state():
  """Hermetic registry per test."""
  with metrics_lib.isolated():
    yield


def _steady(step_ms=100.0, wait_ms=5.0, **kw):
  record = {"step_ms": step_ms, "data_wait_ms": wait_ms,
            "barrier_dominated": 0.0, "nonfinite_params": 0.0}
  record.update(kw)
  return record


# ---------------------------------------------------------------------------
# Sentinel detectors: synthetic streams -> exact incident records.
# ---------------------------------------------------------------------------


class TestDetectors:

  def test_step_time_spike_exact_incident(self):
    s = sentinel_lib.Sentinel(clock=lambda: 1234.5)
    for i in range(20):
      s.observe_step_record(i, _steady())
    s.observe_step_record(20, _steady(step_ms=1000.0))
    for i in range(21, 30):
      s.observe_step_record(i, _steady())
    incidents = s.incidents()
    assert len(incidents) == 1
    inc = incidents[0]
    assert inc["schema"] == runlog_lib.INCIDENT_SCHEMA
    assert inc["kind"] == "step_time_spike"
    assert inc["severity"] == "warn"
    assert inc["step"] == 20
    assert inc["value"] == 1000.0
    assert inc["unix_time"] == 1234.5
    # Threshold is the EWMA + max(6*1.4826*MAD, 0.5*EWMA) rule: with a
    # constant 100 ms stream, MAD == 0 so the floor term governs.
    assert inc["threshold"] == pytest.approx(150.0)

  def test_spike_episode_emits_once_and_rearms_after_recovery(self):
    """Latched per episode: consecutive spiking windows are ONE
    incident; a recovered-then-re-spiking stream is a second one. A
    one-off spike also must not drag the EWMA up (the next detection's
    bar stays where it was)."""
    s = sentinel_lib.Sentinel()
    for i in range(20):
      s.observe_step_record(i, _steady())
    s.observe_step_record(20, _steady(step_ms=1000.0))
    s.observe_step_record(21, _steady(step_ms=1000.0))
    assert [i["step"] for i in s.incidents()] == [20]
    s.observe_step_record(22, _steady())  # episode ends
    s.observe_step_record(23, _steady(step_ms=900.0))
    assert [i["step"] for i in s.incidents()] == [20, 23]

  def test_persistent_regime_shift_adapts_instead_of_flooding(self):
    """The device slowing down FOR GOOD is one incident + a new baseline,
    not an incident per window forever (which would fsync-append
    thousands of identical records and evict the pre-shift timeline
    from every ring buffer). After adaptation, a spike over the NEW
    regime fires again."""
    s = sentinel_lib.Sentinel()
    for i in range(20):
      s.observe_step_record(i, _steady())
    for i in range(20, 60):  # 2x shift, permanently
      s.observe_step_record(i, _steady(step_ms=200.0))
    assert [i["step"] for i in s.incidents()] == [20]
    # The baseline has adapted: a 2x spike over the NEW regime fires.
    s.observe_step_record(60, _steady(step_ms=400.0))
    assert [i["step"] for i in s.incidents()] == [20, 60]

  def test_barrier_dominated_records_skip_spike_detection(self):
    """A barrier-dominated window's step_ms is an UPPER BOUND
    (stepstats.BARRIER_DOMINATED_RESIDUAL), not a measurement — the
    spike detector must ignore it entirely."""
    s = sentinel_lib.Sentinel()
    for i in range(20):
      s.observe_step_record(i, _steady())
    s.observe_step_record(20, _steady(step_ms=1000.0,
                                      barrier_dominated=1.0))
    assert s.incidents() == []

  def test_data_starvation_fires_after_consecutive_windows(self):
    s = sentinel_lib.Sentinel()
    s.observe_step_record(0, _steady())
    for i in range(1, 4):
      s.observe_step_record(i, _steady(wait_ms=80.0))
    incidents = s.incidents()
    assert len(incidents) == 1
    inc = incidents[0]
    assert inc["kind"] == "data_starvation"
    assert inc["step"] == 3  # the third consecutive starved window
    assert inc["value"] == pytest.approx(0.8)
    assert inc["threshold"] == pytest.approx(0.6)
    # Latched while the episode continues...
    s.observe_step_record(4, _steady(wait_ms=80.0))
    assert len(s.incidents()) == 1
    # ...and re-arms after recovery.
    s.observe_step_record(5, _steady())
    for i in range(6, 9):
      s.observe_step_record(i, _steady(wait_ms=90.0))
    assert len(s.incidents()) == 2

  def test_two_starved_windows_do_not_fire(self):
    s = sentinel_lib.Sentinel()
    s.observe_step_record(0, _steady(wait_ms=80.0))
    s.observe_step_record(1, _steady(wait_ms=80.0))
    s.observe_step_record(2, _steady())
    assert s.incidents() == []

  def test_nonfinite_params_is_fatal_and_latched(self):
    s = sentinel_lib.Sentinel()
    s.observe_step_record(0, _steady())
    s.observe_step_record(1, _steady(nonfinite_params=1.0))
    s.observe_step_record(2, _steady(nonfinite_params=1.0))
    incidents = s.incidents()
    assert [i["kind"] for i in incidents] == ["nonfinite_params"]
    assert incidents[0]["severity"] == "fatal"
    assert incidents[0]["step"] == 1

  def test_nonfinite_metric_latched_per_metric(self):
    s = sentinel_lib.Sentinel()
    s.observe_metrics(1, {"loss": 0.5, "grad_norm": 2.0})
    assert s.incidents() == []
    s.observe_metrics(2, {"loss": float("nan"), "grad_norm": 2.0})
    s.observe_metrics(3, {"loss": float("nan"),
                          "grad_norm": float("inf")})
    incidents = s.incidents()
    assert sorted(i["detail"]["metric"] for i in incidents) == [
        "grad_norm", "loss"]
    assert all(i["severity"] == "fatal" for i in incidents)
    # A NaN value cannot live in strict JSON: it is recorded as a repr.
    loss_inc = next(i for i in incidents
                    if i["detail"]["metric"] == "loss")
    assert "value" not in loss_inc
    assert loss_inc["detail"]["value_repr"] == "nan"
    json.dumps(incidents, allow_nan=False)  # the append contract holds

  def test_nonfinite_metric_skips_live_device_values(self):
    """The zero-extra-round-trips contract: a value that is not already
    host-side (e.g. a live jax array in the single-step path) must be
    SKIPPED, not fetched."""
    import jax.numpy as jnp

    fetches = []

    class _Tattletale:
      """A stand-in device value that records any host conversion."""

      def __array__(self, *a, **k):
        fetches.append(1)
        return np.zeros(())

    s = sentinel_lib.Sentinel()
    s.observe_metrics(1, {"device": _Tattletale(),
                          "jax": jnp.zeros(()),
                          "host": float("nan")})
    assert fetches == []
    assert [i["detail"]["metric"] for i in s.incidents()] == ["host"]

  def test_hbm_drift_ratchets(self):
    base = 1e9
    s = sentinel_lib.Sentinel()
    s.observe_step_record(0, _steady(device_bytes_in_use=base))
    s.observe_step_record(1, _steady(device_bytes_in_use=base * 1.1))
    assert s.incidents() == []  # below the 20% rel threshold
    s.observe_step_record(2, _steady(device_bytes_in_use=base * 1.4))
    incidents = s.incidents()
    assert [i["kind"] for i in incidents] == ["hbm_drift"]
    assert incidents[0]["value"] == pytest.approx(base * 1.4)
    # Watermark ratcheted: stable-at-the-new-level is NOT a new incident,
    # a further +20% is.
    s.observe_step_record(3, _steady(device_bytes_in_use=base * 1.4))
    assert len(s.incidents()) == 1
    s.observe_step_record(4, _steady(device_bytes_in_use=base * 1.75))
    assert len(s.incidents()) == 2

  def test_gradual_leak_accumulates_and_fires(self):
    """The blind-OOM case: +8%/window stays under the per-window
    threshold forever, but the baseline only ratchets ON incident, so
    the CUMULATIVE drift crosses +20% and fires — then re-arms against
    the new watermark."""
    s = sentinel_lib.Sentinel()
    value = 1e9
    fired_at = []
    for i in range(40):
      s.observe_step_record(i, _steady(device_bytes_in_use=value))
      if len(s.incidents()) > len(fired_at):
        fired_at.append(i)
      value *= 1.08
    # ~3 windows per +20%: a 40-window leak fires repeatedly, each time
    # against the previous incident's watermark.
    assert len(fired_at) >= 8
    assert fired_at[0] == 3  # 1.08^3 = 1.26 > 1.2 cumulative
    for inc in s.incidents():
      assert inc["kind"] == "hbm_drift"

  def test_small_absolute_growth_never_fires(self):
    """The CPU-smoke guard: tiny live-bytes wobble is relatively large
    but absolutely trivial — the drift_min_bytes gate keeps it quiet."""
    s = sentinel_lib.Sentinel()
    s.observe_step_record(0, _steady(live_bytes=1e6))
    s.observe_step_record(1, _steady(live_bytes=3e6))
    assert s.incidents() == []

  def test_incidents_count_into_registry_and_sinks(self):
    sunk = []
    s = sentinel_lib.Sentinel(sinks=[sunk.append])
    s.observe_metrics(1, {"loss": float("nan")})
    snap = metrics_lib.snapshot()
    assert snap["counter/sentinel/incidents"] == 1.0
    assert snap["counter/sentinel/nonfinite_metric"] == 1.0
    assert len(sunk) == 1 and sunk[0]["kind"] == "nonfinite_metric"

  def test_failing_sink_does_not_break_detection(self, capsys):
    def bad_sink(record):
      raise RuntimeError("sink exploded")

    s = sentinel_lib.Sentinel(sinks=[bad_sink])
    s.observe_metrics(1, {"loss": float("nan")})
    assert len(s.incidents()) == 1
    assert "sink failed" in capsys.readouterr().err

  def test_serving_slo_breach_counter(self):
    assert not sentinel_lib.observe_serving_latency(5.0, 10.0)
    assert sentinel_lib.observe_serving_latency(25.0, 10.0)
    assert not sentinel_lib.observe_serving_latency(25.0, None)  # disabled
    snap = metrics_lib.snapshot()
    assert snap["counter/serve/slo_breaches"] == 1.0
    assert snap["hist/serve/slo_breach_ms/max"] == 25.0


# ---------------------------------------------------------------------------
# The stepstats barrier.
# ---------------------------------------------------------------------------


class TestStepStatsBarrier:

  def test_stepstats_barrier_piggybacks_the_nonfinite_check(self):
    """The piggyback contract: one barrier fetch feeds the divergence
    check — no extra fetches."""
    fetches = []

    def barrier(state):
      fetches.append(1)
      return np.array([1.0, float("nan")])

    rec = stepstats_lib.StepStatsRecorder(batch_size=4, every_n_steps=1,
                                          barrier=barrier,
                                          device_gauges=False)
    seen = []
    rec.add_observer(lambda step, record: seen.append((step, record)))
    rec.start()
    rec.before_dispatch()
    rec.after_dispatch()
    rec.end_step(1, state=object())
    assert fetches == [1]
    (step, record), = seen
    assert step == 1
    assert record["nonfinite_params"] == 1.0

  def test_stepstats_flags_barrier_dominated_windows(self):
    rec = stepstats_lib.StepStatsRecorder(
        batch_size=4, every_n_steps=1, device_gauges=False,
        barrier=lambda state: time.sleep(0.05))
    seen = []
    rec.add_observer(lambda step, record: seen.append(record))
    rec.start()
    rec.before_dispatch()
    rec.after_dispatch()
    rec.end_step(1, state=object())
    assert seen[0]["barrier_dominated"] == 1.0

  def test_failing_barrier_raises_and_emits_no_record(self):
    """A device that dies mid-run surfaces as a FAILING barrier fetch:
    the error reaches the loop (and its flight-recorder dump) and no
    record is made of a window that never closed."""

    def dying_barrier(state):
      raise RuntimeError("device lost mid-fetch")

    rec = stepstats_lib.StepStatsRecorder(batch_size=4, every_n_steps=1,
                                          barrier=dying_barrier,
                                          device_gauges=False)
    seen = []
    rec.add_observer(lambda step, record: seen.append(record))
    rec.start()
    rec.before_dispatch()
    rec.after_dispatch()
    with pytest.raises(RuntimeError, match="device lost mid-fetch"):
      rec.end_step(1, state=object())
    assert seen == [] and rec.drain() == []

  def test_broken_observer_is_detached_not_fatal(self, capsys):
    rec = stepstats_lib.StepStatsRecorder(batch_size=4, every_n_steps=1,
                                          barrier=lambda s: None,
                                          device_gauges=False)
    rec.add_observer(lambda step, record: 1 / 0)
    rec.start()
    for step in (1, 2):
      rec.before_dispatch()
      rec.after_dispatch()
      rec.end_step(step, state=object())
    assert len(rec.drain()) == 2  # the loop survived both windows
    assert "detached" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# Flight recorder: ring bounds, fatal auto-dump, watchdog, SIGTERM.
# ---------------------------------------------------------------------------


class TestFlightRecorder:

  def test_ring_buffer_keeps_last_capacity_steps(self, tmp_path):
    fr = flightrec_lib.FlightRecorder(str(tmp_path), capacity=16)
    for i in range(50):
      fr.record_step(i, {"step_ms": float(i)})
    bundle_dir = fr.dump("test")
    bundle = json.load(open(os.path.join(bundle_dir,
                                         flightrec_lib.BUNDLE_FILENAME)))
    assert [r["step"] for r in bundle["steps"]] == list(range(34, 50))
    assert bundle["schema"] == flightrec_lib.POSTMORTEM_SCHEMA
    assert bundle["reason"] == "test"

  def test_nan_steps_survive_strict_json(self, tmp_path):
    fr = flightrec_lib.FlightRecorder(str(tmp_path), capacity=4)
    fr.record_step(1, {"loss": float("nan"), "step_ms": 2.0})
    bundle_dir = fr.dump("test")
    bundle = json.load(open(os.path.join(bundle_dir,
                                         flightrec_lib.BUNDLE_FILENAME)))
    assert bundle["steps"][0]["loss"] == "nan"
    assert bundle["steps"][0]["step_ms"] == 2.0

  def test_fatal_incident_auto_dumps_once_per_kind(self, tmp_path):
    fr = flightrec_lib.FlightRecorder(str(tmp_path), capacity=4)
    warn = runlog_lib.make_incident("step_time_spike", step=1)
    fatal = runlog_lib.make_incident("nonfinite_metric", step=2,
                                     severity="fatal")
    fr.record_incident(warn)
    assert fr.dumps() == []  # warnings ring-buffer only
    fr.record_incident(fatal)
    fr.record_incident(dict(fatal, step=3))
    dumps = fr.dumps()
    assert len(dumps) == 1
    bundle = json.load(open(os.path.join(
        dumps[0], flightrec_lib.BUNDLE_FILENAME)))
    assert bundle["reason"] == "incident:nonfinite_metric"
    # The dump fires AT the first fatal, so the bundle holds everything
    # up to and including it (the later duplicate only rings).
    assert [i["kind"] for i in bundle["incidents"]] == [
        "step_time_spike", "nonfinite_metric"]

  def test_watchdog_dumps_on_synthetic_hang(self, tmp_path):
    """A loop that stops touch()ing IS the hang — the watchdog dumps
    exactly one bundle from host-side state while the 'hang' is live,
    and a recovered loop re-arms it."""
    fr = flightrec_lib.FlightRecorder(str(tmp_path), capacity=8,
                                      hang_timeout_secs=0.2)
    for i in range(5):
      fr.record_step(i, {"step_ms": 10.0})
    fr.install()
    try:
      fr.touch()
      deadline = time.monotonic() + 5.0
      while not fr.dumps() and time.monotonic() < deadline:
        time.sleep(0.05)
      assert len(fr.dumps()) == 1
      time.sleep(0.5)  # still hung: latched, no second bundle
      assert len(fr.dumps()) == 1
    finally:
      fr.close()
    bundle = json.load(open(os.path.join(
        fr.dumps()[0], flightrec_lib.BUNDLE_FILENAME)))
    assert bundle["reason"] == "hang"
    assert bundle["watchdog"]["hang_timeout_secs"] == 0.2
    assert bundle["watchdog"]["stalled_secs"] > 0.2
    assert [r["step"] for r in bundle["steps"]] == list(range(5))

  def test_sigterm_handler_dumps_bundle_in_subprocess(self, tmp_path):
    """The handler must flush a bundle AND still let the process die
    with SIGTERM — under a poisoned JAX_PLATFORMS, proving the handler
    path touches no backend."""
    code = """
import os, signal, time
from tensor2robot_tpu.obs import flightrec
fr = flightrec.FlightRecorder(os.environ["OUT_DIR"], capacity=8)
for i in range(3):
    fr.record_step(i, {"step_ms": 1.0})
fr.install()
os.kill(os.getpid(), signal.SIGTERM)
time.sleep(30)  # must never be reached
raise SystemExit("survived SIGTERM")
"""
    env = {**os.environ, "PYTHONPATH": REPO_ROOT,
           "JAX_PLATFORMS": "flightrec_trap",
           "OUT_DIR": str(tmp_path)}
    env.pop("XLA_FLAGS", None)
    result = subprocess.run([sys.executable, "-c", code],
                            capture_output=True, text=True, timeout=120,
                            env=env, cwd=REPO_ROOT)
    assert result.returncode == -signal.SIGTERM, (result.returncode,
                                                  result.stderr[-2000:])
    bundles = flightrec_lib.find_bundles(str(tmp_path))
    assert len(bundles) == 1
    bundle = json.load(open(bundles[0]))
    assert bundle["reason"] == "sigterm"
    assert [r["step"] for r in bundle["steps"]] == [0, 1, 2]


# ---------------------------------------------------------------------------
# Postmortem bundles rendered semantically by the CLI.
# ---------------------------------------------------------------------------


def _synthetic_nan_loss_run(model_dir: str) -> None:
  """Drives sentinel + flight recorder through a synthetic
  run that diverges to NaN at step 12 — the train_eval wiring shape
  (sinks to incidents.jsonl AND the recorder), no backend needed."""
  fr = flightrec_lib.FlightRecorder(
      os.path.join(model_dir, flightrec_lib.FLIGHTREC_DIRNAME),
      capacity=32)
  incidents_path = os.path.join(model_dir, runlog_lib.INCIDENTS_FILENAME)
  s = sentinel_lib.Sentinel(sinks=[
      lambda record: runlog_lib.append_record(incidents_path, record),
      fr.record_incident])
  # Recorder BEFORE sentinel — the train_eval wiring order — so the
  # fatal-incident dump includes the very window that triggered it.
  for i in range(12):
    record = _steady(step_ms=100.0 + i)
    fr.record_step(i, record)
    s.observe_step_record(i, record)
    s.observe_metrics(i, {"loss": 1.0 / (i + 1)})
  bad = _steady(step_ms=112.0, nonfinite_params=1.0)
  fr.record_step(12, bad)
  s.observe_step_record(12, bad)
  s.observe_metrics(12, {"loss": float("nan")})


class TestPostmortemCLI:

  def test_nan_loss_bundle_renders_steps_and_incidents(
      self, tmp_path, capsys):
    model_dir = str(tmp_path)
    _synthetic_nan_loss_run(model_dir)
    assert graftscope.main(["postmortem", model_dir]) == 0
    out = capsys.readouterr().out
    # Last-N steps table, including the diverged window.
    assert "last " in out and "step_ms" in out
    assert "nonfinite_params" in out
    # The incident timeline names both fatal incidents and the metric.
    assert "nonfinite_params" in out
    assert "nonfinite_metric" in out and "metric=loss" in out
    assert "fatal" in out
    # The latest bundle's reason is a fatal divergence incident.
    assert "reason: incident:nonfinite_" in out
    # Observer-order contract: the window that TRIGGERED the fatal
    # incident must itself be in the bundle's step ring.
    first = json.load(open(flightrec_lib.find_bundles(model_dir)[0]))
    assert first["reason"] == "incident:nonfinite_params"
    assert first["steps"][-1]["step"] == 12
    assert first["steps"][-1]["nonfinite_params"] == 1.0

  def test_hang_bundle_renders_watchdog_and_steps(self, tmp_path,
                                                  capsys):
    fr = flightrec_lib.FlightRecorder(str(tmp_path), capacity=8,
                                      hang_timeout_secs=0.2)
    for i in range(4):
      fr.record_step(i, _steady(step_ms=10.0 + i))
    fr.install()
    try:
      fr.touch()
      deadline = time.monotonic() + 5.0
      while not fr.dumps() and time.monotonic() < deadline:
        time.sleep(0.05)
    finally:
      fr.close()
    assert graftscope.main(["postmortem", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "reason: hang" in out
    assert "watchdog: timeout 0.2s" in out
    assert "last 4 recorded step window(s)" in out

  def test_incidents_only_model_dir_renders_timeline(self, tmp_path,
                                                     capsys):
    """A run that logged incidents but never crashed still has a
    postmortem answer: the incident history."""
    path = os.path.join(str(tmp_path), runlog_lib.INCIDENTS_FILENAME)
    runlog_lib.append_record(path, runlog_lib.make_incident(
        "data_starvation", step=7, value=0.9, threshold=0.6))
    assert graftscope.main(["postmortem", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "incident history only" in out
    assert "data_starvation" in out

  def test_missing_dir_exits_2_and_empty_dir_exits_1(self, tmp_path,
                                                     capsys):
    assert graftscope.main(
        ["postmortem", str(tmp_path / "nope")]) == 2
    assert graftscope.main(["postmortem", str(tmp_path)]) == 1
    err = capsys.readouterr().err
    assert "no such path" in err and "no postmortem bundles" in err

  def test_wrong_typed_incident_fields_render_not_raise(self, tmp_path,
                                                        capsys):
    """The never-raise contract covers wrong TYPES, not just invalid
    JSON: a valid-JSON incident with string value/step/unix_time must
    render verbatim instead of killing the CLI with a TypeError."""
    path = os.path.join(str(tmp_path), runlog_lib.INCIDENTS_FILENAME)
    with open(path, "w") as f:
      f.write(json.dumps({"kind": "hbm_drift", "severity": "warn",
                          "value": "nan", "threshold": [1, 2],
                          "step": "twelve", "unix_time": "later"})
              + "\n")
    assert graftscope.main(["postmortem", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "hbm_drift" in out and "value=nan" in out

  def test_corrupt_bundle_is_skipped_not_raised(self, tmp_path, capsys):
    bundle_dir = tmp_path / (flightrec_lib.BUNDLE_PREFIX + "x")
    bundle_dir.mkdir()
    (bundle_dir / flightrec_lib.BUNDLE_FILENAME).write_bytes(
        b'{"schema": "graftscope-postmortem-v1", "reason": tru\xff')
    assert graftscope.main(["postmortem", str(tmp_path)]) == 2
    assert "corrupt bundle" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# train_eval integration: healthy runs stay clean, crashes dump.
# ---------------------------------------------------------------------------


class TestTrainEvalIntegration:

  def _run(self, model_dir, hook_builders=None, **kw):
    return train_eval.train_eval_model(
        model=mocks.MockT2RModel(device_type="cpu"),
        model_dir=model_dir,
        mode="train",
        max_train_steps=6,
        input_generator_train=mocks.MockInputGenerator(batch_size=8),
        log_every_n_steps=2,
        checkpoint_every_n_steps=100,
        hook_builders=hook_builders,
        **kw)

  def test_healthy_run_no_bundle_and_record_carries_sentinel(
      self, tmp_path):
    config.clear_config()
    model_dir = str(tmp_path)
    self._run(model_dir)
    flight_dir = os.path.join(model_dir, flightrec_lib.FLIGHTREC_DIRNAME)
    assert flightrec_lib.find_bundles(model_dir) == []
    assert not os.path.exists(
        os.path.join(model_dir, runlog_lib.INCIDENTS_FILENAME))
    assert not os.path.isdir(flight_dir) or not os.listdir(flight_dir)
    records = runlog_lib.load_records(
        os.path.join(model_dir, runlog_lib.RUNS_FILENAME))
    extra = records[-1]["extra"]
    assert extra["sentinel"] == {"incidents": 0, "by_kind": {}}
    assert records[-1]["platform"] == "cpu"

  def test_crashing_run_dumps_exception_bundle(self, tmp_path, capsys):
    config.clear_config()
    model_dir = str(tmp_path)

    class _Bomb(hooks_lib.Hook):

      def after_step(self, ctx, step, metrics):
        if step == 3:
          raise RuntimeError("injected step-3 crash")

    class _Builder(hooks_lib.HookBuilder):

      def create_hooks(self, model, md):
        return [_Bomb()]

    with pytest.raises(RuntimeError, match="injected step-3 crash"):
      self._run(model_dir, hook_builders=[_Builder()])
    bundles = flightrec_lib.find_bundles(model_dir)
    assert len(bundles) == 1
    bundle = json.load(open(bundles[0]))
    assert bundle["reason"] == "exception"
    assert bundle["exception"]["type"] == "RuntimeError"
    assert "injected step-3 crash" in bundle["exception"]["traceback"]
    # Ring buffer holds every window up to the crash (step 3's window
    # closed before its after_step hooks fired the bomb).
    assert [r["step"] for r in bundle["steps"]] == [1, 2, 3]
    # And the CLI renders it.
    assert graftscope.main(["postmortem", model_dir]) == 0
    out = capsys.readouterr().out
    assert "reason: exception" in out
    assert "RuntimeError" in out and "injected step-3 crash" in out

  def test_enable_sentinel_false_runs_bare(self, tmp_path):
    config.clear_config()
    model_dir = str(tmp_path)
    self._run(model_dir, enable_sentinel=False)
    assert flightrec_lib.find_bundles(model_dir) == []
    records = runlog_lib.load_records(
        os.path.join(model_dir, runlog_lib.RUNS_FILENAME))
    assert "sentinel" not in records[-1]["extra"]


# ---------------------------------------------------------------------------
# Finite train streams: mid-group batches are trained, not dropped.
# ---------------------------------------------------------------------------


class _FiniteInputGenerator(mocks.MockInputGenerator):
  """MockInputGenerator truncated to a fixed number of batches."""

  def __init__(self, num_batches: int, **kw):
    super().__init__(**kw)
    self._num_batches = num_batches

  def create_dataset(self, mode):
    import itertools

    return itertools.islice(super().create_dataset(mode),
                            self._num_batches)


def test_finite_stream_mid_group_batches_are_single_stepped(tmp_path):
  """Regression (ADVICE round 5): with iterations_per_loop=4 and a
  6-batch finite stream, the 2 batches consumed by the incomplete
  second group used to be DROPPED — they must train as single steps
  (mirror of the eval partial-group rule) before StopIteration
  propagates (the documented finite-stream loop-exit contract)."""
  config.clear_config()
  steps_seen = []

  class _Recorder(hooks_lib.Hook):

    def after_step(self, ctx, step, metrics):
      steps_seen.append(step)

  class _Builder(hooks_lib.HookBuilder):

    def create_hooks(self, model, model_dir):
      return [_Recorder()]

  with pytest.raises(StopIteration):
    train_eval.train_eval_model(
        model=mocks.MockT2RModel(device_type="cpu"),
        model_dir=str(tmp_path),
        mode="train",
        max_train_steps=20,
        input_generator_train=_FiniteInputGenerator(6, batch_size=8),
        iterations_per_loop=4,
        device_prefetch_depth=0,
        log_every_n_steps=100,
        checkpoint_every_n_steps=100,
        hook_builders=[_Builder()])
  assert steps_seen == [1, 2, 3, 4, 5, 6]
  # A finite stream ending is the loop-exit contract, not a crash: the
  # flight recorder must NOT have dumped an exception bundle for it.
  assert flightrec_lib.find_bundles(str(tmp_path)) == []


# ---------------------------------------------------------------------------
# Tier-1: sentinel/flightrec/postmortem CLI are backend-free.
# ---------------------------------------------------------------------------


def test_sentinel_flightrec_and_postmortem_cli_backend_free(tmp_path):
  """Imports, detectors, the flight-recorder dump AND the postmortem
  CLI must run without initializing any JAX backend — the obs/
  poisoned-platform discipline (tier-1): these are exactly the
  components that must work while the device is hung."""
  code = """
import json, os, sys
from tensor2robot_tpu.obs import flightrec, runlog, sentinel
d = sys.argv[1]
fr = flightrec.FlightRecorder(os.path.join(d, "flightrec"), capacity=8)
inc = os.path.join(d, "incidents.jsonl")
s = sentinel.Sentinel(sinks=[lambda r: runlog.append_record(inc, r),
                             fr.record_incident])
for i in range(12):
    rec = {"step_ms": 50.0, "data_wait_ms": 40.0,
           "barrier_dominated": 0.0, "nonfinite_params": 0.0}
    s.observe_step_record(i, rec)
    fr.record_step(i, rec)
s.observe_metrics(12, {"loss": float("nan")})
assert fr.dumps(), "fatal incident must have dumped a bundle"
from tensor2robot_tpu.bin import graftscope
rc = graftscope.main(["postmortem", d])
assert rc == 0, rc
from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("SENTINEL_NO_BACKEND_OK")
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "sentinel_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", code, str(tmp_path)],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "SENTINEL_NO_BACKEND_OK" in result.stdout
