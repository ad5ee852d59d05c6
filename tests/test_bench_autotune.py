"""bench.py auto-tune policy: pure-logic tests over a fake probe.

The real measurements run in per-probe subprocesses on the chip
(untestable without one); the decision policy — batch doubling, OOM
halving, remat/s2d adoption, and the hang-deadline abort that keeps the
best-so-far number instead of forfeiting the headline JSON — is pure
logic over a probe callable and is pinned here, with the probe child's
own contract: it writes its record to a file, it refuses to measure
another platform than it was asked for, and a child past its deadline
is stopped and reaped, never left holding the chip. Reference analogue:
the reference has no throughput bench; policy provenance is
PERFORMANCE.md and the round-4 AOT lever matrix.
"""

from __future__ import annotations

import importlib.util
import os
import sys

_BENCH_PATH = os.path.join(os.path.dirname(__file__), "..", "bench.py")
_spec = importlib.util.spec_from_file_location("bench", _BENCH_PATH)
bench = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench)


def test_probe_child_writes_its_record_to_the_file(tmp_path):
  """The record round-trips as JSON and carries a real measured
  throughput, on the platform it was asked to measure."""
  import json
  out_path = str(tmp_path / "probe.json")
  bench._probe_child_entry(
      json.dumps({"platform": "cpu", "batch_size": 4}), out_path)
  rec = json.load(open(out_path))
  assert rec["ok"] and rec["batch_size"] == 4
  assert rec["examples_per_sec"] > 0 and rec["platform"] == "cpu"
  assert not os.path.exists(out_path + ".tmp")


def test_probe_child_refuses_to_measure_another_platform(tmp_path):
  """A TPU probe on a process whose jax runs on the CPU is an error
  record flagged `wrong_platform` — never a CPU number."""
  import json
  out_path = str(tmp_path / "probe.json")
  bench._probe_child_entry(
      json.dumps({"platform": "tpu", "batch_size": 4}), out_path)
  rec = json.load(open(out_path))
  assert rec["ok"] is False and rec["wrong_platform"] is True
  assert "asked for platform 'tpu'" in rec["error"]
  assert "examples_per_sec" not in rec


def test_subprocess_probe_stops_and_reaps_a_child_past_its_deadline(
    monkeypatch):
  """One process for each chip: a probe child that outlives its
  deadline is terminated, then killed, and WAITED for — it is never
  left running with the chip in its hands."""
  events = []

  class HangingProc:
    returncode = None

    def __init__(self, argv, **kw):
      assert argv[2] == "--probe"

    def wait(self, timeout=None):
      events.append(("wait", timeout))
      if len([e for e in events if e[0] == "wait"]) < 3:
        raise bench.subprocess.TimeoutExpired(cmd="probe", timeout=timeout)
      return -9

    def terminate(self):
      events.append(("terminate", None))

    def kill(self):
      events.append(("kill", None))

  monkeypatch.setattr(bench.subprocess, "Popen", HangingProc)
  assert bench._subprocess_probe(64, deadline=0.01) == {"timeout": True}
  assert [e[0] for e in events] == ["wait", "terminate", "wait", "kill",
                                    "wait"]


class FakeProbe:
  """Maps (batch, remat, s2d) -> ex/s, 'oom', 'timeout', or 'error'."""

  def __init__(self, table):
    self.table = table
    self.calls = []

  def __call__(self, batch, remat, s2d):
    self.calls.append((batch, remat, s2d))
    val = self.table[(batch, remat, s2d)]
    if val == "timeout":
      return {"timeout": True}
    if val == "oom":
      return {"ok": False, "error": "RESOURCE_EXHAUSTED: hbm"}
    if val == "error":
      return {"ok": False, "error": "XlaRuntimeError: boom"}
    return {"ok": True, "examples_per_sec": val, "step_sec": batch / val,
            "flops": 1e12, "bytes_accessed": 2e10,
            "device_kind": "TPU v5e", "platform": "tpu",
            "batch_size": batch}


def test_doubling_runs_to_cap_and_probes_remat_s2d_at_winner():
  probe = FakeProbe({
      (64, False, False): 1000.0,
      (128, False, False): 1500.0,
      (256, False, False): 1200.0,   # regression: doubling continues
      (512, False, False): 1100.0,
      (128, True, False): 1400.0,    # remat loses
      (128, False, True): 1600.0,    # s2d wins
  })
  best = bench.autotune(probe)
  assert best["batch_size"] == 128
  assert not best["remat"] and best["s2d"]
  assert best["examples_per_sec"] == 1600.0
  assert best["value_batch64"] == 1000.0
  assert not best["aborted"]
  # s2d probed at the winning batch with the winning remat setting.
  assert (128, False, True) in probe.calls


def test_remat_win_carries_into_s2d_probe():
  probe = FakeProbe({
      (64, False, False): 1000.0,
      (128, False, False): 900.0,
      (256, False, False): 800.0,
      (512, False, False): 700.0,
      (64, True, False): 1100.0,
      (64, True, True): 1050.0,
  })
  best = bench.autotune(probe)
  assert best["batch_size"] == 64 and best["remat"] and not best["s2d"]
  assert best["examples_per_sec"] == 1100.0
  assert (64, True, True) in probe.calls


def test_priority_batch_probed_first_secures_headline_on_timeout():
  """The measured-winner batch is probed FIRST, so a stall on a
  later probe keeps the HEADLINE number (the old ascending order kept
  only the b64 comparison — below the north star)."""
  probe = FakeProbe({
      (256, False, False): 2480.0,
      (64, False, False): "timeout",
  })
  best = bench.autotune(probe)
  assert probe.calls[0] == (256, False, False)
  assert best["examples_per_sec"] == 2480.0
  assert best["batch_size"] == 256
  assert best["aborted"]
  assert best["value_batch64"] is None  # the b64 probe never landed
  # Nothing further probed once a probe hung.
  assert probe.calls == [(256, False, False), (64, False, False)]


def test_timeout_on_first_probe_returns_none_for_fallback():
  probe = FakeProbe({(256, False, False): "timeout"})
  assert bench.autotune(probe) is None


def test_error_everywhere_fails_fast_without_degraded_probes():
  """Generic (non-OOM) failures across the ladder must NOT trigger the
  degraded halving — four more full-deadline probes can't succeed
  either; fall back to the caller immediately."""
  errs = {(b, False, False): "error" for b in (256, 64, 128, 512)}
  probe = FakeProbe(errs)
  assert bench.autotune(probe) is None
  assert all(b >= 64 for b, _, _ in probe.calls)  # no 32/16/8/4 probes


def test_oom_everywhere_halves_initial_batch_without_doubling():
  probe = FakeProbe({
      (256, False, False): "oom",   # floor=256
      (64, False, False): "oom",    # floor=64 -> 128/512 skipped
      (32, False, False): 800.0,    # degraded winner
      (32, True, False): 700.0,
      (32, False, True): 750.0,
  })
  best = bench.autotune(probe)
  assert best["batch_size"] == 32
  assert best["value_batch64"] is None
  # An OOMed floor skips every larger rung (they only OOM harder).
  assert (128, False, False) not in probe.calls
  assert (512, False, False) not in probe.calls


def test_doubling_crosses_a_cliff_valley_to_the_far_winner():
  """The round-5 on-chip shape: b128 falls into a ~5x-slow compiler
  valley but b256 returns to the fast regime ABOVE the b64 number.
  Stopping at the first regression would forfeit the real winner."""
  probe = FakeProbe({
      (64, False, False): 1478.0,
      (128, False, False): 285.0,    # valley
      (256, False, False): 2480.0,   # fast regime returns — the winner
      (512, False, False): 2000.0,
      (256, True, False): 1000.0,
      (256, False, True): 1200.0,
  })
  best = bench.autotune(probe)
  assert best["batch_size"] == 256
  assert best["examples_per_sec"] == 2480.0
  assert best["value_batch64"] == 1478.0


def test_oom_mid_doubling_stops_larger_probes():
  """RESOURCE_EXHAUSTED at a doubled batch ends the doubling (larger
  batches only OOM harder — measured: b512 OOMs where b256 wins) but
  remat/s2d still probe at the winner."""
  probe = FakeProbe({
      (64, False, False): 1478.0,
      (128, False, False): 285.0,
      (256, False, False): 2480.0,
      (512, False, False): "oom",
      (256, True, False): 1000.0,
      (256, False, True): 1200.0,
  })
  best = bench.autotune(probe)
  assert best["batch_size"] == 256
  assert best["examples_per_sec"] == 2480.0
  assert not best["aborted"]
  assert (1024, False, False) not in probe.calls


def test_probe_failure_mid_tune_keeps_best_without_abort():
  probe = FakeProbe({
      (64, False, False): 1000.0,
      (128, False, False): "error",
      (256, False, False): "error",
      (512, False, False): "error",
      (64, True, False): "error",
      (64, False, True): "error",
  })
  best = bench.autotune(probe)
  assert best["examples_per_sec"] == 1000.0
  assert not best["aborted"]
  # Non-timeout failures keep probing (an OOM at batch 128 says
  # nothing about remat at batch 64).
  assert (64, False, True) in probe.calls


def test_transient_oom_below_a_successful_rung_does_not_mask_larger():
  """ADVICE.md round 5: the ladder probes 256 FIRST; a transient OOM at
  the b64 comparison probe therefore says nothing about b128/b512 when
  b256 already fit — before the fix, the oom_floor silently skipped
  them and the headline was stuck at the priority batch."""
  probe = FakeProbe({
      (256, False, False): 1200.0,
      (64, False, False): "oom",     # transient — 256 already fit
      (128, False, False): 1300.0,
      (512, False, False): 1500.0,   # the real winner
      (512, True, False): 1000.0,
      (512, False, True): 1100.0,
  })
  best = bench.autotune(probe)
  assert (128, False, False) in probe.calls
  assert (512, False, False) in probe.calls
  assert best["batch_size"] == 512
  assert best["examples_per_sec"] == 1500.0
  assert best["value_batch64"] is None  # the b64 probe itself OOMed


def test_genuine_capacity_ceiling_still_short_circuits():
  """An OOM above every successful rung is a real ceiling: nothing
  larger has ever fit, so larger rungs stay skipped."""
  probe = FakeProbe({
      (256, False, False): "oom",    # priority probe OOMs first
      (64, False, False): 1000.0,
      (128, False, False): 1100.0,
      (128, True, False): 900.0,
      (128, False, True): 950.0,
  })
  best = bench.autotune(probe)
  # 512 >= floor(256) and no success above the floor -> skipped.
  assert (512, False, False) not in probe.calls
  assert best["batch_size"] == 128


def test_barrier_dominated_probe_never_outranks_clean_measurement():
  """A clamped (barrier-dominated) timing can inflate examples/sec by
  up to the clamp factor; the headline must come from a clean
  measurement whenever one exists — in the ladder AND in the remat/s2d
  adoption comparisons."""
  def probe(b, remat, s2d):
    rec = {"ok": True, "step_sec": 0.01, "flops": 1e12,
           "bytes_accessed": 1e10, "device_kind": "TPU v5e",
           "platform": "tpu", "batch_size": b}
    if (b, remat, s2d) == (128, False, False):
      # Suspiciously fast AND flagged: must not win.
      return dict(rec, examples_per_sec=9999.0, barrier_dominated=True)
    if (b, remat, s2d) == (256, True, False):
      return dict(rec, examples_per_sec=8888.0, barrier_dominated=True)
    return dict(rec, examples_per_sec=1000.0 + b,
                barrier_dominated=False)

  best = bench.autotune(probe)
  assert best["batch_size"] == 512
  assert best["examples_per_sec"] == 1512.0
  assert best["barrier_dominated"] is False
  assert not best["remat"]  # the flagged remat 8888 didn't displace it


def test_all_probes_barrier_dominated_still_yields_a_headline():
  """When EVERY probe is flagged, the best flagged number still wins —
  a degraded headline beats no headline."""
  def probe(b, remat, s2d):
    return {"ok": True, "examples_per_sec": 1000.0 + b,
            "step_sec": 0.01, "flops": None, "bytes_accessed": None,
            "device_kind": "TPU v5e", "platform": "tpu",
            "batch_size": b, "barrier_dominated": True}

  best = bench.autotune(probe)
  assert best["batch_size"] == 512
  assert best["barrier_dominated"] is True


def test_record_probe_counts_every_outcome():
  """_record_probe's accounting: ok, failed and timed-out probes each
  land in their own `bench/probes_*` counter (the headline's
  `graftscope` block)."""
  from tensor2robot_tpu.obs import metrics as metrics_lib

  with metrics_lib.isolated():
    bench._record_probe({"ok": True, "examples_per_sec": 1.0,
                         "step_sec": 1.0, "platform": "tpu"})
    bench._record_probe({"ok": False,
                         "error": "RESOURCE_EXHAUSTED: hbm"})
    bench._record_probe({"ok": False, "error": "libtpu mismatch"})
    bench._record_probe({"timeout": True})
    block = bench._graftscope_block()["metrics"]
  assert block["counter/bench/probes_ok"] == 1.0
  assert block["counter/bench/probes_failed"] == 2.0
  assert block["counter/bench/probes_timeout"] == 1.0
