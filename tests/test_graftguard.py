"""graftguard: deterministic fault injection + self-healing recovery.

What is proven here (ISSUE 13):

* `utils.retry.RetryPolicy` — jittered exponential backoff, deadline
  budget, retryable predicate, `retry/*` telemetry — deterministic
  under a seeded rng/fake clock;
* `obs.faultlab` — seeded deterministic fault plane: at/every/rate
  firing, per-key targeting, count caps, attribution summary, and a
  poisoned-platform trap (backend-free at import like the rest of
  `obs/`);
* checkpoint integrity — manifest sidecar at save, checksum
  verification before restore, QUARANTINE of bit-flipped/torn steps
  with automatic fallback to the newest verified step (including the
  satellite regression: `restore(step=None)` on a truncated latest
  step dir), reader-side managers never blessing foreign bytes;
* data-plane degradation — corrupt records / preprocess failures /
  source I/O errors skipped-and-counted under the `max_corrupt_records`
  quota (both the serial chain and the overlapped loader), strict
  raise-immediately behavior preserved at quota 0, raise past quota;
* divergence rewind — an injected NaN loss triggers sentinel ->
  flight-recorder bundle -> restore of the newest verified checkpoint,
  the run completes all steps, and the bounded rewind budget escalates
  to an abort when exhausted;
* graftlint `bare-retry-rule` — constant-sleep + broad-except-swallow
  retry loops flagged in serving//data/ hot paths only, suppressible,
  repo pinned clean.
"""

from __future__ import annotations

import json
import os
import random
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from tensor2robot_tpu import checkpoints as checkpoints_lib
from tensor2robot_tpu.analysis import retry_check
from tensor2robot_tpu.obs import faultlab
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.utils import retry as retry_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------


class TestRetryPolicy:

  def _policy(self, **kwargs):
    kwargs.setdefault("rng", random.Random(7))
    kwargs.setdefault("sleep", lambda s: None)
    return retry_lib.RetryPolicy(**kwargs)

  def test_succeeds_after_transient_failures(self):
    calls = []

    def flaky():
      calls.append(1)
      if len(calls) < 3:
        raise IOError("transient")
      return "ok"

    with metrics_lib.isolated() as registry:
      policy = self._policy(name="t", max_attempts=5)
      assert policy.call(flaky) == "ok"
      snap = registry.snapshot(prefix="retry/")
    assert len(calls) == 3
    assert snap["counter/retry/t/attempts"] == 3.0
    assert snap["counter/retry/t/retries"] == 2.0
    assert snap["counter/retry/t/giveups"] == 0.0

  def test_non_retryable_raises_immediately(self):
    calls = []

    def typo():
      calls.append(1)
      raise TypeError("programming error")

    policy = self._policy(retryable=lambda e: isinstance(e, IOError))
    with pytest.raises(TypeError):
      policy.call(typo)
    assert len(calls) == 1

  def test_budget_exhaustion_chains_last_error(self):
    policy = self._policy(name="x", max_attempts=3)
    with metrics_lib.isolated() as registry:
      with pytest.raises(retry_lib.RetryBudgetExhausted) as exc:
        policy.call(lambda: (_ for _ in ()).throw(IOError("down")))
      snap = registry.snapshot(prefix="retry/")
    assert isinstance(exc.value.__cause__, IOError)
    assert snap["counter/retry/x/giveups"] == 1.0
    assert snap["counter/retry/x/attempts"] == 3.0

  def test_deadline_budget_stops_attempts(self):
    clock = {"now": 0.0}

    def fake_sleep(s):
      clock["now"] += s

    policy = retry_lib.RetryPolicy(
        name="d", max_attempts=100, base_delay_s=1.0, multiplier=1.0,
        max_delay_s=1.0, jitter=0.0, deadline_s=3.5,
        sleep=fake_sleep, clock=lambda: clock["now"])
    calls = []
    with pytest.raises(retry_lib.RetryBudgetExhausted):
      policy.call(lambda: calls.append(1) or
                  (_ for _ in ()).throw(IOError()))
    # t=0, 1, 2, 3 attempts fit the 3.5 s budget; t=4 does not.
    assert len(calls) == 4

  def test_backoff_is_exponential_capped_and_jittered(self):
    policy = self._policy(base_delay_s=0.1, multiplier=2.0,
                          max_delay_s=0.5, jitter=0.5)
    raw = [policy.backoff_s(n) for n in range(6)]
    for n, delay in enumerate(raw):
      nominal = min(0.1 * 2 ** n, 0.5)
      assert 0.5 * nominal <= delay <= 1.5 * nominal
    # Seeded rng => deterministic schedule.
    again = self._policy(base_delay_s=0.1, multiplier=2.0,
                         max_delay_s=0.5, jitter=0.5)
    assert raw == [again.backoff_s(n) for n in range(6)]

  def test_delays_iterator_respects_attempt_cap(self):
    policy = self._policy(max_attempts=4, jitter=0.0, base_delay_s=0.1,
                          multiplier=2.0, max_delay_s=10.0)
    assert [round(d, 3) for d in policy.delays()] == [0.1, 0.2, 0.4]

  def test_jittered_s_bounds_and_determinism(self):
    rng = random.Random(3)
    for _ in range(50):
      d = retry_lib.jittered_s(2.0, jitter=0.25, rng=rng)
      assert 1.5 <= d <= 2.5
    assert retry_lib.jittered_s(2.0, jitter=0.0) == 2.0
    assert retry_lib.jittered_s(0.0) == 0.0
    with pytest.raises(ValueError):
      retry_lib.jittered_s(1.0, jitter=1.5)

  def test_validates_arguments(self):
    with pytest.raises(ValueError):
      retry_lib.RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
      retry_lib.RetryPolicy(jitter=1.5)


# ---------------------------------------------------------------------------
# faultlab
# ---------------------------------------------------------------------------


class TestFaultlab:

  def test_spec_validation(self):
    with pytest.raises(ValueError):
      faultlab.FaultSpec(point="nonsense.point", at=(0,))
    with pytest.raises(ValueError):
      faultlab.FaultSpec(point=faultlab.SERVE_DISPATCH)  # no mode
    with pytest.raises(ValueError):
      faultlab.FaultSpec(point=faultlab.SERVE_DISPATCH, at=(0,), every=2)
    with pytest.raises(ValueError):
      faultlab.FaultSpec(point=faultlab.SERVE_DISPATCH, rate=1.5)
    with pytest.raises(ValueError):
      # bool(-5) passes the one-mode check but can never fire.
      faultlab.FaultSpec(point=faultlab.SERVE_DISPATCH, every=-5)
    with pytest.raises(ValueError):
      faultlab.FaultSpec(point=faultlab.SERVE_DISPATCH, at=(-1,))

  def test_at_and_every_and_count(self):
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.SERVE_DISPATCH, at=(1, 3)),
        faultlab.FaultSpec(point=faultlab.DATA_PREPROCESS, every=2,
                           count=2),
    ], seed=5)
    dispatch = [plan.maybe_fire(faultlab.SERVE_DISPATCH) is not None
                for _ in range(5)]
    assert dispatch == [False, True, False, True, False]
    preprocess = [plan.maybe_fire(faultlab.DATA_PREPROCESS) is not None
                  for _ in range(8)]
    # every=2 fires on arrivals 1, 3 then the count cap stops it.
    assert preprocess == [False, True, False, True, False, False,
                          False, False]

  def test_key_targeting_and_independent_arrival_counters(self):
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.SERVE_DISPATCH, key=1,
                           at=(0,))], seed=0)
    assert plan.maybe_fire(faultlab.SERVE_DISPATCH, key=0) is None
    # Replica 1's OWN arrival 0, regardless of other keys' traffic.
    assert plan.maybe_fire(faultlab.SERVE_DISPATCH, key=1) is not None

  def test_rate_mode_is_deterministic_per_seed(self):
    def draws(seed):
      plan = faultlab.FaultPlan([
          faultlab.FaultSpec(point=faultlab.DATA_CORRUPT_RECORD,
                             rate=0.3)], seed=seed)
      return [plan.maybe_fire(faultlab.DATA_CORRUPT_RECORD) is not None
              for _ in range(64)]

    first, second = draws(11), draws(11)
    assert first == second
    assert first != draws(12)
    assert 4 <= sum(first) <= 40  # roughly Bernoulli(0.3)

  def test_counters_summary_and_fired(self):
    with metrics_lib.isolated() as registry:
      plan = faultlab.FaultPlan([
          faultlab.FaultSpec(point=faultlab.CKPT_TORN, at=(0,))], seed=2)
      assert plan.maybe_fire(faultlab.CKPT_TORN) is not None
      assert plan.maybe_fire(faultlab.CKPT_TORN) is None
      snap = registry.snapshot(prefix="faultlab/")
    assert snap["counter/faultlab/injected"] == 1.0
    assert snap["counter/faultlab/ckpt.torn"] == 1.0
    summary = plan.summary()
    assert summary == {"seed": 2, "injected": 1,
                       "by_point": {"ckpt.torn": 1},
                       "arrivals": {"ckpt.torn": 2}}
    assert plan.fired() == [{"point": "ckpt.torn", "key": None,
                             "arrival": 0, "spec": 0}]

  def test_activation_scoping(self):
    assert faultlab.maybe_fire(faultlab.TRAIN_NONFINITE) is None
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.TRAIN_NONFINITE, at=(0,))])
    with plan.activated():
      assert faultlab.active() is plan
      assert faultlab.maybe_fire(faultlab.TRAIN_NONFINITE) is not None
    assert faultlab.active() is None
    assert faultlab.maybe_fire(faultlab.TRAIN_NONFINITE) is None

  def test_from_config_round_trip(self):
    plan = faultlab.FaultPlan.from_config(
        {"seed": 9, "faults": [{"point": "serve.latency", "every": 3,
                                "arg": 25.0, "key": 1}]})
    assert plan.seed == 9
    assert plan.maybe_fire(faultlab.SERVE_LATENCY, key=1) is None
    assert plan.maybe_fire(faultlab.SERVE_LATENCY, key=1) is None
    spec = plan.maybe_fire(faultlab.SERVE_LATENCY, key=1)
    assert spec is not None and spec.arg == 25.0

  def test_backend_free_under_poisoned_platform(self):
    """faultlab + retry import, fire, and summarize without a usable
    jax backend (the `obs/` discipline)."""
    code = """
import random
from tensor2robot_tpu.obs import faultlab
from tensor2robot_tpu.utils import retry
plan = faultlab.FaultPlan(
    [faultlab.FaultSpec(point="serve.dispatch", at=(0,))], seed=1)
with plan.activated():
    assert faultlab.maybe_fire("serve.dispatch") is not None
policy = retry.RetryPolicy(name="p", max_attempts=2,
                           rng=random.Random(0), sleep=lambda s: None)
assert policy.call(lambda: "ok") == "ok"
print("GRAFTGUARD_POISONED_OK", plan.summary()["injected"])
"""
    env = {**os.environ, "PYTHONPATH": REPO_ROOT,
           "JAX_PLATFORMS": "graftguard_trap"}
    result = subprocess.run([sys.executable, "-c", code], env=env,
                            capture_output=True, text=True, timeout=120)
    assert result.returncode == 0, result.stderr
    assert "GRAFTGUARD_POISONED_OK 1" in result.stdout


# ---------------------------------------------------------------------------
# Checkpoint integrity: manifest / verify / quarantine / fallback.
# ---------------------------------------------------------------------------


def _state():
  return {"a": np.arange(16.0), "b": np.zeros((4,), np.float32)}


def _manager(directory, **kwargs):
  kwargs.setdefault("async_checkpointing", False)
  return checkpoints_lib.CheckpointManager(str(directory), **kwargs)


class TestCheckpointIntegrity:

  def test_manifest_written_at_save_and_verifies(self, tmp_path):
    with _manager(tmp_path) as manager:
      manager.save(1, _state())
      manager.wait_until_finished()
      path = os.path.join(str(tmp_path),
                          checkpoints_lib.MANIFEST_DIRNAME, "1.json")
      assert os.path.isfile(path)
      manifest = json.load(open(path))
      assert manifest["schema"] == checkpoints_lib.MANIFEST_SCHEMA
      assert manifest["files"]  # every checkpoint file listed
      assert manager.verify_step(1) is True

  def test_bitflip_detected_quarantined_and_fallback(self, tmp_path):
    with metrics_lib.isolated() as registry:
      with _manager(tmp_path) as manager:
        manager.save(1, _state())
        manager.save(2, _state())
        manager.wait_until_finished()
        checkpoints_lib._corrupt_step_for_faultlab(str(tmp_path), 2,
                                                   "bitflip")
        assert manager.verify_step(2) is False
        restored = manager.restore()
        assert manager.last_restored_step == 1
        assert "a" in restored or "params" in restored
        assert manager.latest_step() == 1  # quarantined step is GONE
      snap = registry.snapshot(prefix="ckpt/")
    assert snap["counter/ckpt/quarantined"] == 1.0
    assert snap["counter/ckpt/verify_failures"] >= 1.0
    qdir = os.path.join(str(tmp_path),
                        checkpoints_lib.QUARANTINE_DIRNAME)
    assert sorted(os.listdir(qdir)) == ["2"]

  def test_torn_latest_dir_falls_back_regression(self, tmp_path):
    """Satellite 1: `restore(step=None)` on a torn/partial latest step
    dir (no manifest — e.g. written by a crashed foreign process) must
    fall back to the newest intact step instead of raising."""
    with _manager(tmp_path) as manager:
      manager.save(1, _state())
      manager.wait_until_finished()
    # A truncated step dir appears as the latest step.
    torn = tmp_path / "5"
    torn.mkdir()
    (torn / "_CHECKPOINT_METADATA").write_text("{")
    with _manager(tmp_path) as manager:
      assert manager.latest_step() == 5
      restored = manager.restore()
      assert manager.last_restored_step == 1
      assert restored is not None
    qdir = os.path.join(str(tmp_path), checkpoints_lib.QUARANTINE_DIRNAME)
    assert "5" in os.listdir(qdir)

  def test_explicit_corrupt_step_raises(self, tmp_path):
    with _manager(tmp_path) as manager:
      manager.save(1, _state())
      manager.save(2, _state())
      manager.wait_until_finished()
    checkpoints_lib._corrupt_step_for_faultlab(str(tmp_path), 2, "torn")
    with _manager(tmp_path) as manager:
      with pytest.raises(checkpoints_lib.CheckpointCorruptionError):
        manager.restore(2)

  def test_explicit_missing_step_is_not_found_not_corruption(self,
                                                             tmp_path):
    with _manager(tmp_path) as manager:
      manager.save(1, _state())
      manager.wait_until_finished()
      with pytest.raises(FileNotFoundError):
        manager.restore(7)  # GC'd/never-saved step: not corruption

  def test_caller_error_on_legacy_step_never_quarantines(self, tmp_path):
    """A manifest-less (pre-graftguard) checkpoint whose restore fails
    on a CALLER error — mismatched abstract_state — must re-raise, not
    be displaced into quarantine: the bytes are structurally intact."""
    with _manager(tmp_path) as manager:
      manager.save(1, _state())
      manager.save(2, _state())
      manager.wait_until_finished()
    shutil.rmtree(os.path.join(str(tmp_path),
                               checkpoints_lib.MANIFEST_DIRNAME))
    wrong = {"different_tree": jax.ShapeDtypeStruct((3,), np.float32)}
    with _manager(tmp_path) as manager:
      assert manager.verify_step(2) is None  # no manifest to consult
      with pytest.raises(Exception) as excinfo:
        manager.restore(abstract_state=wrong)
      assert not isinstance(excinfo.value,
                            checkpoints_lib.CheckpointCorruptionError)
      assert manager.latest_step() == 2  # nothing displaced
    assert not os.path.isdir(os.path.join(
        str(tmp_path), checkpoints_lib.QUARANTINE_DIRNAME))

  def test_all_steps_corrupt_raises_corruption_error(self, tmp_path):
    with _manager(tmp_path) as manager:
      manager.save(1, _state())
      manager.wait_until_finished()
    checkpoints_lib._corrupt_step_for_faultlab(str(tmp_path), 1, "bitflip")
    with _manager(tmp_path) as manager:
      with pytest.raises(checkpoints_lib.CheckpointCorruptionError):
        manager.restore()

  def test_reader_manager_never_blesses_foreign_bytes(self, tmp_path):
    """A manager that only restores must not write manifests for step
    dirs it merely found — that would certify torn bytes as good."""
    with _manager(tmp_path) as manager:
      manager.save(1, _state())
      manager.wait_until_finished()
    os.remove(os.path.join(str(tmp_path),
                           checkpoints_lib.MANIFEST_DIRNAME, "1.json"))
    with _manager(tmp_path) as manager:
      manager.restore()  # works (restore guards it, not the manifest)
      assert manager.verify_step(1) is None  # still no manifest

  def test_faultlab_ckpt_points_corrupt_after_manifest(self, tmp_path):
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.CKPT_TORN, at=(1,))], seed=0)
    with plan.activated():
      with _manager(tmp_path) as manager:
        manager.save(1, _state())
        manager.save(2, _state())  # <- torn by the plan
        manager.wait_until_finished()
        assert manager.verify_step(1) is True
        assert manager.verify_step(2) is False  # manifest caught it
        manager.restore()
        assert manager.last_restored_step == 1

  def test_latest_verified_step_skips_failed(self, tmp_path):
    with _manager(tmp_path) as manager:
      manager.save(1, _state())
      manager.save(2, _state())
      manager.wait_until_finished()
      checkpoints_lib._corrupt_step_for_faultlab(str(tmp_path), 2,
                                                 "bitflip")
      assert manager.latest_verified_step() == 1

  def test_backup_checkpoint_retries_under_policy(self, tmp_path):
    with _manager(tmp_path / "ckpt") as manager:
      manager.save(3, _state())
      manager.wait_until_finished()
    backup = checkpoints_lib.backup_checkpoint(str(tmp_path / "ckpt"), 3)
    assert backup is not None and os.path.isdir(backup)
    # A nonexistent step exhausts the policy and returns None (the
    # reference's retrying backup-copy contract), never raises.
    assert checkpoints_lib.backup_checkpoint(
        str(tmp_path / "ckpt"), 99, max_attempts=2) is None


# ---------------------------------------------------------------------------
# Data-plane degradation (corrupt-record quota).
# ---------------------------------------------------------------------------


def _write_records(root, num_files=3, per_file=40):
  from tensor2robot_tpu import specs as specs_lib
  from tensor2robot_tpu.data import codec, parsing, tfrecord
  spec = specs_lib.SpecStruct({
      "pose": specs_lib.TensorSpec(shape=(4,), dtype=np.float32,
                                   name="pose"),
      "label": specs_lib.TensorSpec(shape=(1,), dtype=np.int64,
                                    name="label"),
  })
  rng = np.random.RandomState(0)
  for shard in range(num_files):
    path = os.path.join(root, f"rec-{shard:03d}.tfr")
    with tfrecord.RecordWriter(path) as writer:
      for _ in range(per_file):
        writer.write(codec.encode_example(
            {"pose": rng.randn(4).astype(np.float32),
             "label": rng.randint(0, 2, (1,), np.int64)}, spec))
  return os.path.join(root, "rec-*.tfr"), parsing.create_parse_fn(spec)


def _make_pipe(patterns, parse_fn, **kwargs):
  from tensor2robot_tpu.data import pipeline as pipeline_lib

  kwargs.setdefault("batch_size", 8)
  kwargs.setdefault("mode", "train")
  kwargs.setdefault("shuffle_buffer_size", 16)
  kwargs.setdefault("seed", 3)
  return pipeline_lib.RecordBatchPipeline(patterns, parse_fn, **kwargs)


class TestDataDegradation:

  def test_strict_mode_raises_on_corrupt_record(self, tmp_path):
    patterns, parse_fn = _write_records(str(tmp_path))
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.DATA_CORRUPT_RECORD, at=(1,))])
    pipe = _make_pipe(patterns, parse_fn, prefetch_size=0, overlap=False,
                      num_parallel_parses=1)
    with plan.activated():
      stream = iter(pipe)
      next(stream)
      with pytest.raises(Exception):
        for _ in range(4):
          next(stream)

  @pytest.mark.parametrize("overlap", [False, True])
  def test_corrupt_batches_skipped_under_quota(self, tmp_path, overlap):
    patterns, parse_fn = _write_records(str(tmp_path))
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.DATA_CORRUPT_RECORD, every=4,
                           count=2),
        faultlab.FaultSpec(point=faultlab.DATA_PREPROCESS, at=(9,),
                           count=1),
    ], seed=1)
    pipe = _make_pipe(patterns, parse_fn, overlap=overlap,
                      prefetch_size=2 if overlap else 0,
                      num_parallel_parses=2, max_corrupt_records=64)
    with plan.activated(), metrics_lib.isolated() as registry:
      stream = iter(pipe)
      batches = [next(stream) for _ in range(12)]
      if hasattr(stream, "close"):
        stream.close()
      snap = registry.snapshot(prefix="data/")
    assert len(batches) == 12
    assert all(b["features/pose"].shape == (8, 4) for b in batches)
    assert snap["counter/data/corrupt_batches_skipped"] == 3.0
    assert snap["counter/data/corrupt_records_skipped"] == 24.0

  def test_quota_exceeded_raises(self, tmp_path):
    patterns, parse_fn = _write_records(str(tmp_path))
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.DATA_CORRUPT_RECORD, every=2)])
    # Quota of one batch's worth: the second corrupt batch must raise.
    pipe = _make_pipe(patterns, parse_fn, overlap=False, prefetch_size=0,
                      num_parallel_parses=1, max_corrupt_records=8)
    with plan.activated():
      with pytest.raises(Exception):
        stream = iter(pipe)
        for _ in range(12):
          next(stream)

  def test_source_io_error_ends_epoch_and_continues(self, tmp_path):
    patterns, parse_fn = _write_records(str(tmp_path))
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.DATA_RECORD_IO, at=(20,),
                           count=1)])
    pipe = _make_pipe(patterns, parse_fn, overlap=False, prefetch_size=0,
                      num_parallel_parses=1, use_native_stager=False,
                      max_corrupt_records=64)
    with plan.activated(), metrics_lib.isolated() as registry:
      stream = iter(pipe)
      batches = [next(stream) for _ in range(20)]  # crosses the epoch cut
      snap = registry.snapshot(prefix="data/")
    assert len(batches) == 20
    assert snap["counter/data/source_io_errors"] == 1.0
    # An I/O flake is charged against the quota but is NOT corruption:
    # the corrupt-record counters must stay untouched.
    assert "counter/data/corrupt_records_skipped" not in snap
    assert "counter/data/corrupt_batches_skipped" not in snap

  def test_no_quota_no_behavior_change(self, tmp_path):
    """With the quota off and no plan active, the chain is untouched
    (same batches as ever)."""
    patterns, parse_fn = _write_records(str(tmp_path))
    a = list(__import__("itertools").islice(iter(_make_pipe(
        patterns, parse_fn, overlap=False, prefetch_size=0,
        num_parallel_parses=1, repeat=False)), 5))
    b = list(__import__("itertools").islice(iter(_make_pipe(
        patterns, parse_fn, overlap=False, prefetch_size=0,
        num_parallel_parses=1, repeat=False,
        max_corrupt_records=64)), 5))
    for batch_a, batch_b in zip(a, b):
      np.testing.assert_array_equal(batch_a["features/pose"],
                                    batch_b["features/pose"])


# ---------------------------------------------------------------------------
# Divergence rewind (train loop).
# ---------------------------------------------------------------------------


class TestDivergenceRewind:

  def _run(self, model_dir, plan, max_rewinds=2, steps=12):
    from tensor2robot_tpu import train_eval
    from tensor2robot_tpu.utils import mocks

    with plan.activated():
      return train_eval.train_eval_model(
          model=mocks.MockT2RModel(device_type="cpu"),
          model_dir=str(model_dir), mode="train",
          max_train_steps=steps, checkpoint_every_n_steps=4,
          log_every_n_steps=1, executable_cache_dir=None,
          max_rewinds=max_rewinds,
          input_generator_train=mocks.MockInputGenerator(batch_size=8))

  def test_nan_rewinds_to_verified_checkpoint_and_completes(self,
                                                            tmp_path):
    from tensor2robot_tpu.obs import runlog as runlog_lib

    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.TRAIN_NONFINITE, at=(6,),
                           count=1)], seed=0)
    metrics = self._run(tmp_path / "m", plan)
    assert np.isfinite(metrics["loss"])
    records = runlog_lib.load_records(
        os.path.join(str(tmp_path / "m"), "runs.jsonl"))
    extra = records[-1]["extra"]
    assert extra["final_step"] == 12
    assert extra["graftguard"]["rewinds"] == 1
    assert extra["graftguard"]["rewind_steps"] == [4]
    assert extra["faultlab"]["by_point"] == {"train.nonfinite": 1}
    assert extra["sentinel"]["by_kind"].get("nonfinite_metric") == 1
    # The fatal incident dumped a postmortem bundle BEFORE the rewind.
    from tensor2robot_tpu.obs import flightrec
    assert flightrec.find_bundles(str(tmp_path / "m"))

  def test_rewound_run_reaches_the_params_of_a_clean_resume(self,
                                                             tmp_path):
    """A rewind costs nothing but time: the run that hit a NaN at step
    6 and rewound to the verified step-4 checkpoint ends with the same
    parameters as a run that was simply resumed from that checkpoint
    (the rewind re-seeds the data stream as a resume does)."""
    from tensor2robot_tpu import train_eval

    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.TRAIN_NONFINITE, at=(6,),
                           count=1)], seed=0)
    self._run(tmp_path / "rewound", plan)
    source = tmp_path / "rewound" / train_eval.CHECKPOINT_DIRNAME / "4"
    clean = tmp_path / "clean" / train_eval.CHECKPOINT_DIRNAME
    clean.mkdir(parents=True)
    shutil.copytree(source, clean / "4")
    self._run(tmp_path / "clean", faultlab.FaultPlan([], seed=0))

    def final_params(model_dir):
      with checkpoints_lib.CheckpointManager(os.path.join(
          str(model_dir), train_eval.CHECKPOINT_DIRNAME)) as manager:
        restored = manager.restore()
        assert manager.last_restored_step == 12
        return restored["params"]

    rewound, resumed = (final_params(tmp_path / name)
                        for name in ("rewound", "clean"))
    assert (jax.tree_util.tree_structure(rewound)
            == jax.tree_util.tree_structure(resumed))
    for a, b in zip(jax.tree_util.tree_leaves(rewound),
                    jax.tree_util.tree_leaves(resumed)):
      np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

  def test_rewind_resaves_quarantined_step(self, tmp_path):
    """A checkpoint step quarantined by the rewind's restore walk must
    be SAVED AGAIN when the replay re-crosses it — the save-dedup set
    is pruned to what is actually on disk, otherwise every rewind
    leaves a permanent checkpoint gap behind it."""
    from tensor2robot_tpu import train_eval

    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.CKPT_BITFLIP, at=(1,), count=1),
        faultlab.FaultSpec(point=faultlab.TRAIN_NONFINITE, at=(9,),
                           count=1)], seed=0)
    self._run(tmp_path / "m", plan)
    ckpt_dir = os.path.join(str(tmp_path / "m"),
                            train_eval.CHECKPOINT_DIRNAME)
    qdir = os.path.join(ckpt_dir, checkpoints_lib.QUARANTINE_DIRNAME)
    assert "8" in os.listdir(qdir)  # the bit-flipped step-8 save
    assert os.path.isdir(os.path.join(ckpt_dir, "8"))  # re-saved on replay

  def test_rewind_budget_exhaustion_escalates(self, tmp_path):
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.TRAIN_NONFINITE, at=(6, 8),
                           count=2)], seed=0)
    with pytest.raises(RuntimeError, match="rewind"):
      self._run(tmp_path / "m", plan, max_rewinds=1)

  def test_no_verified_checkpoint_escalates(self, tmp_path):
    # NaN before the first checkpoint: nothing to rewind to.
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.TRAIN_NONFINITE, at=(1,),
                           count=1)], seed=0)
    with pytest.raises(RuntimeError, match="no verified checkpoint"):
      self._run(tmp_path / "m", plan)

  def test_recurring_nan_right_after_rewind_escalates(self, tmp_path):
    # Back-to-back NaN observations (arrivals 6 and 7) with NO finite
    # value in between: the second lands on the very first post-rewind
    # fetch. The sentinel's non-finite latch must be re-armed by the
    # rewind, or the recurrence is silently swallowed and the run
    # "succeeds" with NaNs instead of exhausting the rewind budget.
    plan = faultlab.FaultPlan([
        faultlab.FaultSpec(point=faultlab.TRAIN_NONFINITE, at=(6, 7),
                           count=2)], seed=0)
    with pytest.raises(RuntimeError, match="rewind budget exhausted"):
      self._run(tmp_path / "m", plan, max_rewinds=1)

  def test_auto_resume_with_torn_newest_step_falls_back(self, tmp_path):
    """A crash mid-save leaves a torn newest step dir; the restart's
    auto-resume must ride the verified walk (quarantine + fallback to
    the newest intact step) instead of raising out of an explicit
    `restore(latest_step())`."""
    from tensor2robot_tpu import train_eval
    from tensor2robot_tpu.utils import mocks

    model_dir = tmp_path / "m"

    def _go(steps):
      return train_eval.train_eval_model(
          model=mocks.MockT2RModel(device_type="cpu"),
          model_dir=str(model_dir), mode="train", max_train_steps=steps,
          checkpoint_every_n_steps=4, log_every_n_steps=4,
          executable_cache_dir=None,
          input_generator_train=mocks.MockInputGenerator(batch_size=8))

    _go(8)  # checkpoints at steps 4 and 8
    ckpt_dir = os.path.join(str(model_dir), train_eval.CHECKPOINT_DIRNAME)
    checkpoints_lib._corrupt_step_for_faultlab(ckpt_dir, 8, "torn")
    metrics = _go(12)  # resume: 8 is torn -> quarantine, restart from 4
    assert np.isfinite(metrics["loss"])
    qdir = os.path.join(ckpt_dir, checkpoints_lib.QUARANTINE_DIRNAME)
    assert "8" in os.listdir(qdir)


# ---------------------------------------------------------------------------
# graftlint bare-retry-rule
# ---------------------------------------------------------------------------


_BAD_RETRY = """
import time

def fetch(source):
  for attempt in range(5):
    try:
      return source.read()
    except Exception:
      pass
    time.sleep(0.5)
"""

_POLL_ONLY = """
import time

def wait(flag):
  while not flag.is_set():
    time.sleep(0.005)
"""

_POLICY_PACED = """
import time

def fetch(source, policy):
  for delay in policy.delays():
    try:
      return source.read()
    except Exception:
      pass
    time.sleep(policy.backoff_s(0))
"""


class TestBareRetryRule:

  def _check(self, tmp_path, subdir, source):
    target = tmp_path / subdir
    target.mkdir(parents=True, exist_ok=True)
    path = target / "mod.py"
    path.write_text(source)
    return retry_check.check_python_file(str(path))

  def test_flags_constant_sleep_retry_in_serving(self, tmp_path):
    findings = self._check(tmp_path, "serving", _BAD_RETRY)
    assert len(findings) == 1
    assert findings[0].rule == "bare-retry-rule"
    assert "RetryPolicy" in findings[0].message

  def test_flags_in_data_not_elsewhere(self, tmp_path):
    assert self._check(tmp_path, "data", _BAD_RETRY)
    assert not self._check(tmp_path, "models", _BAD_RETRY)

  def test_poll_loop_not_flagged(self, tmp_path):
    assert not self._check(tmp_path, "serving", _POLL_ONLY)

  def test_policy_paced_sleep_not_flagged(self, tmp_path):
    """`sleep(policy.backoff_s(...))` is a computed delay — the whole
    point of the migration — and must not be flagged."""
    assert not self._check(tmp_path, "serving", _POLICY_PACED)

  def test_suppression(self, tmp_path):
    suppressed = _BAD_RETRY.replace(
        "for attempt in range(5):",
        "for attempt in range(5):  # graftlint: disable=bare-retry-rule")
    assert not self._check(tmp_path, "serving", suppressed)

  def test_repo_hot_paths_pinned_clean(self):
    for subdir in ("tensor2robot_tpu/serving", "tensor2robot_tpu/data"):
      root = os.path.join(REPO_ROOT, subdir)
      for name in sorted(os.listdir(root)):
        if name.endswith(".py"):
          findings = retry_check.check_python_file(
              os.path.join(root, name))
          assert not findings, findings
