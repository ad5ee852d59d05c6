"""graftserve tests: dynamic micro-batching + shape-bucketed executables.

Pins the ISSUE 5 serving semantics:
* bucket cache compiles exactly `len(buckets)` times and NEVER recompiles
  across a randomized request-size sweep (the zero-recompile guarantee);
* per-request output splitting is exact vs unbatched predict;
* deadline expiry SHEDS a stale request (never serves it) and feeds the
  existing `serve/slo_breaches` counter;
* partial batches flush at `max_delay_ms`;
* queue-depth admission control sheds instead of queueing unboundedly;
* `close()` JOINS the worker (the same discipline as
  `parallel/mesh.DevicePrefetcher.close`) and fails queued requests;
* the whole `serving/` package imports AND a batcher runs end-to-end
  under a poisoned JAX_PLATFORMS (tier-1 backend-free trap).
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
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.serving import engine as engine_lib
from tensor2robot_tpu.serving import loadgen

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Bucket ladder.
# ---------------------------------------------------------------------------


class TestBucketLadder:

  def test_doubling_ladder(self):
    assert engine_lib.bucket_ladder(8) == [1, 2, 4, 8]
    assert engine_lib.bucket_ladder(1) == [1]

  def test_non_power_of_two_max_is_top_rung(self):
    assert engine_lib.bucket_ladder(12) == [1, 2, 4, 8, 12]

  def test_invalid_max_raises(self):
    with pytest.raises(ValueError):
      engine_lib.bucket_ladder(0)


# ---------------------------------------------------------------------------
# BucketedEngine over a real (mock-model) predictor.
# ---------------------------------------------------------------------------


def _mock_predictor():
  from tensor2robot_tpu.predictors import predictors as predictors_lib
  from tensor2robot_tpu.utils import mocks

  predictor = predictors_lib.CheckpointPredictor(
      model=mocks.MockT2RModel(device_type="cpu"),
      model_dir="/nonexistent")
  predictor.init_randomly()
  return predictor


@pytest.fixture(scope="module")
def warmed_engine():
  predictor = _mock_predictor()
  with metrics_lib.isolated():
    engine = serving.BucketedEngine(predictor=predictor, max_batch_size=8)
    engine.warmup()
  return predictor, engine


class TestBucketedEngine:

  def test_warmup_compiles_one_executable_per_bucket(self):
    predictor = _mock_predictor()
    with metrics_lib.isolated() as registry:
      engine = serving.BucketedEngine(predictor=predictor,
                                      max_batch_size=8)
      engine.warmup()
      assert engine.buckets == [1, 2, 4, 8]
      assert engine.compile_count == 4
      snap = registry.snapshot()
    assert snap["counter/serve/engine/compiles"] == 4.0
    # compile telemetry flows through the graftscope-xray path
    records = engine.compile_records
    assert len(records) == 4
    for record in records:
      assert record["compile_s"] >= 0.0
      assert "bucket" in record["name"]

  def test_warmup_is_idempotent(self, warmed_engine):
    _, engine = warmed_engine
    count = engine.compile_count
    engine.warmup()
    assert engine.compile_count == count

  def test_zero_recompiles_across_randomized_size_sweep(self,
                                                        warmed_engine):
    """THE acceptance pin: after warmup, a randomized request-size sweep
    (padding + oversize chunking included) never compiles again, and
    every output matches the unbatched predict row-for-row."""
    predictor, engine = warmed_engine
    rng = np.random.RandomState(0)
    with metrics_lib.isolated() as registry:
      for _ in range(40):
        rows = int(rng.randint(1, 20))  # crosses the top bucket too
        x = rng.randn(rows, 3).astype(np.float32)
        direct = predictor.predict({"x": x})
        bucketed = engine.predict({"x": x})
        assert bucketed["prediction"].shape == direct["prediction"].shape
        np.testing.assert_allclose(bucketed["prediction"],
                                   direct["prediction"], rtol=1e-5)
      snap = registry.snapshot()
    assert engine.compile_count == len(engine.buckets)
    # No dispatch ever fell back to the (re-tracing) plain jit, and no
    # new executables were compiled inside the sweep's registry scope.
    assert snap.get("counter/serve/engine/exec_fallbacks", 0.0) == 0.0
    assert snap.get("counter/serve/engine/compiles", 0.0) == 0.0
    assert snap.get("counter/serve/engine/padded_rows", 0.0) > 0.0

  def test_restore_hot_swap_serves_new_params_without_recompiling(
      self, warmed_engine):
    import jax

    predictor, engine = warmed_engine
    x = np.linspace(-1.0, 1.0, 9, dtype=np.float32).reshape(3, 3)
    before = engine.predict({"x": x})["prediction"]
    # A restore() hot swap: same shapes/dtypes, different values.
    old_state = predictor._state
    try:
      bump = lambda t: (jax.tree_util.tree_map(  # noqa: E731
          lambda p: p + 0.25, t) if t is not None else None)
      predictor._state = old_state.replace(
          params=bump(old_state.params),
          ema_params=bump(old_state.ema_params))
      after = engine.predict({"x": x})["prediction"]
      assert engine.compile_count == len(engine.buckets)
      assert not np.allclose(before, after), "state swap not picked up"
      np.testing.assert_allclose(
          after, predictor.predict({"x": x})["prediction"], rtol=1e-5)
    finally:
      predictor._state = old_state

  def test_non_batched_outputs_pass_through_unsliced(self):
    """An output whose leading dim is NOT the batch axis (a fixed-size
    diagnostic) must pass through padding/masking AND oversize chunking
    intact — only outputs shaped like the padded batch get sliced."""
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu.predictors import predictors as predictors_lib
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    @jax.jit
    def fn(state, features):
      x = features["x"]
      return {"pred": x * 2.0,
              "diag": jnp.arange(7.0),        # fixed-size, non-batched
              "scalar": jnp.float32(3.0)}

    class _BundlePredictor:
      def serving_bundle(self):
        return predictors_lib.ServingBundle(
            jit_predict=fn, get_state=lambda: {},
            preprocess=lambda f: f,
            feature_spec=SpecStruct({"x": TensorSpec(shape=(2,))}))

    engine = serving.BucketedEngine(predictor=_BundlePredictor(),
                                    max_batch_size=4)
    engine.warmup()
    for rows in (3, 11):  # padded bucket + oversize chunked
      x = np.arange(rows * 2, dtype=np.float32).reshape(rows, 2)
      out = engine.predict({"x": x})
      np.testing.assert_array_equal(out["pred"], x * 2.0)
      np.testing.assert_array_equal(out["diag"], np.arange(7.0))
      assert out["scalar"] == np.float32(3.0)

  def test_explicit_buckets(self):
    predictor = _mock_predictor()
    engine = serving.BucketedEngine(predictor=predictor, buckets=[2, 6])
    engine.warmup()
    assert engine.buckets == [2, 6]
    assert engine.compile_count == 2
    out = predictor.predict({"x": np.zeros((5, 3), np.float32)})
    padded = engine.predict({"x": np.zeros((5, 3), np.float32)})
    np.testing.assert_allclose(padded["prediction"], out["prediction"],
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# MicroBatcher semantics over a pure-numpy backend (no jax involved).
# ---------------------------------------------------------------------------


class _NumpyBackend:
  """Row-wise deterministic function with dispatch accounting."""

  def __init__(self, delay_s: float = 0.0):
    self.delay_s = delay_s
    self.batches = []  # list of row counts per dispatch
    self.seen_rows = []  # first column of every served row

  def __call__(self, features):
    x = np.asarray(features["x"])
    self.batches.append(x.shape[0])
    self.seen_rows.extend(x[:, 0].tolist())
    if self.delay_s:
      time.sleep(self.delay_s)
    return {"out": x * 2.0, "scalar": np.float32(7.0)}


class TestMicroBatcherSemantics:

  def test_concurrent_requests_coalesce_and_split_exactly(self):
    backend = _NumpyBackend()
    with metrics_lib.isolated() as registry, \
        serving.MicroBatcher(backend=backend, max_batch_size=8,
                             max_delay_ms=20.0) as batcher:
      results = {}

      def client(i):
        x = np.array([[float(i), -float(i)]], np.float32)
        results[i] = batcher.predict({"x": x})

      threads = [threading.Thread(target=client, args=(i,))
                 for i in range(16)]
      for t in threads:
        t.start()
      for t in threads:
        t.join()
      snap = registry.snapshot()
    # Split exactness: each caller got exactly its own doubled row, plus
    # the replicated non-batch scalar.
    for i, out in results.items():
      np.testing.assert_array_equal(
          out["out"], np.array([[2.0 * i, -2.0 * i]], np.float32))
      assert out["scalar"] == np.float32(7.0)
    # Coalescing happened: strictly fewer dispatches than requests and
    # at least one multi-row batch.
    assert len(backend.batches) < 16
    assert max(backend.batches) > 1
    assert sum(backend.batches) == 16
    assert snap["counter/serve/batcher/requests"] == 16.0
    assert snap["counter/serve/batcher/batches"] == len(backend.batches)

  def test_partial_batch_flushes_at_max_delay(self):
    backend = _NumpyBackend()
    with serving.MicroBatcher(backend=backend, max_batch_size=8,
                              max_delay_ms=30.0) as batcher:
      start = time.monotonic()
      out = batcher.predict({"x": np.ones((1, 2), np.float32)})
      elapsed = time.monotonic() - start
    np.testing.assert_array_equal(out["out"],
                                  np.full((1, 2), 2.0, np.float32))
    assert backend.batches == [1]  # served alone, not starved forever
    # Flushed by the delay policy: on the order of max_delay_ms, with
    # generous slack for a loaded CI host.
    assert elapsed < 5.0

  def test_deadline_expiry_sheds_and_feeds_slo_counter(self):
    backend = _NumpyBackend(delay_s=0.25)
    with metrics_lib.isolated() as registry, \
        serving.MicroBatcher(backend=backend, max_batch_size=2,
                             max_delay_ms=1.0) as batcher:
      # Occupy the worker with a slow dispatch...
      blocker = threading.Thread(
          target=lambda: batcher.predict(
              {"x": np.zeros((2, 2), np.float32)}))
      blocker.start()
      time.sleep(0.05)  # worker is now inside the 250 ms dispatch
      # ...then enqueue a request whose deadline expires meanwhile.
      with pytest.raises(serving.DeadlineError):
        batcher.predict({"x": np.full((1, 2), 5.0, np.float32)},
                        deadline_ms=10.0)
      blocker.join()
      snap = registry.snapshot()
    # The stale request was shed, never served: its value never reached
    # the backend.
    assert 5.0 not in backend.seen_rows
    assert snap["counter/serve/batcher/shed_deadline"] == 1.0
    assert snap["counter/serve/slo_breaches"] == 1.0
    assert snap["hist/serve/slo_breach_ms/count"] == 1.0

  def test_queue_full_sheds_immediately(self):
    backend = _NumpyBackend(delay_s=0.3)
    with metrics_lib.isolated() as registry, \
        serving.MicroBatcher(backend=backend, max_batch_size=1,
                             max_delay_ms=1.0, max_queue=2) as batcher:
      threads = []
      errors = []

      def client(i):
        try:
          batcher.predict({"x": np.full((1, 2), float(i), np.float32)})
        except serving.ShedError as e:
          errors.append(e)

      for i in range(8):
        threads.append(threading.Thread(target=client, args=(i,)))
        threads[-1].start()
      for t in threads:
        t.join()
      snap = registry.snapshot()
    assert errors, "a bounded queue under overload must shed"
    assert snap["counter/serve/batcher/shed_queue_full"] == len(errors)

  def test_oversize_request_bypasses_coalescing(self):
    backend = _NumpyBackend()
    with metrics_lib.isolated() as registry, \
        serving.MicroBatcher(backend=backend, max_batch_size=4) as batcher:
      x = np.arange(24, dtype=np.float32).reshape(12, 2)
      out = batcher.predict({"x": x})
      snap = registry.snapshot()
    np.testing.assert_array_equal(out["out"], x * 2.0)
    assert backend.batches == [12]
    assert snap["counter/serve/batcher/bypass"] == 1.0

  def test_inconsistent_leading_dims_rejected(self):
    backend = _NumpyBackend()
    with serving.MicroBatcher(backend=backend) as batcher:
      with pytest.raises(ValueError, match="inconsistent leading dims"):
        batcher.predict({"x": np.zeros((2, 2), np.float32),
                         "y": np.zeros((3, 2), np.float32)})

  def test_backend_error_propagates_to_every_caller(self):
    def broken(features):
      raise RuntimeError("backend exploded")

    with serving.MicroBatcher(backend=broken, max_delay_ms=5.0) as batcher:
      with pytest.raises(RuntimeError, match="backend exploded"):
        batcher.predict({"x": np.zeros((1, 2), np.float32)})
      # The worker survives a backend error and serves the next request.
      with pytest.raises(RuntimeError, match="backend exploded"):
        batcher.predict({"x": np.zeros((1, 2), np.float32)})


class TestMicroBatcherShutdown:
  """The worker is JOINED, never abandoned."""

  def test_close_joins_worker_and_rejects_new_requests(self):
    backend = _NumpyBackend()
    batcher = serving.MicroBatcher(backend=backend)
    batcher.predict({"x": np.zeros((1, 2), np.float32)})
    batcher.close()
    assert not batcher._worker.is_alive(), "worker must be joined"
    with pytest.raises(serving.ShutdownError):
      batcher.predict({"x": np.zeros((1, 2), np.float32)})
    batcher.close()  # idempotent

  def test_close_waits_out_inflight_dispatch(self):
    """A close() racing a dispatch waits for the device call to finish
    (a thread is never abandoned mid-transfer);
    the in-flight request still completes successfully."""
    backend = _NumpyBackend(delay_s=0.4)
    batcher = serving.MicroBatcher(backend=backend, max_delay_ms=1.0)
    result = {}

    def client():
      result["out"] = batcher.predict(
          {"x": np.ones((1, 2), np.float32)})

    thread = threading.Thread(target=client)
    thread.start()
    time.sleep(0.1)  # worker is mid-dispatch now
    assert batcher._phase[0] == "dispatch"
    batcher.close()
    assert not batcher._worker.is_alive()
    thread.join()
    np.testing.assert_array_equal(result["out"]["out"],
                                  np.full((1, 2), 2.0, np.float32))

  def test_close_fails_queued_requests_with_shutdown_error(self):
    backend = _NumpyBackend(delay_s=0.3)
    batcher = serving.MicroBatcher(backend=backend, max_batch_size=1,
                                   max_delay_ms=1.0, max_queue=16)
    outcomes = []

    def client(i):
      try:
        batcher.predict({"x": np.full((1, 2), float(i), np.float32)})
        outcomes.append("served")
      except serving.ShutdownError:
        outcomes.append("shutdown")

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(6)]
    for t in threads:
      t.start()
    time.sleep(0.1)  # first dispatch in flight, the rest queued
    batcher.close()
    for t in threads:
      t.join()
    assert not batcher._worker.is_alive()
    assert "shutdown" in outcomes, "queued requests must fail, not hang"
    assert "served" in outcomes, "the in-flight request must complete"


# ---------------------------------------------------------------------------
# Load generator.
# ---------------------------------------------------------------------------


class TestLoadgen:

  def test_run_load_counts_and_errors(self):
    calls = []

    def predict(features):
      calls.append(1)
      if len(calls) == 3:
        raise RuntimeError("transient")
      return {"out": features["x"]}

    result = loadgen.run_load(predict,
                              lambda i: {"x": np.zeros((1, 1))},
                              concurrency=2, requests_per_thread=5)
    assert result["requests"] == 10
    assert result["ok"] == 9
    assert result["errors"] == {"RuntimeError": 1}
    assert result["qps"] > 0

  def test_latency_percentiles_from_registry(self):
    with metrics_lib.isolated():
      hist = metrics_lib.histogram("serve/request_ms")
      for v in [1.0, 2.0, 3.0, 100.0]:
        hist.record(v)
      stats = loadgen.latency_percentiles()
      assert stats["count"] == 4.0
      assert stats["p50"] == pytest.approx(2.5)
      assert stats["p99"] <= 100.0
    assert loadgen.latency_percentiles("serve/empty") == {}


# ---------------------------------------------------------------------------
# Policy integration: the serving stack in front of a policy's predictor.
# ---------------------------------------------------------------------------


class TestPolicyIntegration:

  def test_policy_restore_warms_serving_stack_and_serves(self, tmp_path):
    from tensor2robot_tpu import train_eval
    from tensor2robot_tpu.policies import policies as policies_lib
    from tensor2robot_tpu.predictors import predictors as predictors_lib
    from tensor2robot_tpu.utils import mocks

    model_dir = str(tmp_path / "m")
    train_eval.train_eval_model(
        model=mocks.MockT2RModel(device_type="cpu"),
        model_dir=model_dir, mode="train", max_train_steps=5,
        checkpoint_every_n_steps=5,
        input_generator_train=mocks.MockInputGenerator(batch_size=8),
        log_every_n_steps=5)
    predictor = predictors_lib.CheckpointPredictor(
        model=mocks.MockT2RModel(device_type="cpu"), model_dir=model_dir)
    engine = serving.BucketedEngine(predictor=predictor, max_batch_size=4)
    with serving.MicroBatcher(backend=engine, max_delay_ms=2.0) as batcher:
      policy = policies_lib.RegressionPolicy(predictor=batcher,
                                             action_key="prediction")
      assert policy.restore()
      # restore() warmed the bucket cache BEFORE the first action.
      assert engine.compile_count == len(engine.buckets)
      assert policy.global_step == 5
      action = policy.select_action({"x": np.zeros(3, np.float32)})
      assert action.shape == (1,)
      assert engine.compile_count == len(engine.buckets)


# ---------------------------------------------------------------------------
# Tier-1: serving/ is backend-free (poisoned-platform trap).
# ---------------------------------------------------------------------------


def test_serving_imports_and_batcher_run_backend_free():
  """`tensor2robot_tpu.serving` must import — and a MicroBatcher must
  coalesce, serve, shed and JOIN its worker — without initializing any
  JAX backend (same two-layer proof as the obs/analysis suites:
  poisoned JAX_PLATFORMS + empty backend cache). The engine only
  touches jax inside warmup/predict, which never run here."""
  code = """
import threading
import numpy as np
from tensor2robot_tpu import serving
from tensor2robot_tpu.serving import batcher, engine, loadgen

seen = []
def backend(features):
    x = np.asarray(features["x"])
    seen.append(x.shape[0])
    return {"out": x + 1.0}

b = serving.MicroBatcher(backend=backend, max_batch_size=4,
                         max_delay_ms=5.0)
results = {}
def client(i):
    results[i] = b.predict({"x": np.full((1, 2), float(i))})
threads = [threading.Thread(target=client, args=(i,)) for i in range(8)]
for t in threads: t.start()
for t in threads: t.join()
assert sum(seen) == 8, seen
for i, out in results.items():
    assert float(out["out"][0, 0]) == i + 1.0
stats = loadgen.run_load(b.predict, lambda i: {"x": np.zeros((1, 2))},
                         concurrency=2, requests_per_thread=4)
assert stats["ok"] == 8, stats
b.close()
assert not b._worker.is_alive()
assert engine.bucket_ladder(8) == [1, 2, 4, 8]
from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("SERVING_NO_BACKEND_OK")
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "graftserve_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", code],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "SERVING_NO_BACKEND_OK" in result.stdout
