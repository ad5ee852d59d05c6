"""Stateful serving sessions: on-device decode caches (ISSUE 11).

Pins the session-serving semantics:
* tick-by-tick decode through the model seam AND through a warmed
  `SessionEngine` matches the stateless full-prefix forward at every
  step — attention (KV append) and LSTM (carry) paths, mixed-progress
  continuous batching and padded partial buckets included;
* zero recompiles after warmup across open/step/close/evict churn
  (`compile_count` pinned at the warmed ladder count, no fallbacks);
* eviction under slot pressure (LRU victim, in-flight sessions immune,
  evicted session's next step raises; `admission='shed'` refuses);
* `close_session()` with in-flight steps waits the dispatch out
  (the join discipline);
* `restore()` param hot-swap mid-episode keeps session state coherent;
* graftcache warm start loads the decode ladder with zero compiles;
* the open-loop session load shape (`loadgen.run_session_load`)
  exercises admission/eviction and counts outcomes;
* graftlint `session-state-leak` flags dropped decode state and host
  fetches of session state, repo pinned clean;
* session bookkeeping + lint run under a poisoned JAX_PLATFORMS
  (tier-1 backend-free trap).
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from tensor2robot_tpu import serving
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.serving import loadgen

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEQ_KW = dict(obs_size=4, action_size=2, sequence_length=6,
              hidden_size=8, num_blocks=2, num_heads=2)
LSTM_KW = dict(obs_size=4, action_size=2, sequence_length=6,
               hidden_size=8)


def _make_predictor(model_cls=None, **kw):
  from tensor2robot_tpu.models import sequence_model
  from tensor2robot_tpu.predictors import predictors as predictors_lib

  model_cls = model_cls or sequence_model.SequenceRegressionModel
  predictor = predictors_lib.CheckpointPredictor(
      model=model_cls(**kw), model_dir="/nonexistent")
  predictor.init_randomly()
  return predictor


@pytest.fixture(scope="module")
def seq_predictor():
  return _make_predictor(**SEQ_KW)


@pytest.fixture(scope="module")
def warmed_engine(seq_predictor):
  with metrics_lib.isolated():
    engine = serving.SessionEngine(predictor=seq_predictor,
                                   max_sessions=6, max_tick_batch=4)
    engine.warmup()
  return engine


def _obs_seq(batch, seq_len, obs_size, seed=0):
  return np.random.RandomState(seed).randn(
      batch, seq_len, obs_size).astype(np.float32)


# ---------------------------------------------------------------------------
# Decode parity: the model seam, both recurrent families.
# ---------------------------------------------------------------------------


class TestDecodeSeamParity:

  @pytest.mark.parametrize("family", ["attention", "lstm"])
  def test_tick_by_tick_matches_full_prefix(self, family):
    """THE semantic-parity acceptance: a session advanced one tick at a
    time through the pure decode seam reproduces the stateless
    full-prefix forward at EVERY step, same seed — KV-append (causal
    attention) and carry (LSTM) paths."""
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu.models import sequence_model

    if family == "attention":
      predictor = _make_predictor(**SEQ_KW)
      seq_len, obs_size = SEQ_KW["sequence_length"], SEQ_KW["obs_size"]
    else:
      predictor = _make_predictor(sequence_model.LSTMRegressionModel,
                                  **LSTM_KW)
      seq_len, obs_size = LSTM_KW["sequence_length"], LSTM_KW["obs_size"]
    obs = _obs_seq(2, seq_len, obs_size, seed=3)
    full = predictor.predict({"observation": obs})["action"]  # [2, T, A]
    bundle = predictor.decode_bundle()
    state = bundle.get_state()
    sess = jax.tree_util.tree_map(jnp.asarray,
                                  bundle.init_session_state(2))
    for t in range(seq_len):
      sess, out = bundle.decode_fn(state, sess,
                                   {"observation": jnp.asarray(obs[:, t])})
      np.testing.assert_allclose(np.asarray(out["action"]), full[:, t],
                                 rtol=1e-5, atol=1e-6)
    # The per-session tick index advanced with the episode.
    assert np.asarray(sess["index"]).tolist() == [seq_len, seq_len]

  def test_unsupported_model_raises(self):
    from tensor2robot_tpu.predictors import predictors as predictors_lib
    from tensor2robot_tpu.utils import mocks

    predictor = predictors_lib.CheckpointPredictor(
        model=mocks.MockT2RModel(device_type="cpu"),
        model_dir="/nonexistent")
    predictor.init_randomly()
    with pytest.raises(ValueError, match="session-decode seam"):
      predictor.decode_bundle()


# ---------------------------------------------------------------------------
# SessionEngine: parity, continuous batching, zero recompiles.
# ---------------------------------------------------------------------------


class TestSessionEngine:

  def test_engine_episode_matches_stateless(self, seq_predictor,
                                            warmed_engine):
    obs = _obs_seq(1, SEQ_KW["sequence_length"], SEQ_KW["obs_size"],
                   seed=11)
    full = seq_predictor.predict({"observation": obs})["action"]
    sid = warmed_engine.open()
    for t in range(SEQ_KW["sequence_length"]):
      out = warmed_engine.step(sid, {"observation": obs[0, t]})
      np.testing.assert_allclose(out["action"], full[0, t],
                                 rtol=1e-5, atol=1e-6)
    assert warmed_engine.session_ticks(sid) == SEQ_KW["sequence_length"]
    warmed_engine.close_session(sid)

  def test_mixed_progress_continuous_batching(self, seq_predictor,
                                              warmed_engine):
    """Sessions at DIFFERENT episode ticks share one padded dispatch
    (the continuous-batching shape) and each still matches its own
    stateless forward — the per-session index + masked scatter are what
    make this work."""
    seq_len, obs_size = SEQ_KW["sequence_length"], SEQ_KW["obs_size"]
    obs_a = _obs_seq(1, seq_len, obs_size, seed=21)
    obs_b = _obs_seq(1, seq_len, obs_size, seed=22)
    obs_c = _obs_seq(1, seq_len, obs_size, seed=23)
    full = {
        name: seq_predictor.predict({"observation": o})["action"]
        for name, o in (("a", obs_a), ("b", obs_b), ("c", obs_c))}
    sid_a = warmed_engine.open()
    sid_b = warmed_engine.open()
    # Stagger: a gets a 2-tick head start, then a+b together (b behind
    # by 2), then a 3-way partial bucket with a fresh c (pad lane 4).
    for t in range(2):
      warmed_engine.step(sid_a, {"observation": obs_a[0, t]})
    for t in range(2):
      outs = warmed_engine.step_many([
          (sid_a, {"observation": obs_a[0, 2 + t]}),
          (sid_b, {"observation": obs_b[0, t]})])
      np.testing.assert_allclose(outs[0]["action"], full["a"][0, 2 + t],
                                 rtol=1e-5, atol=1e-6)
      np.testing.assert_allclose(outs[1]["action"], full["b"][0, t],
                                 rtol=1e-5, atol=1e-6)
    sid_c = warmed_engine.open()
    outs = warmed_engine.step_many([
        (sid_a, {"observation": obs_a[0, 4]}),
        (sid_b, {"observation": obs_b[0, 2]}),
        (sid_c, {"observation": obs_c[0, 0]})])
    np.testing.assert_allclose(outs[0]["action"], full["a"][0, 4],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[1]["action"], full["b"][0, 2],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(outs[2]["action"], full["c"][0, 0],
                               rtol=1e-5, atol=1e-6)
    for sid in (sid_a, sid_b, sid_c):
      warmed_engine.close_session(sid)

  def test_zero_recompiles_across_session_churn(self, warmed_engine):
    """THE zero-recompile acceptance: compile_count stays at the warmed
    ladder count (len(buckets) + 1 reset executable) and nothing falls
    back across an open/step/close/evict sweep over every bucket."""
    assert warmed_engine.compile_count == len(warmed_engine.buckets) + 1
    count = warmed_engine.compile_count
    obs = _obs_seq(1, SEQ_KW["sequence_length"], SEQ_KW["obs_size"])
    with metrics_lib.isolated() as registry:
      rng = np.random.RandomState(0)
      for _ in range(6):
        sids = [warmed_engine.open()
                for _ in range(int(rng.randint(1, 7)))]
        for group_start in range(0, len(sids), 4):
          group = sids[group_start:group_start + 4]
          warmed_engine.step_many(
              [(s, {"observation": obs[0, 0]}) for s in group])
        for sid in sids:
          warmed_engine.close_session(sid)
      snap = registry.snapshot()
    assert warmed_engine.compile_count == count
    assert snap.get("counter/serve/session/exec_fallbacks", 0.0) == 0.0
    assert snap.get("counter/serve/session/compiles", 0.0) == 0.0

  def test_step_validates_batch_shape(self, warmed_engine):
    sid = warmed_engine.open()
    with pytest.raises(ValueError, match="distinct"):
      warmed_engine.step_many([
          (sid, {"observation": np.zeros(4, np.float32)}),
          (sid, {"observation": np.zeros(4, np.float32)})])
    with pytest.raises(ValueError, match="max_tick_batch"):
      warmed_engine.step_many([
          (sid, {"observation": np.zeros(4, np.float32)})] * 5)
    warmed_engine.close_session(sid)

  def test_horizon_guard_raises_instead_of_silent_drop(self,
                                                       warmed_engine):
    """A tick past the KV capacity would be an out-of-bounds scatter
    XLA silently DROPS (write vanishes, mask all-true, outputs quietly
    wrong) — the engine must raise loudly at the horizon instead."""
    obs = np.zeros(4, np.float32)
    sid = warmed_engine.open()
    for _ in range(SEQ_KW["sequence_length"]):
      warmed_engine.step(sid, {"observation": obs})
    with pytest.raises(serving.SessionHorizonError, match="horizon"):
      warmed_engine.step(sid, {"observation": obs})
    warmed_engine.close_session(sid)

  def test_concurrent_steps_of_one_session_rejected(self, seq_predictor):
    """A second dispatch of an in-flight session must be refused —
    membership in the in-flight set is not a count, so letting it
    through would race the arena scatter and un-protect close()."""
    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=seq_predictor,
                                     max_sessions=2, max_tick_batch=1,
                                     buckets=[1])
      engine.warmup()
      sid = engine.open()
      obs = np.zeros(4, np.float32)
      release = threading.Event()
      in_dispatch = threading.Event()
      real_get_state = engine._bundle.get_state

      def slow_get_state():
        in_dispatch.set()
        release.wait(timeout=10.0)
        return real_get_state()

      engine._bundle = engine._bundle._replace(get_state=slow_get_state)
      thread = threading.Thread(
          target=lambda: engine.step(sid, {"observation": obs}))
      thread.start()
      assert in_dispatch.wait(timeout=10.0)
      with pytest.raises(serving.SessionError, match="in flight"):
        engine.step(sid, {"observation": obs})
      release.set()
      thread.join(timeout=30.0)
      assert not thread.is_alive()
      engine.step(sid, {"observation": obs})  # serialized tick is fine
      engine.close_session(sid)

  def test_failed_open_reset_leaves_no_ghost_session(self,
                                                     seq_predictor):
    """If the slot-reset dispatch fails, the half-opened session must
    be deregistered (slot freed) — a ghost session under
    admission='shed' would shed every later open() forever."""
    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=seq_predictor,
                                     max_sessions=1, max_tick_batch=1,
                                     buckets=[1], admission="shed")
      engine.warmup()

      def broken_reset(*args):
        raise RuntimeError("reset dispatch failed")

      good_compiled, good_jit = engine._reset_compiled, engine._reset_jit
      engine._reset_compiled, engine._reset_jit = None, broken_reset
      with pytest.raises(RuntimeError, match="reset dispatch failed"):
        engine.open()
      assert engine.active_sessions == 0
      engine._reset_compiled, engine._reset_jit = good_compiled, good_jit
      sid = engine.open()  # the slot is free again, not leaked
      engine.step(sid, {"observation": np.zeros(4, np.float32)})
      engine.close_session(sid)

  def test_unknown_and_closed_session_errors(self, warmed_engine):
    with pytest.raises(serving.UnknownSessionError):
      warmed_engine.step(987654, {"observation": np.zeros(4, np.float32)})
    sid = warmed_engine.open()
    warmed_engine.close_session(sid)
    with pytest.raises(serving.SessionClosedError):
      warmed_engine.step(sid, {"observation": np.zeros(4, np.float32)})
    # close after close is idempotent
    warmed_engine.close_session(sid)


# ---------------------------------------------------------------------------
# Eviction / admission under slot pressure.
# ---------------------------------------------------------------------------


class TestEviction:

  def test_lru_eviction_under_slot_pressure(self, seq_predictor):
    with metrics_lib.isolated() as registry:
      engine = serving.SessionEngine(predictor=seq_predictor,
                                     max_sessions=3, max_tick_batch=2,
                                     buckets=[1, 2])
      engine.warmup()
      obs = np.zeros(4, np.float32)
      sids = [engine.open() for _ in range(3)]
      # Tick 1 and 2 so session 0 is the least-recently-ticked.
      engine.step(sids[1], {"observation": obs})
      engine.step(sids[2], {"observation": obs})
      extra = engine.open()  # full table: evicts sids[0]
      with pytest.raises(serving.SessionEvictedError):
        engine.step(sids[0], {"observation": obs})
      # Survivors + the newcomer still serve.
      engine.step(sids[1], {"observation": obs})
      engine.step(extra, {"observation": obs})
      snap = registry.snapshot()
    assert snap["counter/serve/session/evictions"] == 1.0
    assert engine.active_sessions == 3

  def test_shed_admission_refuses_instead(self, seq_predictor):
    with metrics_lib.isolated() as registry:
      engine = serving.SessionEngine(predictor=seq_predictor,
                                     max_sessions=2, max_tick_batch=1,
                                     buckets=[1], admission="shed")
      engine.warmup()
      engine.open(), engine.open()
      with pytest.raises(serving.SessionShedError):
        engine.open()
      snap = registry.snapshot()
    assert snap["counter/serve/session/shed"] == 1.0

  def test_in_flight_session_never_evicted(self, seq_predictor):
    """Slot pressure during a slow dispatch must evict an idle victim,
    not a session whose state is mid-flight on device."""
    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=seq_predictor,
                                     max_sessions=2, max_tick_batch=1,
                                     buckets=[1])
      engine.warmup()
      busy, idle = engine.open(), engine.open()
      obs = np.zeros(4, np.float32)
      release = threading.Event()
      in_dispatch = threading.Event()
      real_get_state = engine._bundle.get_state

      def slow_get_state():
        in_dispatch.set()
        release.wait(timeout=10.0)
        return real_get_state()

      engine._bundle = engine._bundle._replace(get_state=slow_get_state)
      result = {}

      def stepper():
        result["out"] = engine.step(busy, {"observation": obs})

      thread = threading.Thread(target=stepper)
      thread.start()
      assert in_dispatch.wait(timeout=10.0)
      opened = engine.open()  # must evict `idle`, not in-flight `busy`
      release.set()
      thread.join(timeout=30.0)
      assert not thread.is_alive()
      assert "out" in result
      with pytest.raises(serving.SessionEvictedError):
        engine.step(idle, {"observation": obs})
      engine.step(busy, {"observation": obs})  # still alive and coherent
      for sid in (busy, opened):
        engine.close_session(sid)


# ---------------------------------------------------------------------------
# close() with in-flight steps (the join discipline).
# ---------------------------------------------------------------------------


class TestInFlightClose:

  def test_close_session_waits_out_in_flight_dispatch(self, seq_predictor):
    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=seq_predictor,
                                     max_sessions=2, max_tick_batch=1,
                                     buckets=[1])
      engine.warmup()
      sid = engine.open()
      obs = np.zeros(4, np.float32)
      release = threading.Event()
      in_dispatch = threading.Event()
      real_get_state = engine._bundle.get_state

      def slow_get_state():
        in_dispatch.set()
        release.wait(timeout=10.0)
        return real_get_state()

      engine._bundle = engine._bundle._replace(get_state=slow_get_state)
      done = {}

      def stepper():
        done["out"] = engine.step(sid, {"observation": obs})

      thread = threading.Thread(target=stepper)
      thread.start()
      assert in_dispatch.wait(timeout=10.0)
      t0 = time.monotonic()
      closer = threading.Thread(target=engine.close_session, args=(sid,))
      closer.start()
      # close_session must BLOCK while the step is in flight.
      closer.join(timeout=0.3)
      assert closer.is_alive(), "close_session returned mid-dispatch"
      release.set()
      thread.join(timeout=30.0)
      closer.join(timeout=30.0)
      assert not closer.is_alive()
      assert "out" in done  # the in-flight tick was served, not dropped
      assert time.monotonic() - t0 < 30.0
      assert engine.active_sessions == 0


# ---------------------------------------------------------------------------
# restore() hot-swap mid-episode.
# ---------------------------------------------------------------------------


class TestRestoreHotSwap:

  def test_restore_mid_episode_keeps_state_coherent(self, tmp_path):
    """A checkpoint hot-swap mid-episode: the open session keeps its
    device state and bookkeeping (no reset, no recompile), later ticks
    run under the NEW params, and a FRESH session matches the stateless
    forward under the new params exactly."""
    from tensor2robot_tpu.parallel import train_step as ts

    predictor = _make_predictor(**SEQ_KW)
    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=predictor,
                                     max_sessions=3, max_tick_batch=1,
                                     buckets=[1])
      engine.warmup()
      obs = _obs_seq(1, SEQ_KW["sequence_length"], SEQ_KW["obs_size"],
                     seed=31)
      sid = engine.open()
      for t in range(3):
        engine.step(sid, {"observation": obs[0, t]})
      compiles = engine.compile_count

      # Hot-swap: perturb the params in place (the predictor's state
      # getter is what the decode dispatch reads — exactly the
      # restore() wiring, without a checkpoint round trip).
      import jax

      old_state = predictor._state
      new_params = jax.tree_util.tree_map(lambda p: p * 1.5,
                                          old_state.params)
      predictor._state = old_state.replace(params=new_params)

      # The session continues mid-episode under the new params.
      out_after = engine.step(sid, {"observation": obs[0, 3]})
      assert np.all(np.isfinite(out_after["action"]))
      assert engine.session_ticks(sid) == 4
      assert engine.compile_count == compiles  # no re-warm needed

      # A fresh session under the new params == stateless forward.
      full_new = predictor.predict({"observation": obs})["action"]
      sid2 = engine.open()
      for t in range(4):
        out = engine.step(sid2, {"observation": obs[0, t]})
        np.testing.assert_allclose(out["action"], full_new[0, t],
                                   rtol=1e-5, atol=1e-6)
      for s in (sid, sid2):
        engine.close_session(s)
      assert isinstance(predictor._state, ts.TrainState)


# ---------------------------------------------------------------------------
# graftcache warm start for the decode ladder.
# ---------------------------------------------------------------------------


class TestSessionGraftcache:

  def test_warm_start_loads_ladder_without_compiles(self, seq_predictor,
                                                    tmp_path):
    cache_dir = str(tmp_path / "excache")
    with metrics_lib.isolated():
      cold = serving.SessionEngine(predictor=seq_predictor,
                                   max_sessions=4, max_tick_batch=2,
                                   buckets=[1, 2], cache=cache_dir)
      cold.warmup()
    assert cold.compile_count == 3  # 2 buckets + reset
    with metrics_lib.isolated():
      warm = serving.SessionEngine(predictor=seq_predictor,
                                   max_sessions=4, max_tick_batch=2,
                                   buckets=[1, 2], cache=cache_dir)
      warm.warmup()
    assert warm.compile_count == 0, warm.compile_records
    assert warm.cache_loads == 3
    # And the warm engine actually serves with parity.
    obs = _obs_seq(1, SEQ_KW["sequence_length"], SEQ_KW["obs_size"],
                   seed=41)
    full = seq_predictor.predict({"observation": obs})["action"]
    sid = warm.open()
    for t in range(3):
      out = warm.step(sid, {"observation": obs[0, t]})
      np.testing.assert_allclose(out["action"], full[0, t],
                                 rtol=1e-5, atol=1e-6)
    warm.close_session(sid)


# ---------------------------------------------------------------------------
# SessionBatcher: continuous batching + affinity + shutdown.
# ---------------------------------------------------------------------------


class TestSessionBatcher:

  def test_concurrent_episodes_coalesce_with_parity(self, seq_predictor,
                                                    warmed_engine):
    seq_len, obs_size = SEQ_KW["sequence_length"], SEQ_KW["obs_size"]
    episodes = {i: _obs_seq(1, seq_len, obs_size, seed=50 + i)
                for i in range(3)}
    full = {i: seq_predictor.predict({"observation": o})["action"]
            for i, o in episodes.items()}
    errors = []
    with metrics_lib.isolated() as registry:
      with serving.SessionBatcher(engine=warmed_engine,
                                  max_delay_ms=2.0) as batcher:
        def robot(i):
          try:
            sid = batcher.open()
            for t in range(seq_len):
              out = batcher.step(sid, {"observation": episodes[i][0, t]})
              np.testing.assert_allclose(out["action"], full[i][0, t],
                                         rtol=1e-5, atol=1e-6)
            batcher.close_session(sid)
          except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

        threads = [threading.Thread(target=robot, args=(i,))
                   for i in episodes]
        for thread in threads:
          thread.start()
        for thread in threads:
          thread.join(timeout=120.0)
      snap = registry.snapshot()
    assert not errors, errors
    ticks = snap["counter/serve/session/ticks"]
    dispatches = snap["counter/serve/session/dispatches"]
    assert ticks == 3 * seq_len
    # Coalescing actually happened: fewer dispatches than ticks.
    assert dispatches < ticks

  def test_affinity_same_session_ticks_serialize(self, warmed_engine):
    """Two queued ticks of ONE session never share a dispatch — the
    second waits for the next batch (order inside an episode is the
    correctness contract)."""
    obs = np.zeros(4, np.float32)
    with metrics_lib.isolated() as registry:
      with serving.SessionBatcher(engine=warmed_engine,
                                  max_delay_ms=20.0) as batcher:
        sid = batcher.open()
        results = []

        def tick():
          results.append(batcher.step(sid, {"observation": obs}))

        threads = [threading.Thread(target=tick) for _ in range(3)]
        for thread in threads:
          thread.start()
        for thread in threads:
          thread.join(timeout=60.0)
        batcher.close_session(sid)
      snap = registry.snapshot()
    assert len(results) == 3
    # 3 ticks of one session = 3 separate dispatches, never batched.
    assert snap["counter/serve/session/dispatches"] == 3.0

  def test_close_fails_queued_and_joins_worker(self, warmed_engine):
    batcher = serving.SessionBatcher(engine=warmed_engine)
    batcher.close()
    assert not batcher._worker.is_alive()
    with pytest.raises(serving.ShutdownError):
      batcher.step(1, {"observation": np.zeros(4, np.float32)})


# ---------------------------------------------------------------------------
# Policy + run_env: episodes ride sessions.
# ---------------------------------------------------------------------------


class _CountdownEnv:
  """Minimal gymnasium-5-tuple env: fixed-length episodes of random
  observations (the policy's actions are ignored)."""

  def __init__(self, obs_size: int, horizon: int, seed: int = 0):
    self._rng = np.random.RandomState(seed)
    self._obs_size = obs_size
    self._horizon = horizon
    self._t = 0

  def reset(self):
    self._t = 0
    return {"observation": self._rng.randn(
        self._obs_size).astype(np.float32)}, {}

  def step(self, action):
    self._t += 1
    obs = {"observation": self._rng.randn(
        self._obs_size).astype(np.float32)}
    done = self._t >= self._horizon
    return obs, 1.0, done, False, {}


class TestSessionPolicy:

  def test_run_env_episodes_ride_sessions(self, warmed_engine):
    from tensor2robot_tpu.envs import run_env as run_env_lib
    from tensor2robot_tpu.policies import policies as policies_lib

    policy = policies_lib.SessionRegressionPolicy(
        predictor=warmed_engine, action_key="inference_output")
    with metrics_lib.isolated() as registry:
      stats = run_env_lib.run_env(
          env=_CountdownEnv(SEQ_KW["obs_size"], horizon=4),
          policy=policy, num_episodes=3)
      policy.close()
      snap = registry.snapshot()
    assert stats["collect/episode_length_mean"] == 4.0
    # One session per episode, all closed by reset()/close().
    assert snap["counter/serve/session/opens"] == 3.0
    assert snap["counter/serve/session/closes"] == 3.0
    assert warmed_engine.active_sessions == 0

  def test_transient_error_keeps_session_id(self, warmed_engine):
    """A retryable (non-lifecycle) failure must NOT drop the policy's
    session id — dropping it would silently reset() mid-episode onto an
    empty decode cache and leak the old slot."""
    from tensor2robot_tpu.policies import policies as policies_lib

    class FlakyFront:
      """Session-surface wrapper that fails one step transiently."""

      def __init__(self, engine):
        self._engine = engine
        self.fail_next = False

      def open(self):
        return self._engine.open()

      def close_session(self, sid):
        self._engine.close_session(sid)

      def close(self):
        pass  # the shared engine outlives this front

      def step(self, sid, features):
        if self.fail_next:
          self.fail_next = False
          raise RuntimeError("transient backend hiccup")
        return self._engine.step(sid, features)

    front = FlakyFront(warmed_engine)
    policy = policies_lib.SessionRegressionPolicy(predictor=front)
    obs = {"observation": np.zeros(4, np.float32)}
    policy.reset()
    policy.select_action(obs)
    sid = policy.session_id
    front.fail_next = True
    with pytest.raises(RuntimeError, match="transient"):
      policy.select_action(obs)
    assert policy.session_id == sid  # retryable: same episode continues
    policy.select_action(obs)
    assert warmed_engine.session_ticks(sid) == 2
    policy.close()

  def test_horizon_error_frees_the_slot(self, seq_predictor):
    """An episode outrunning the decode horizon must not leak its slot
    — under admission='shed' a leaked slot per finished episode is
    denial of service."""
    from tensor2robot_tpu.policies import policies as policies_lib

    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=seq_predictor,
                                     max_sessions=1, max_tick_batch=1,
                                     buckets=[1], admission="shed")
      engine.warmup()
      policy = policies_lib.SessionRegressionPolicy(predictor=engine)
      obs = {"observation": np.zeros(4, np.float32)}
      policy.reset()
      for _ in range(SEQ_KW["sequence_length"]):
        policy.select_action(obs)
      with pytest.raises(serving.SessionHorizonError):
        policy.select_action(obs)
      assert engine.active_sessions == 0  # slot released, not leaked
      policy.reset()  # a new episode admits on the single slot
      policy.select_action(obs)
      policy.close()

  def test_eviction_surfaces_and_policy_recovers(self, seq_predictor):
    from tensor2robot_tpu.policies import policies as policies_lib

    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=seq_predictor,
                                     max_sessions=1, max_tick_batch=1,
                                     buckets=[1])
      engine.warmup()
      policy = policies_lib.SessionRegressionPolicy(predictor=engine)
      obs = {"observation": np.zeros(4, np.float32)}
      policy.reset()
      policy.select_action(obs)
      engine.open()  # steals the single slot: policy's session evicted
      with pytest.raises(serving.SessionEvictedError):
        policy.select_action(obs)
      policy.reset()  # recovers by opening a fresh session
      action = policy.select_action(obs)
      assert action.shape == (SEQ_KW["action_size"],)


# ---------------------------------------------------------------------------
# Open-loop session load shape.
# ---------------------------------------------------------------------------


class TestSessionLoadgen:

  def test_open_loop_drives_eviction_and_counts_outcomes(self,
                                                         seq_predictor):
    """A session-shaped open-loop burst against a tiny slot table must
    finish every episode OR count its eviction — and the engine must
    stay coherent (no recompiles, slots all freed)."""
    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=seq_predictor,
                                     max_sessions=2, max_tick_batch=2,
                                     buckets=[1, 2])
      engine.warmup()
      compiles = engine.compile_count
      obs = np.zeros(4, np.float32)
      stats = loadgen.run_session_load(
          engine,
          make_obs=lambda i, t: {"observation": obs},
          num_sessions=8, session_rate_hz=200.0, episode_ticks=4,
          think_time_ms=1.0, seed=0)
    assert stats["sessions"] == 8
    accounted = (stats["completed_episodes"] + stats["evicted_episodes"]
                 + sum(stats["errors"].values()) - stats["errors"].get(
                     "SessionEvictedError", 0))
    assert accounted >= stats["completed_episodes"]
    assert stats["completed_episodes"] >= 1
    assert stats["ok_ticks"] > 0
    assert engine.compile_count == compiles
    assert engine.active_sessions == 0  # every episode closed/evicted

  def test_rejects_bad_args(self, warmed_engine):
    with pytest.raises(ValueError):
      loadgen.run_session_load(warmed_engine, lambda i, t: {},
                               num_sessions=0, session_rate_hz=1.0,
                               episode_ticks=1)
    with pytest.raises(ValueError):
      loadgen.run_session_load(warmed_engine, lambda i, t: {},
                               num_sessions=1, session_rate_hz=0.0,
                               episode_ticks=1)


# ---------------------------------------------------------------------------
# graftlint session-state-leak.
# ---------------------------------------------------------------------------


class TestSessionStateLeakLint:

  def _findings(self, src):
    from tensor2robot_tpu.analysis import session_check

    return session_check.check_python_source("x.py", src)

  def test_flags_dropped_state(self):
    findings = self._findings(
        "def f(decode_step, s, sess, o):\n"
        "  decode_step(s, sess, o)\n")
    assert len(findings) == 1
    assert findings[0].rule == "session-state-leak"
    assert "discarded" in findings[0].message

  def test_flags_underscore_state_binding(self):
    findings = self._findings(
        "def f(decode_step, s, sess, o):\n"
        "  _, out = decode_step(s, sess, o)\n")
    assert len(findings) == 1
    assert "underscore" in findings[0].message

  def test_flags_host_fetch_of_session_state(self):
    findings = self._findings(
        "import numpy as np\n"
        "def f(session_state, engine):\n"
        "  a = np.asarray(session_state)\n"
        "  b = np.asarray(engine._arena)\n")
    assert len(findings) == 2

  def test_clean_and_suppressed_sites_pass(self):
    from tensor2robot_tpu.analysis import session_check
    from tensor2robot_tpu.analysis.findings import (filter_findings,
                                                    load_suppressions)

    src = ("def f(decode_step, s, sess, o, out):\n"
           "  sess, out = decode_step(s, sess, o)\n"
           "  import numpy as np\n"
           "  c = np.asarray(out)\n"
           "  decode_step(s, sess, o)"
           "  # graftlint: disable=session-state-leak\n")
    findings = filter_findings(
        session_check.check_python_source("x.py", src),
        load_suppressions(src))
    assert findings == []

  def test_rule_in_catalog_and_repo_pinned_clean(self):
    from tensor2robot_tpu.analysis import engine, lint

    engine.load_builtin_rules()
    assert "session-state-leak" in engine.catalog_text()
    package = os.path.join(REPO_ROOT, "tensor2robot_tpu")
    findings = [f for f in lint.run([package])
                if f.rule == "session-state-leak"]
    assert findings == [], findings


# ---------------------------------------------------------------------------
# Tier-1: session bookkeeping is backend-free (poisoned-platform trap).
# ---------------------------------------------------------------------------


def test_session_module_backend_free():
  """`serving.session` must import — and the host-side bookkeeping
  (errors, admission validation, batcher worker lifecycle, the lint
  rule, loadgen arg validation) must run — without initializing any JAX
  backend (the engine touches jax only inside warmup/step, never
  here)."""
  code = """
import numpy as np
from tensor2robot_tpu import serving
from tensor2robot_tpu.serving import session as session_lib
from tensor2robot_tpu.serving import loadgen
from tensor2robot_tpu.analysis import session_check

# Constructor-time validation is pure host work.
class _Stub:
    pass
engine = serving.SessionEngine(predictor=_Stub(), max_sessions=4,
                               max_tick_batch=2)
assert engine.buckets == [1, 2]
assert engine.max_sessions == 4
try:
    serving.SessionEngine(predictor=_Stub(), max_sessions=2,
                          max_tick_batch=8)
    raise AssertionError("max_tick_batch > max_sessions accepted")
except ValueError:
    pass
try:
    serving.SessionEngine(predictor=_Stub(), admission="nope")
    raise AssertionError("bad admission accepted")
except ValueError:
    pass

# The lint rule is pure AST.
findings = session_check.check_python_source(
    "x.py", "def f(decode_step, a, b, c):\\n  decode_step(a, b, c)\\n")
assert len(findings) == 1, findings

# Loadgen validation without ever opening a session.
try:
    loadgen.run_session_load(None, lambda i, t: {}, num_sessions=0,
                             session_rate_hz=1.0, episode_ticks=1)
    raise AssertionError("bad loadgen args accepted")
except ValueError:
    pass

err = serving.SessionEvictedError("gone", session_id=7)
assert err.session_id == 7

from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("SESSION_NO_BACKEND_OK")
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "graftsession_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", code],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "SESSION_NO_BACKEND_OK" in result.stdout
