"""graftkern: Pallas decode-tick kernel tier (ISSUE 20).

Pins the kernel tier's semantics and its gate:
* `fused_decode_attention` (interpret mode) matches the XLA reference
  composition at EVERY append index, partial blocks and pad lanes
  included, and leaves the null slot untouched (pad-lane immunity);
* a `use_decode_kernel=True` engine matches the `=False` engine AND the
  stateless full-prefix forward tick-by-tick at every step T in {8, 32},
  through padded partial buckets, up to the `SessionHorizonError` edge;
* zero recompiles after warmup across open/step/close/evict churn on
  the kernel engine;
* `restore()` param hot-swap mid-episode keeps a kernel-engine session
  coherent (no re-warm, fresh session matches new-param forward);
* graftcache warm start loads kernel-dispatch rungs with zero compiles,
  and an xla-arm engine sharing the cache dir never cross-loads them
  (the `pallas` key component keeps the rungs distinct);
* the gate: auto declines off-TPU (interpreter mode is a smoke tier,
  not a win), LSTM models auto-decline (no KV arena) and a forced
  `True` falls back counted + still serves with parity;
* gate resolution is backend-free on every forced/declined path
  (poisoned JAX_PLATFORMS trap over `decode_kernel_mode`).

Reference decode semantics: /root/reference/policies/policies.py:188-218
(host-side recurrent-state threading this tier replaces).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from tensor2robot_tpu import serving
from tensor2robot_tpu.obs import metrics as metrics_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEQ_BASE = dict(obs_size=4, action_size=2, hidden_size=8,
                num_blocks=2, num_heads=2)
LSTM_KW = dict(obs_size=4, action_size=2, sequence_length=8,
               hidden_size=8)


def _make_predictor(model_cls=None, **kw):
  from tensor2robot_tpu.models import sequence_model
  from tensor2robot_tpu.predictors import predictors as predictors_lib

  model_cls = model_cls or sequence_model.SequenceRegressionModel
  predictor = predictors_lib.CheckpointPredictor(
      model=model_cls(**kw), model_dir="/nonexistent")
  predictor.init_randomly()
  return predictor


def _obs_seq(batch, seq_len, obs_size, seed=0):
  return np.random.RandomState(seed).randn(
      batch, seq_len, obs_size).astype(np.float32)


# ---------------------------------------------------------------------------
# Kernel-level parity: fused vs the XLA reference composition.
# ---------------------------------------------------------------------------


class TestFusedKernelParity:

  @pytest.mark.parametrize("t,block_k", [(8, 4), (8, 8), (32, 8)])
  def test_matches_reference_at_every_index(self, t, block_k):
    """The numerics contract at EVERY append index 0..T-1: mixed-progress
    lanes (one at idx, one lagging at idx//2), a pad lane on the null
    slot, partial last blocks — fused (interpret) == reference, all
    three outputs."""
    import jax.numpy as jnp

    from tensor2robot_tpu.ops import decode_kernels as dk

    s, b, h, d = 5, 3, 2, 4
    rs = np.random.RandomState(t * 31 + block_k)
    k_arena0 = rs.randn(s, t, h, d).astype(np.float32)
    v_arena0 = rs.randn(s, t, h, d).astype(np.float32)
    slots = jnp.asarray([1, 3, 0], jnp.int32)
    mask = jnp.asarray([True, True, False])
    for idx_val in range(t):
      q = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
      k_new = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
      v_new = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
      index = jnp.asarray([idx_val, idx_val // 2, 0], jnp.int32)
      args = (q, k_new, v_new, jnp.asarray(k_arena0),
              jnp.asarray(v_arena0), slots, index, mask)
      out_f, k_f, v_f = dk.fused_decode_attention(
          *args, block_k=block_k, interpret=True)
      out_r, k_r, v_r = dk.reference_decode_attention(*args)
      np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_r),
                                 rtol=1e-5, atol=1e-5,
                                 err_msg=f"out mismatch at index {idx_val}")
      np.testing.assert_allclose(np.asarray(k_f), np.asarray(k_r),
                                 rtol=1e-6, atol=1e-6)
      np.testing.assert_allclose(np.asarray(v_f), np.asarray(v_r),
                                 rtol=1e-6, atol=1e-6)

  def test_pad_lane_leaves_null_slot_untouched(self):
    """Null-slot immunity: a pad lane (mask False, slot 0) must land the
    OLD row value — the whole arena is bit-identical after its 'append'
    (duplicate writes through slot 0 are idempotent)."""
    import jax.numpy as jnp

    from tensor2robot_tpu.ops import decode_kernels as dk

    s, t, h, d = 3, 8, 2, 4
    rs = np.random.RandomState(7)
    k_arena0 = rs.randn(s, t, h, d).astype(np.float32)
    v_arena0 = rs.randn(s, t, h, d).astype(np.float32)
    _, k_upd, v_upd = dk.fused_decode_attention(
        jnp.asarray(rs.randn(1, h, d).astype(np.float32)),
        jnp.asarray(rs.randn(1, h, d).astype(np.float32)),
        jnp.asarray(rs.randn(1, h, d).astype(np.float32)),
        jnp.asarray(k_arena0), jnp.asarray(v_arena0),
        jnp.asarray([0], jnp.int32), jnp.asarray([3], jnp.int32),
        jnp.asarray([False]), interpret=True)
    np.testing.assert_array_equal(np.asarray(k_upd), k_arena0)
    np.testing.assert_array_equal(np.asarray(v_upd), v_arena0)

  def test_effective_block_tiles_every_horizon(self):
    from tensor2robot_tpu.ops import decode_kernels as dk

    for t in range(1, 65):
      block = dk._effective_block(t, 8)
      assert 1 <= block <= min(8, t) and t % block == 0, (t, block)


# ---------------------------------------------------------------------------
# Engine-level parity: kernel arm vs jitted arm vs stateless forward.
# ---------------------------------------------------------------------------


class TestEngineKernelParity:

  @pytest.mark.parametrize("t", [8, 32])
  def test_tick_by_tick_parity_at_every_step(self, t):
    """THE acceptance pin: a forced-kernel engine reproduces both the
    forced-jitted engine and the stateless full-prefix forward at EVERY
    step, including padded partial buckets (3 live lanes in the
    4-bucket) and the horizon edge."""
    predictor = _make_predictor(sequence_length=t, **SEQ_BASE)
    with metrics_lib.isolated():
      kern = serving.SessionEngine(predictor=predictor, max_sessions=4,
                                   buckets=[1, 2, 4],
                                   use_decode_kernel=True)
      xla = serving.SessionEngine(predictor=predictor, max_sessions=4,
                                  buckets=[1, 2, 4],
                                  use_decode_kernel=False)
      kern.warmup()
      xla.warmup()
      assert (kern.decode_kernel_active, kern.decode_kernel_reason) == \
          (True, "on")
      assert xla.decode_kernel_active is False

      n = 3  # 3 distinct sessions pad into the 4-bucket every dispatch
      obs = _obs_seq(n, t, SEQ_BASE["obs_size"], seed=t)
      full = predictor.predict({"observation": obs})["action"]
      sids_k = [kern.open() for _ in range(n)]
      sids_x = [xla.open() for _ in range(n)]
      for step in range(t):
        outs_k = kern.step_many(
            [(sid, {"observation": obs[i, step]})
             for i, sid in enumerate(sids_k)])
        outs_x = xla.step_many(
            [(sid, {"observation": obs[i, step]})
             for i, sid in enumerate(sids_x)])
        for i in range(n):
          np.testing.assert_allclose(
              outs_k[i]["action"], full[i, step], rtol=1e-4, atol=1e-5,
              err_msg=f"kernel-vs-stateless at step {step} lane {i}")
          np.testing.assert_allclose(
              outs_k[i]["action"], outs_x[i]["action"],
              rtol=1e-5, atol=1e-6,
              err_msg=f"kernel-vs-jitted at step {step} lane {i}")
      # Horizon edge on BOTH tiers: tick T+1 refuses identically.
      for engine, sid in ((kern, sids_k[0]), (xla, sids_x[0])):
        with pytest.raises(serving.SessionHorizonError, match="horizon"):
          engine.step(sid, {"observation": obs[0, 0]})
      for engine, sids in ((kern, sids_k), (xla, sids_x)):
        for sid in sids:
          engine.close_session(sid)

  def test_kernel_engine_zero_recompiles_after_warmup(self):
    """Open/step/close churn under slot pressure (evictions included)
    never grows the kernel engine's compile count past the warmed
    ladder, and nothing falls back to the plain jit."""
    predictor = _make_predictor(sequence_length=8, **SEQ_BASE)
    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=predictor, max_sessions=3,
                                     buckets=[1, 2],
                                     use_decode_kernel=True)
      engine.warmup()
      warmed = engine.compile_count
      obs = _obs_seq(1, 8, SEQ_BASE["obs_size"], seed=5)
      sids = [engine.open() for _ in range(3)]
      engine.step_many([(s, {"observation": obs[0, 0]})
                        for s in sids[:2]])
      for _ in range(2):
        sids.append(engine.open())  # evicts an idle LRU session
      for sid in sids:
        try:
          engine.step(sid, {"observation": obs[0, 1]})
        except serving.SessionError:
          pass  # evicted mid-sweep: expected under slot pressure
      for sid in sids:
        try:
          engine.close_session(sid)
        except serving.SessionError:
          pass
      snap = metrics_lib.snapshot(prefix="serve/session/")
    assert engine.compile_count == warmed, engine.compile_records
    assert snap.get("counter/serve/session/exec_fallbacks", 0.0) == 0.0

  def test_restore_hot_swap_mid_episode(self):
    """Param hot-swap under the kernel tier: the open session continues
    (no re-warm), and a fresh session matches the stateless forward
    under the NEW params — params flow through the dispatch's state
    argument, never the kernel closure."""
    import jax

    predictor = _make_predictor(sequence_length=8, **SEQ_BASE)
    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=predictor, max_sessions=3,
                                     buckets=[1],
                                     use_decode_kernel=True)
      engine.warmup()
      obs = _obs_seq(1, 8, SEQ_BASE["obs_size"], seed=13)
      sid = engine.open()
      for step in range(3):
        engine.step(sid, {"observation": obs[0, step]})
      compiles = engine.compile_count

      old_state = predictor._state
      new_params = jax.tree_util.tree_map(lambda p: p * 1.5,
                                          old_state.params)
      predictor._state = old_state.replace(params=new_params)

      out_after = engine.step(sid, {"observation": obs[0, 3]})
      assert np.all(np.isfinite(out_after["action"]))
      assert engine.session_ticks(sid) == 4
      assert engine.compile_count == compiles

      full_new = predictor.predict({"observation": obs})["action"]
      sid2 = engine.open()
      for step in range(4):
        out = engine.step(sid2, {"observation": obs[0, step]})
        np.testing.assert_allclose(out["action"], full_new[0, step],
                                   rtol=1e-5, atol=1e-6)
      for s in (sid, sid2):
        engine.close_session(s)

  def test_graftcache_warm_start_with_kernel_rungs(self, tmp_path):
    """Kernel-dispatch rungs round-trip through graftcache (warm engine:
    zero compiles, full loads, serving parity) and never cross-load
    into an xla-arm engine sharing the cache dir — the `pallas` key
    component keeps the two dispatch families distinct."""
    cache_dir = str(tmp_path / "excache")
    predictor = _make_predictor(sequence_length=8, **SEQ_BASE)
    with metrics_lib.isolated():
      cold = serving.SessionEngine(predictor=predictor, max_sessions=4,
                                   buckets=[1, 2], cache=cache_dir,
                                   use_decode_kernel=True)
      cold.warmup()
    assert cold.compile_count == 3  # 2 buckets + reset
    with metrics_lib.isolated():
      warm = serving.SessionEngine(predictor=predictor, max_sessions=4,
                                   buckets=[1, 2], cache=cache_dir,
                                   use_decode_kernel=True)
      warm.warmup()
    assert warm.compile_count == 0, warm.compile_records
    assert warm.cache_loads == 3
    obs = _obs_seq(1, 8, SEQ_BASE["obs_size"], seed=17)
    full = predictor.predict({"observation": obs})["action"]
    sid = warm.open()
    for step in range(4):
      out = warm.step(sid, {"observation": obs[0, step]})
      np.testing.assert_allclose(out["action"], full[0, step],
                                 rtol=1e-5, atol=1e-6)
    warm.close_session(sid)
    # The OTHER tier against the same cache dir: the RESET rung is
    # tier-independent (no decode body) and legitimately shared — it
    # loads — while the two decode rungs must NOT cross-load (different
    # dispatch jaxpr + the `pallas` key component) and compile fresh.
    with metrics_lib.isolated():
      other = serving.SessionEngine(predictor=predictor, max_sessions=4,
                                    buckets=[1, 2], cache=cache_dir,
                                    use_decode_kernel=False)
      other.warmup()
    assert other.cache_loads == 1, other.warmup_provenance
    assert other.compile_count == 2, other.compile_records


# ---------------------------------------------------------------------------
# The gate: auto off-TPU, unsupported models, forced fallback.
# ---------------------------------------------------------------------------


class TestDecodeKernelGate:

  def test_auto_declines_off_tpu(self):
    """`use_decode_kernel=None` on a non-TPU backend stays on the jitted
    path (interpreter-mode kernels are a parity vehicle, not a win) —
    CPU tier-1/bench defaults measure what they always measured."""
    import jax

    if jax.default_backend() == "tpu":
      pytest.skip("auto resolves ON on a real TPU backend")
    predictor = _make_predictor(sequence_length=8, **SEQ_BASE)
    with metrics_lib.isolated():
      engine = serving.SessionEngine(predictor=predictor, max_sessions=2,
                                     max_tick_batch=1)
      active, reason = engine.decode_kernel_mode()
    assert active is False
    assert reason.startswith("auto-off: non-TPU backend")

  def test_lstm_auto_declines_and_forced_true_falls_back(self):
    """No KV arena layout (LSTM carry) => auto declines silently;
    forced True degrades COUNTED (the native-stager discipline) and the
    engine still serves with full parity on the jitted path."""
    from tensor2robot_tpu.models import sequence_model

    predictor = _make_predictor(sequence_model.LSTMRegressionModel,
                                **LSTM_KW)
    with metrics_lib.isolated():
      auto = serving.SessionEngine(predictor=predictor, max_sessions=2,
                                   max_tick_batch=1)
      active, reason = auto.decode_kernel_mode()
      assert active is False and reason.startswith("model-unsupported")

    with metrics_lib.isolated():
      forced = serving.SessionEngine(predictor=predictor, max_sessions=2,
                                     max_tick_batch=1,
                                     use_decode_kernel=True)
      forced.warmup()
      snap = metrics_lib.snapshot(prefix="serve/session/")
      assert forced.decode_kernel_active is False
      assert snap.get("counter/serve/session/decode_kernel_off") == 1.0
      assert snap.get("gauge/serve/session/decode_kernel") == 0.0
      obs = _obs_seq(1, LSTM_KW["sequence_length"], LSTM_KW["obs_size"],
                     seed=23)
      full = predictor.predict({"observation": obs})["action"]
      sid = forced.open()
      for step in range(4):
        out = forced.step(sid, {"observation": obs[0, step]})
        np.testing.assert_allclose(out["action"], full[0, step],
                                   rtol=1e-5, atol=1e-6)
      forced.close_session(sid)


# ---------------------------------------------------------------------------
# Tier-1: gate resolution is backend-free (poisoned-platform trap).
# ---------------------------------------------------------------------------


def test_decode_kernel_gate_backend_free():
  """Every forced/declined gate path — including `decode_kernel_mode`
  over a backend-free bundle — must resolve without initializing any
  JAX backend; only the fully-eligible auto path may consult it."""
  code = """
from tensor2robot_tpu import serving
from tensor2robot_tpu.serving import session as session_lib

def boom():
    raise AssertionError("backend thunk invoked on a forced path")

assert session_lib.resolve_decode_kernel(False, True, boom)[0] is False
assert session_lib.resolve_decode_kernel(True, True, boom) == (True, "on")
assert session_lib.resolve_decode_kernel(None, False, boom)[1].startswith(
    "model-unsupported")
assert session_lib.resolve_decode_kernel(
    None, True, lambda: False)[1].startswith("auto-off")
assert session_lib.resolve_decode_kernel(
    None, True, lambda: True) == (True, "on")

# decode_kernel_mode on a backend-free bundle: binds + resolves with no
# device work (auto + no arena seam declines before the backend thunk).
class _Bundle:
    pass

class _Pred:
    def decode_bundle(self):
        return _Bundle()

engine = serving.SessionEngine(predictor=_Pred(), max_sessions=2,
                               max_tick_batch=1)
active, reason = engine.decode_kernel_mode()
assert active is False and reason.startswith("model-unsupported"), reason
forced_off = serving.SessionEngine(predictor=_Pred(), max_sessions=2,
                                   max_tick_batch=1,
                                   use_decode_kernel=False)
assert forced_off.decode_kernel_mode() == (
    False, "disabled (use_decode_kernel=False)")

from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("DECODE_KERNEL_GATE_NO_BACKEND_OK")
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "graftkern_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", code],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "DECODE_KERNEL_GATE_NO_BACKEND_OK" in result.stdout
