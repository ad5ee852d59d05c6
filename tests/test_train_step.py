"""Tests for the SPMD train/eval step factory on a virtual 8-device mesh.

The JAX twin of the reference's TPUEstimator-on-CPU strategy
(SURVEY.md §4): all sharding is exercised on the forced 8-device CPU
backend from conftest.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from tensor2robot_tpu import modes, specs as specs_lib
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import train_step as ts
from tensor2robot_tpu.utils import mocks


@pytest.fixture(scope="module")
def dp_mesh():
  return mesh_lib.create_mesh(mesh_shape=(8, 1, 1))


def _batch(generator, mesh=None):
  raw = next(generator)
  features, labels = raw["features"], raw["labels"]
  if mesh is not None:
    features = mesh_lib.put_host_batch(mesh, features)
    labels = mesh_lib.put_host_batch(mesh, labels)
  return features, labels


class TestMeshConstruction:

  def test_default_mesh_all_data(self):
    m = mesh_lib.create_mesh()
    assert m.shape["data"] == 8
    assert m.shape["fsdp"] == m.shape["model"] == 1

  def test_explicit_shapes(self):
    m = mesh_lib.create_mesh(mesh_shape=(2, 2, 2))
    assert m.shape == {"data": 2, "fsdp": 2, "model": 2}

  def test_too_large_shape_raises(self):
    with pytest.raises(ValueError, match="cover"):
      mesh_lib.create_mesh(mesh_shape=(16, 1, 1))

  def test_smaller_shape_uses_device_prefix(self):
    m = mesh_lib.create_mesh(mesh_shape=(2, 1, 1))
    assert m.devices.size == 2

  def test_local_batch_size(self, dp_mesh):
    assert mesh_lib.local_batch_size(32, dp_mesh) == 32  # single process

  def test_put_host_batch_shards_leading_dim(self, dp_mesh):
    batch = specs_lib.SpecStruct({"x": np.zeros((16, 3), np.float32)})
    out = mesh_lib.put_host_batch(dp_mesh, batch)
    shard_shapes = {s.data.shape for s in out["x"].addressable_shards}
    assert shard_shapes == {(2, 3)}


class TestDevicePrefetcher:

  def _batches(self, n):
    for i in range(n):
      yield {"features": specs_lib.SpecStruct(
          {"x": np.full((8, 2), float(i), np.float32)}),
             "labels": specs_lib.SpecStruct(
          {"y": np.full((8, 1), float(i), np.float32)})}

  def test_preserves_order_and_placement(self, dp_mesh):
    pf = mesh_lib.DevicePrefetcher(self._batches(5), dp_mesh, depth=2)
    seen = []
    for features, labels in pf:
      assert features["x"].sharding.spec == PartitionSpec("data")
      seen.append(float(np.asarray(features["x"])[0, 0]))
      assert float(np.asarray(labels["y"])[0, 0]) == seen[-1]
    assert seen == [0.0, 1.0, 2.0, 3.0, 4.0]

  def test_worker_exception_reraises_in_consumer(self, dp_mesh):
    def bad():
      yield {"features": specs_lib.SpecStruct(
          {"x": np.zeros((8, 2), np.float32)})}
      raise RuntimeError("pipeline broke")

    pf = mesh_lib.DevicePrefetcher(bad(), dp_mesh, depth=1)
    next(pf)  # first batch ok
    with pytest.raises(RuntimeError, match="pipeline broke"):
      next(pf)

  def test_close_stops_worker(self, dp_mesh):
    import itertools
    import time

    pulled = [0]

    def infinite():
      for i in itertools.count():
        pulled[0] = i
        yield {"features": specs_lib.SpecStruct(
            {"x": np.zeros((8, 2), np.float32)})}

    pf = mesh_lib.DevicePrefetcher(infinite(), dp_mesh, depth=1)
    next(pf)
    pf.close()
    time.sleep(0.3)
    stopped_at = pulled[0]
    time.sleep(0.3)
    assert pulled[0] <= stopped_at + 1  # worker stopped pulling

  def test_depth_validation(self, dp_mesh):
    with pytest.raises(ValueError, match="depth"):
      mesh_lib.DevicePrefetcher(iter(()), dp_mesh, depth=0)

  def test_exhausted_keeps_raising_stopiteration(self, dp_mesh):
    pf = mesh_lib.DevicePrefetcher(self._batches(2), dp_mesh, depth=1)
    assert len(list(pf)) == 2
    with pytest.raises(StopIteration):  # iterator protocol: stays done
      next(pf)
    pf.close()  # idempotent after exhaustion

  def test_next_after_close_raises_stopiteration(self, dp_mesh):
    pf = mesh_lib.DevicePrefetcher(self._batches(5), dp_mesh, depth=1)
    next(pf)
    pf.close()
    with pytest.raises(StopIteration):
      next(pf)

  def test_context_manager_closes(self, dp_mesh):
    with mesh_lib.DevicePrefetcher(self._batches(3), dp_mesh,
                                   depth=1) as pf:
      next(pf)
    assert not pf._thread.is_alive()

  def test_close_returns_despite_stalled_source(self, dp_mesh):
    import threading
    import time

    unblock = threading.Event()

    def stalled():
      yield {"features": specs_lib.SpecStruct(
          {"x": np.zeros((8, 2), np.float32)})}
      unblock.wait(timeout=30)  # worker blocks inside next(dataset)

    pf = mesh_lib.DevicePrefetcher(stalled(), dp_mesh, depth=1)
    next(pf)
    start = time.perf_counter()
    pf.close(timeout=0.5)  # must not hang on the blocked worker
    assert time.perf_counter() - start < 5.0
    unblock.set()

  def test_finalizer_stops_abandoned_worker(self, dp_mesh):
    import gc
    import time

    pf = mesh_lib.DevicePrefetcher(self._batches(5), dp_mesh, depth=1)
    next(pf)
    stop_event = pf._stop
    del pf  # abandoned without close()
    gc.collect()
    for _ in range(50):
      if stop_event.is_set():
        break
      time.sleep(0.1)
    assert stop_event.is_set()


class TestTrainStep:

  def _setup(self, mesh, use_ema=False, use_bfloat16=False, rules=None,
             batch_size=32):
    model = mocks.MockT2RModel(use_ema=use_ema, use_bfloat16=use_bfloat16,
                               device_type="cpu")
    gen = mocks.MockInputGenerator(batch_size=batch_size)
    gen.set_specification_from_model(model, modes.TRAIN)
    dataset = gen.create_dataset(modes.TRAIN)
    features, labels = _batch(dataset)
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), features, mesh=mesh, rules=rules)
    step = ts.make_train_step(model, mesh=mesh, shardings=shardings)
    return model, dataset, state, shardings, step

  def test_loss_decreases_dp(self, dp_mesh):
    model, dataset, state, shardings, step = self._setup(dp_mesh)
    losses = []
    for batch in dataset:
      features = mesh_lib.put_host_batch(dp_mesh, batch["features"])
      labels = mesh_lib.put_host_batch(dp_mesh, batch["labels"])
      state, metrics = step(state, features, labels)
      losses.append(float(metrics["loss"]))
      if len(losses) >= 200:
        break
    assert losses[-1] < losses[0] * 0.5, losses[::50]
    assert int(state.step) == 200

  def test_metrics_replicated_and_finite(self, dp_mesh):
    model, dataset, state, shardings, step = self._setup(dp_mesh)
    batch = next(dataset)
    state, metrics = step(state,
                          mesh_lib.put_host_batch(dp_mesh, batch["features"]),
                          mesh_lib.put_host_batch(dp_mesh, batch["labels"]))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["global_gradient_norm"]))

  def test_batch_stats_updated(self, dp_mesh):
    model, dataset, state, shardings, step = self._setup(dp_mesh)
    before = jax.tree_util.tree_map(np.asarray, state.mutable_state)
    batch = next(dataset)
    new_state, _ = step(state,
                        mesh_lib.put_host_batch(dp_mesh, batch["features"]),
                        mesh_lib.put_host_batch(dp_mesh, batch["labels"]))
    after = jax.tree_util.tree_map(np.asarray, new_state.mutable_state)
    leaves_before = jax.tree_util.tree_leaves(before)
    leaves_after = jax.tree_util.tree_leaves(after)
    assert any(not np.allclose(a, b)
               for a, b in zip(leaves_before, leaves_after))

  def test_ema_tracks_params(self, dp_mesh):
    model, dataset, state, shardings, step = self._setup(dp_mesh,
                                                         use_ema=True)
    assert state.ema_params is not None
    batch = next(dataset)
    new_state, _ = step(state,
                        mesh_lib.put_host_batch(dp_mesh, batch["features"]),
                        mesh_lib.put_host_batch(dp_mesh, batch["labels"]))
    # EMA with decay .9999 stays near init, params move further
    p0 = jax.tree_util.tree_leaves(new_state.params)[0]
    e0 = jax.tree_util.tree_leaves(new_state.ema_params)[0]
    assert not np.allclose(np.asarray(p0), np.asarray(e0))

  def test_eval_step_and_accuracy_improves(self, dp_mesh):
    model, dataset, state, shardings, step = self._setup(dp_mesh)
    eval_step = ts.make_eval_step(model, mesh=dp_mesh, shardings=shardings)
    batch = next(dataset)
    f = mesh_lib.put_host_batch(dp_mesh, batch["features"])
    l = mesh_lib.put_host_batch(dp_mesh, batch["labels"])
    acc_before = float(eval_step(state, f, l)["accuracy"])
    for _ in range(300):
      b = next(dataset)
      state, _ = step(state,
                      mesh_lib.put_host_batch(dp_mesh, b["features"]),
                      mesh_lib.put_host_batch(dp_mesh, b["labels"]))
      jax.block_until_ready(state)  # see conftest.py: one step in flight
    acc_after = float(eval_step(state, f, l)["accuracy"])
    assert acc_after >= acc_before
    assert acc_after > 0.9

  def test_predict_fn(self, dp_mesh):
    model, dataset, state, shardings, step = self._setup(dp_mesh)
    predict = ts.make_predict_fn(model)
    batch = next(dataset)
    out = predict(state, batch["features"])
    assert "prediction" in out
    assert out["prediction"].shape == (32, 1)

  def test_bfloat16_compute(self, dp_mesh):
    model, dataset, state, shardings, step = self._setup(
        dp_mesh, use_bfloat16=True)
    batch = next(dataset)
    state, metrics = step(state,
                          mesh_lib.put_host_batch(dp_mesh, batch["features"]),
                          mesh_lib.put_host_batch(dp_mesh, batch["labels"]))
    assert np.isfinite(float(metrics["loss"]))
    # params stay float32 under the bfloat16 compute policy
    assert jax.tree_util.tree_leaves(state.params)[0].dtype == jnp.float32


class TestShardingRules:

  def test_fsdp_rules_shard_largest_dim(self):
    mesh = mesh_lib.create_mesh(mesh_shape=(2, 4, 1))
    model = mocks.MockT2RModel(device_type="cpu")
    gen = mocks.MockInputGenerator(batch_size=16)
    gen.set_specification_from_model(model, modes.TRAIN)
    batch = next(gen.create_dataset(modes.TRAIN))
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), batch["features"], mesh=mesh,
        rules=ts.fsdp_rules())
    # hidden dense kernel (3,16) or (16,16): largest dim divisible by 4
    kernel_sharding = shardings.params["dense_0"]["kernel"]
    assert "fsdp" in str(kernel_sharding.spec)
    step = ts.make_train_step(model, mesh=mesh, shardings=shardings)
    f = mesh_lib.put_host_batch(mesh, batch["features"])
    l = mesh_lib.put_host_batch(mesh, batch["labels"])
    state, metrics = step(state, f, l)
    assert np.isfinite(float(metrics["loss"]))

  def test_explicit_rule_partition(self):
    mesh = mesh_lib.create_mesh(mesh_shape=(2, 1, 4))
    spec = ts._leaf_partition("dense/kernel", (16, 32),
                              ((r"kernel", (None, "model")),), mesh)
    assert spec == PartitionSpec(None, "model")

  def test_rule_shape_mismatch_falls_back_replicated(self):
    mesh = mesh_lib.create_mesh(mesh_shape=(2, 1, 4))
    spec = ts._leaf_partition("dense/bias", (16,),
                              ((r".*", (None, "model")),), mesh)
    assert spec == PartitionSpec()


class TestMixedPrecision:

  def test_bfloat16_forward_actually_computes_in_bfloat16(self):
    """f32 params + bf16 inputs must not silently promote back to f32
    (flax's default dtype promotion would defeat the MXU bf16 path)."""
    model = mocks.MockT2RModel(device_type="cpu", use_bfloat16=True,
                               use_batch_norm=False)
    features = {"x": np.zeros((2, 3), np.float32)}
    state, _ = ts.create_train_state(model, jax.random.PRNGKey(0), features)
    compute_features = model.cast_features_for_compute(
        jax.tree_util.tree_map(jnp.asarray, features))
    assert compute_features["x"].dtype == jnp.bfloat16
    variables = {"params": state.params, **state.mutable_state}
    outputs, _ = model.inference_network_fn(
        variables, compute_features, modes.TRAIN, train=False)
    assert outputs["logit"].dtype == jnp.bfloat16
    # master params stay float32
    assert jax.tree_util.tree_leaves(state.params)[0].dtype == jnp.float32

  def test_bfloat16_training_still_converges(self):
    model = mocks.MockT2RModel(device_type="cpu", use_bfloat16=True,
                               use_batch_norm=False)
    gen = mocks.MockInputGenerator(batch_size=32)
    gen.set_specification_from_model(model, modes.TRAIN)
    dataset = gen.create_dataset(modes.TRAIN)
    batch = next(dataset)
    state, _ = ts.create_train_state(model, jax.random.PRNGKey(0),
                                     batch["features"])
    step = ts.make_train_step(model)
    first = None
    for _ in range(150):
      b = next(dataset)
      state, metrics = step(state, b["features"], b["labels"])
      first = first if first is not None else float(metrics["loss"])
    assert float(metrics["loss"]) < first * 0.5


class TestGradientAccumulation:
  """`gradient_accumulation_steps=k` (optax.MultiSteps, applied by
  `build_optimizer` so subclass `create_optimizer` overrides keep it):
  k micro-batch steps at batch B must train exactly like one step at
  batch k*B — the fit-bigger-effective-batches knob that does not hold
  k*B activations."""

  def _params(self, state):
    return jax.device_get(state.params)

  def test_two_micro_steps_match_one_large_batch_step(self):
    import optax

    def make(accum):
      # No batch norm: BN stats are per-micro-batch by construction and
      # would (correctly) differ from the large-batch stats.
      return mocks.MockT2RModel(
          use_batch_norm=False, device_type="cpu",
          optimizer_fn=lambda: optax.sgd(0.1),
          gradient_accumulation_steps=accum)

    gen = mocks.MockInputGenerator(batch_size=16)
    gen.set_specification_from_model(make(1), modes.TRAIN)
    batch = next(gen.create_dataset(modes.TRAIN))
    features, labels = batch["features"], batch["labels"]
    half = lambda tree, s: jax.tree_util.tree_map(lambda x: x[s], tree)

    accum_model = make(2)
    a_state, _ = ts.create_train_state(
        accum_model, jax.random.PRNGKey(0), half(features, slice(0, 8)))
    a_step = ts.make_train_step(accum_model, donate=False)
    before = self._params(a_state)
    a_state, _ = a_step(a_state, half(features, slice(0, 8)),
                        half(labels, slice(0, 8)))
    # First micro-step only accumulates: params must be untouched.
    for p0, p1 in zip(jax.tree_util.tree_leaves(before),
                      jax.tree_util.tree_leaves(self._params(a_state))):
      np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    a_state, _ = a_step(a_state, half(features, slice(8, 16)),
                        half(labels, slice(8, 16)))

    big_model = make(1)
    b_state, _ = ts.create_train_state(
        big_model, jax.random.PRNGKey(0), features)
    b_step = ts.make_train_step(big_model, donate=False)
    b_state, _ = b_step(b_state, features, labels)

    for pa, pb in zip(jax.tree_util.tree_leaves(self._params(a_state)),
                      jax.tree_util.tree_leaves(self._params(b_state))):
      np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                 atol=1e-6)

  def test_invalid_accumulation_raises(self):
    with pytest.raises(ValueError, match="gradient_accumulation_steps"):
      mocks.MockT2RModel(device_type="cpu",
                         gradient_accumulation_steps=0)

  def test_accumulation_applies_through_subclass_optimizer_override(self):
    """Models that override create_optimizer (QTOpt, MAML, Mock without
    an injected optimizer_fn) must still get the MultiSteps wrapper —
    the step factories consume build_optimizer, not create_optimizer."""
    model = mocks.MockT2RModel(  # no optimizer_fn: Mock's own override
        use_batch_norm=False, device_type="cpu",
        gradient_accumulation_steps=2)
    gen = mocks.MockInputGenerator(batch_size=8)
    gen.set_specification_from_model(model, modes.TRAIN)
    batch = next(gen.create_dataset(modes.TRAIN))
    state, _ = ts.create_train_state(model, jax.random.PRNGKey(0),
                                     batch["features"])
    step = ts.make_train_step(model, donate=False)
    before = jax.device_get(state.params)
    state, _ = step(state, batch["features"], batch["labels"])
    # First micro-step only accumulates; without the wrapper this
    # would be a full optimizer step and params would move.
    for p0, p1 in zip(jax.tree_util.tree_leaves(before),
                      jax.tree_util.tree_leaves(
                          jax.device_get(state.params))):
      np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))

  def test_ema_moves_once_per_applied_update(self):
    """EMA must track APPLIED updates, not micro-steps: with k=2 the
    accumulated run's EMA matches the equivalent large-batch step's
    EMA exactly (same single decay application)."""
    import optax

    def make(accum):
      return mocks.MockT2RModel(
          use_batch_norm=False, device_type="cpu", use_ema=True,
          ema_decay=0.5, optimizer_fn=lambda: optax.sgd(0.1),
          gradient_accumulation_steps=accum)

    gen = mocks.MockInputGenerator(batch_size=16)
    gen.set_specification_from_model(make(1), modes.TRAIN)
    batch = next(gen.create_dataset(modes.TRAIN))
    features, labels = batch["features"], batch["labels"]
    half = lambda tree, s: jax.tree_util.tree_map(lambda x: x[s], tree)

    accum_model = make(2)
    a_state, _ = ts.create_train_state(
        accum_model, jax.random.PRNGKey(0), half(features, slice(0, 8)))
    a_step = ts.make_train_step(accum_model, donate=False)
    ema_before = jax.device_get(a_state.ema_params)
    a_state, _ = a_step(a_state, half(features, slice(0, 8)),
                        half(labels, slice(0, 8)))
    # Accumulation-only micro-step: EMA untouched.
    for e0, e1 in zip(jax.tree_util.tree_leaves(ema_before),
                      jax.tree_util.tree_leaves(
                          jax.device_get(a_state.ema_params))):
      np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
    a_state, _ = a_step(a_state, half(features, slice(8, 16)),
                        half(labels, slice(8, 16)))

    big_model = make(1)
    b_state, _ = ts.create_train_state(
        big_model, jax.random.PRNGKey(0), features)
    b_step = ts.make_train_step(big_model, donate=False)
    b_state, _ = b_step(b_state, features, labels)

    for ea, eb in zip(jax.tree_util.tree_leaves(
                          jax.device_get(a_state.ema_params)),
                      jax.tree_util.tree_leaves(
                          jax.device_get(b_state.ema_params))):
      np.testing.assert_allclose(np.asarray(ea), np.asarray(eb),
                                 atol=1e-6)

  def test_maml_inherits_base_model_accumulation(self):
    from tensor2robot_tpu.meta_learning import maml

    base = mocks.MockT2RModel(device_type="cpu",
                              gradient_accumulation_steps=4)
    wrapper = maml.MAMLModel(base_model=base)
    assert wrapper.gradient_accumulation_steps == 4
    # Explicit knob on the wrapper wins.
    assert maml.MAMLModel(
        base_model=base,
        gradient_accumulation_steps=1).gradient_accumulation_steps == 1
