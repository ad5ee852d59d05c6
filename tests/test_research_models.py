"""Tests for research model families: pose_env, qtopt (+PCGrad)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu import modes, specs as specs_lib
from tensor2robot_tpu.data import input_generators
from tensor2robot_tpu.ops import pcgrad
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import train_step as ts
from tensor2robot_tpu.research.pose_env import models as pose_models
from tensor2robot_tpu.research.qtopt import models as qtopt_models
from tensor2robot_tpu.utils import config


@pytest.fixture(autouse=True)
def _clean_config():
  config.clear_config()
  yield
  config.clear_config()


def _train_steps(model, batch_size=8, steps=3, mesh=None):
  gen = input_generators.DefaultRandomInputGenerator(batch_size=batch_size)
  gen.set_specification_from_model(model, modes.TRAIN)
  dataset = gen.create_dataset(modes.TRAIN)
  batch = next(dataset)
  state, shardings = ts.create_train_state(
      model, jax.random.PRNGKey(0), batch["features"], mesh=mesh)
  step = ts.make_train_step(model, mesh=mesh, shardings=shardings)
  metrics = None
  for _ in range(steps):
    f, l = batch["features"], batch["labels"]
    if mesh is not None:
      f = mesh_lib.put_host_batch(mesh, f)
      l = mesh_lib.put_host_batch(mesh, l)
    state, metrics = step(state, f, l)
    jax.block_until_ready(metrics)  # see conftest.py: one step in flight
    batch = next(dataset)
  return state, metrics


class TestPoseEnvModels:

  def test_regression_model_trains(self):
    model = pose_models.PoseEnvRegressionModel(device_type="cpu")
    state, metrics = _train_steps(model)
    assert np.isfinite(float(metrics["loss"]))

  def test_critic_model_trains(self):
    model = pose_models.PoseEnvContinuousMCModel(device_type="cpu")
    state, metrics = _train_steps(model)
    assert np.isfinite(float(metrics["loss"]))

  def test_critic_spec_split(self):
    model = pose_models.PoseEnvContinuousMCModel(device_type="cpu")
    fs = model.get_feature_specification(modes.TRAIN)
    assert "state/image" in fs and "action/action" in fs

  def test_action_tiling(self):
    state_tree = {"image": jnp.ones((2, 4))}
    tiled = pose_models.PoseEnvContinuousMCModel.tile_state_for_actions(
        state_tree, 3)
    assert tiled["image"].shape == (6, 4)


class TestQTOpt:

  def test_qtopt_trains_with_ema(self):
    model = qtopt_models.QTOptModel(image_size=32, device_type="cpu")
    state, metrics = _train_steps(model, batch_size=4)
    assert np.isfinite(float(metrics["loss"]))
    assert state.ema_params is not None  # EMA on by default

  def test_qtopt_pcgrad_path(self):
    model = qtopt_models.QTOptModel(image_size=32, device_type="cpu",
                                    use_pcgrad=True)
    state, metrics = _train_steps(model, batch_size=4)
    assert "task_loss/bellman" in metrics
    assert "task_loss/q_regularizer" in metrics
    assert np.isfinite(float(metrics["loss"]))

  def test_qtopt_on_dp_mesh(self):
    mesh = mesh_lib.create_mesh(mesh_shape=(8, 1, 1))
    model = qtopt_models.QTOptModel(image_size=32, device_type="cpu")
    state, metrics = _train_steps(model, batch_size=16, mesh=mesh)
    assert np.isfinite(float(metrics["loss"]))

  def test_q_output_in_unit_interval(self):
    model = qtopt_models.QTOptModel(image_size=32, device_type="cpu")
    spec = model.get_feature_specification(modes.PREDICT)
    features = specs_lib.make_random_numpy(spec, batch_size=2, seed=0)
    state, _ = ts.create_train_state(model, jax.random.PRNGKey(0), features)
    predict = ts.make_predict_fn(model)
    out = predict(state, features)
    q = np.asarray(out["q_predicted"])
    assert (q >= 0).all() and (q <= 1).all()


class TestPCGrad:

  def _grads(self):
    g1 = {"a": jnp.array([1.0, 0.0]), "b": jnp.array([1.0])}
    g2 = {"a": jnp.array([-1.0, 1.0]), "b": jnp.array([1.0])}
    return g1, g2

  def test_non_conflicting_pass_through(self):
    g = {"a": jnp.array([1.0, 1.0])}
    out = pcgrad.pcgrad_combine([g, g])
    np.testing.assert_allclose(np.asarray(out["a"]), [2.0, 2.0])

  def test_conflicting_projection(self):
    g1 = {"a": jnp.array([1.0, 0.0])}
    g2 = {"a": jnp.array([-1.0, 0.5])}
    out = pcgrad.pcgrad_combine([g1, g2])
    # g1 projected: remove component along g2 (dot=-1 <0)
    manual_g1 = np.array([1.0, 0.0]) - (-1.0 / 1.25) * np.array([-1.0, 0.5])
    manual_g2 = np.array([-1.0, 0.5]) - (-1.0 / 1.0) * np.array([1.0, 0.0])
    np.testing.assert_allclose(np.asarray(out["a"]),
                               manual_g1 + manual_g2, rtol=1e-5)

  def test_single_task_identity(self):
    g = {"a": jnp.array([3.0])}
    out = pcgrad.pcgrad_combine([g])
    np.testing.assert_allclose(np.asarray(out["a"]), [3.0])

  def test_denylist_exempts_leaves(self):
    g1 = {"a": jnp.array([1.0, 0.0]), "bias": jnp.array([-1.0])}
    g2 = {"a": jnp.array([-1.0, 0.5]), "bias": jnp.array([1.0])}
    out = pcgrad.pcgrad_combine([g1, g2], denylist=["bias"])
    np.testing.assert_allclose(np.asarray(out["bias"]), [0.0])  # plain sum

  def test_random_order_jits(self):
    g1 = {"a": jnp.array([1.0, 0.0])}
    g2 = {"a": jnp.array([-1.0, 0.5])}
    fn = jax.jit(lambda key: pcgrad.pcgrad_combine([g1, g2], key=key))
    out = fn(jax.random.PRNGKey(0))
    assert np.isfinite(np.asarray(out["a"])).all()

  def test_flat_projection(self):
    g1, g2 = self._grads()
    out = pcgrad.pcgrad_combine([g1, g2], use_flat_projection=True)
    assert set(out.keys()) == {"a", "b"}


class TestPoseEnvReferenceParity:

  def test_reward_weighted_regression(self):
    """Zero-reward examples contribute no loss (reference success-weighted
    BC, pose_env_models.py loss_fn weights=labels.reward)."""
    from tensor2robot_tpu.research.pose_env import models as pose_models

    model = pose_models.PoseEnvRegressionModel(device_type="cpu")
    batch = 4
    outputs = {"inference_output": jnp.ones((batch, 2))}
    model = pose_models.PoseEnvRegressionModel(
        device_type="cpu", success_reward_threshold=0.5)  # {0,1} rewards
    labels = specs_lib.SpecStruct({
        "target_pose": np.zeros((batch, 2), np.float32),
        "reward": np.array([[1.0], [0.0], [1.0], [0.0]], np.float32),
    })
    loss, scalars = model.model_train_fn({}, labels, outputs, modes.TRAIN)
    # only the two reward-1 examples count; each has error 1.0 per dim
    assert float(loss) == pytest.approx(1.0, rel=1e-5)
    assert "weighted_mse" in scalars
    assert float(scalars["success_fraction"]) == pytest.approx(0.5)
    # The bundled toy env writes negative -distance MC returns; the
    # default threshold (-0.25) treats near-zero returns as successes so
    # its own replay is trainable, while far-miss episodes drop out and
    # can never flip the gradient (review r2).
    env_like = specs_lib.SpecStruct({
        "target_pose": np.zeros((batch, 2), np.float32),
        "reward": np.array([[-0.05], [-1.5], [-0.1], [-2.0]], np.float32),
    })
    model_default = pose_models.PoseEnvRegressionModel(device_type="cpu")
    loss_env, scalars_env = model_default.model_train_fn(
        {}, env_like, outputs, modes.TRAIN)
    assert float(scalars_env["success_fraction"]) == pytest.approx(0.5)
    assert float(loss_env) == pytest.approx(1.0, rel=1e-5)
    # without reward labels, plain MSE path
    loss2, _ = model.model_train_fn(
        {}, specs_lib.SpecStruct(
            {"target_pose": np.zeros((batch, 2), np.float32)}),
        outputs, modes.TRAIN)
    assert float(loss2) == pytest.approx(1.0, rel=1e-5)

  def test_pack_features_shapes(self):
    from tensor2robot_tpu.research.pose_env import models as pose_models

    reg = pose_models.PoseEnvRegressionModel(device_type="cpu")
    obs = np.zeros((32, 32, 1), np.uint8)
    packed = reg.pack_features(obs)
    assert packed["state/image"].shape == (1, 32, 32, 1)
    # the toy env's dict observation unwraps too (review r2)
    packed_dict = reg.pack_features({"image": obs, "timestep": 3})
    assert packed_dict["state/image"].shape == (1, 32, 32, 1)

    critic = pose_models.PoseEnvContinuousMCModel(device_type="cpu")
    actions = np.random.RandomState(0).rand(5, 2).astype(np.float32)
    packed = critic.pack_features(obs, actions=actions)
    assert packed["state/image"].shape == (5, 32, 32, 1)
    assert packed["action/action"].shape == (5, 2)
    with pytest.raises(ValueError, match="actions"):
      critic.pack_features(obs)
