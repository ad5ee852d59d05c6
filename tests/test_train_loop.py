"""make_train_loop: the on-device K-step scan train loop.

Semantic pin: the loop must be EXACTLY K sequential make_train_step
calls — same params, same per-step metrics — with the K batches staged
on a leading axis. This is the TPU-idiomatic host-training-loop the
reference gets from TPUEstimator `iterations_per_loop`
(/root/reference/models/abstract_model.py:662-834 returns
TPUEstimatorSpec; the estimator loops on-device between session calls).
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec

from tensor2robot_tpu import modes, specs as specs_lib
from tensor2robot_tpu.parallel import train_step as ts
from tensor2robot_tpu.research.qtopt import flagship


def _model_and_batches(k, batch=4):
  model = flagship.make_flagship_model("cpu", smoke=True)
  pre = model.preprocessor
  fs = [specs_lib.make_random_numpy(
      pre.get_out_feature_specification(modes.TRAIN),
      batch_size=batch, seed=i) for i in range(k)]
  ls = [specs_lib.make_random_numpy(
      pre.get_out_label_specification(modes.TRAIN),
      batch_size=batch, seed=100 + i) for i in range(k)]
  stack = lambda batches: jax.tree_util.tree_map(
      lambda *xs: np.stack(xs), *batches)
  return model, fs, ls, stack(fs), stack(ls)


def test_loop_matches_sequential_steps_exactly():
  k = 3
  model, fs, ls, fsk, lsk = _model_and_batches(k)
  s_seq, _ = ts.create_train_state(model, jax.random.PRNGKey(0), fs[0])
  step = ts.make_train_step(model, donate=False)
  seq_losses = []
  for f, l in zip(fs, ls):
    s_seq, m = step(s_seq, f, l)
    seq_losses.append(float(m["loss"]))

  s_loop, _ = ts.create_train_state(model, jax.random.PRNGKey(0), fs[0])
  loop = ts.make_train_loop(model, k, donate=False)
  s_loop, metrics = loop(s_loop, fsk, lsk)

  # Per-step metrics come back stacked on a leading K axis.
  assert metrics["loss"].shape == (k,)
  np.testing.assert_allclose(np.asarray(metrics["loss"]), seq_losses,
                             rtol=1e-6)
  assert int(s_loop.step) == k
  for a, b in zip(jax.tree_util.tree_leaves(s_seq.params),
                  jax.tree_util.tree_leaves(s_loop.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
  # EMA advanced identically too (flagship has use_ema=True).
  for a, b in zip(jax.tree_util.tree_leaves(s_seq.ema_params),
                  jax.tree_util.tree_leaves(s_loop.ema_params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_loop_under_mesh_matches_single_device():
  k, batch = 2, 8
  model, fs, ls, fsk, lsk = _model_and_batches(k, batch=batch)
  s_single, _ = ts.create_train_state(model, jax.random.PRNGKey(0), fs[0])
  loop_single = ts.make_train_loop(model, k, donate=False)
  s_single, m_single = loop_single(s_single, fsk, lsk)

  devices = np.array(jax.devices()[:4]).reshape(4)
  mesh = Mesh(devices, ("data",))
  s_mesh, shardings = ts.create_train_state(
      model, jax.random.PRNGKey(0), fs[0], mesh=mesh)
  loop = ts.make_train_loop(model, k, mesh=mesh, shardings=shardings,
                            donate=False)
  s_mesh, m_mesh = loop(s_mesh, fsk, lsk)
  np.testing.assert_allclose(np.asarray(m_mesh["loss"]),
                             np.asarray(m_single["loss"]), rtol=1e-5)
  for a, b in zip(jax.tree_util.tree_leaves(s_single.params),
                  jax.tree_util.tree_leaves(s_mesh.params)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_loop_rejects_bad_num_steps():
  model = flagship.make_flagship_model("cpu", smoke=True)
  with pytest.raises(ValueError):
    ts.make_train_loop(model, 0)
