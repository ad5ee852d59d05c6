"""Each plain reference against the program at tiny sizes: the weights it
draws from the seed are the trainer's own, bit for bit, and three training
steps in float32 agree to rounding."""

import jax
import numpy as np
import pytest

from benchmarks.harness import compare
from benchmarks.harness import traffic
from benchmarks.references import qtopt_grasping44_472 as g44
from benchmarks.references import seq_trunk_h512 as seq
from tensor2robot_tpu.parallel import train_step as ts

SEED = 2_147_483_659  # more than 32 signed bits hold


def _program_steps(model, feature_batches, label_batches, first_gradient):
  from benchmarks.drivers import trainer

  state, _ = ts.create_train_state(model, jax.random.PRNGKey(SEED),
                                   feature_batches[0])
  out = {"params0": jax.device_get(state.params), "losses": []}
  step = ts.make_train_step(model, donate=False)
  for i, (f, l) in enumerate(zip(feature_batches, label_batches)):
    state, metrics = step(state, f, l)
    out["losses"].append(float(metrics["loss"]))
    if i == 0:
      out["first_gradient"] = trainer._first_gradient(state.opt_state,
                                                      first_gradient)
      out["first_batch_stats"] = jax.device_get(state.mutable_state).get(
          "batch_stats", {})
  out["params"] = jax.device_get(state.params)
  return out


def _g44(bf16):
  from tensor2robot_tpu.research.qtopt import models as qtopt_models

  values = {"image_size": 96, "action_size": 5, "num_convs": (1, 1, 1),
            "grasp_param_names": {"world_vector": (0, 3),
                                  "vertical_rotation": (3, 2)}}
  model = qtopt_models.QTOptModel(
      network="grasping44", device_type="cpu", use_bfloat16=bf16, **values)
  sizes = g44.sizes_from_bindings(values)
  pool = traffic.make_pool(
      {"features/state/image": ((96, 96, 3), np.uint8),
       "features/action/action": ((5,), np.float32),
       "labels/reward": ((1,), np.float32)}, 8, 3, SEED,
      {"labels/reward": {"dist": "bernoulli"},
       "features/action/action": {"dist": "uniform"}})
  features = [{"state/image": b["features/state/image"],
               "action/action": b["features/action/action"]} for b in pool]
  labels = [{"reward": b["labels/reward"]} for b in pool]
  program = _program_steps(model, features, labels,
                           {"from": "trace", "scale": 1.0})
  return program, g44.train_steps(SEED, sizes, pool), (g44, sizes, pool)


def _seq(bf16):
  from tensor2robot_tpu.models import sequence_model

  values = {"obs_size": 16, "action_size": 7, "sequence_length": 64,
            "hidden_size": 64, "num_blocks": 2, "num_heads": 2}
  model = sequence_model.SequenceRegressionModel(
      attention_backend="flash", device_type="cpu", use_bfloat16=bf16,
      **values)
  sizes = seq.sizes_from_bindings({**values, "reference_rows": 2})
  pool = traffic.make_pool(
      {"features/observation": ((64, 16), np.float32),
       "labels/action": ((64, 7), np.float32)}, 4, 3, SEED)
  features = [{"observation": b["features/observation"]} for b in pool]
  labels = [{"action": b["labels/action"]} for b in pool]
  program = _program_steps(model, features, labels,
                           {"from": "mu", "scale": 10.0})
  return program, seq.train_steps(SEED, sizes, pool), (seq, sizes, pool)


@pytest.fixture(scope="module", params=["g44", "seq"])
def float32_pair(request):
  return {"g44": _g44, "seq": _seq}[request.param](False)


def test_reference_draws_the_trainers_weights(float32_pair):
  program, reference, _ = float32_pair
  p, r = compare.flatten(program["params0"]), compare.flatten(
      reference["params0"])
  assert sorted(p) == sorted(r)
  for key in p:
    assert np.array_equal(p[key], r[key]), key


def test_three_float32_steps_agree_to_rounding(float32_pair):
  program, reference, _ = float32_pair
  numbers = compare.training_numbers(program, reference)
  assert numbers["initial_weights"]["value"] == 0.0
  for name in ("loss1", "loss2", "loss3"):
    assert numbers[name]["value"] < 1e-5, numbers[name]
  assert numbers["first_gradient"]["value"] < 1e-3
  assert numbers["param_change"]["value"] < 1e-3
  if "batch_means" in numbers:  # the model has normalization layers
    assert numbers["batch_means"]["worst"] < 1e-4


def test_dead_leaves_are_found_by_rule_not_by_name(float32_pair):
  program, reference, (module, _, _) = float32_pair
  numbers = compare.training_numbers(program, reference)
  left_out = numbers["param_change"]["left_out"]
  if module is seq:
    assert left_out == ["attn_0/k_proj/bias", "attn_1/k_proj/bias"]
  else:  # biases in front of a batch norm
    assert set(left_out) == {"conv1_1/bias", "vertical_rotation/bias",
                             "world_vector/bias"}


def test_faults_read_far_above_rounding(float32_pair):
  _, reference, (module, sizes, pool) = float32_pair
  rows = len(next(iter(pool[0].values())))
  half = module.train_steps(SEED, sizes, pool, rows=slice(0, rows // 2))
  numbers = compare.training_numbers(half, reference)
  assert max(numbers[n]["value"] for n in (
      "loss1", "first_gradient", "param_change")) > 0.02
  unchanged = dict(reference, params=reference["params0"])
  numbers = compare.training_numbers(unchanged, reference)
  assert numbers["param_change"]["value"] == pytest.approx(1.0)


def test_decide_holds_every_named_number():
  numbers = {"loss1": {"value": 0.5}, "extra": {"value": 9.0}}
  correct, checks = compare.decide(numbers, {"loss1": 0.1, "_note": "x"})
  assert not correct and checks["extra"]["limit"] is None
  correct, _ = compare.decide(numbers, {"loss1": 0.6})
  assert correct
  correct, checks = compare.decide(numbers, {"loss1": 0.6, "loss2": 0.1})
  assert not correct and checks["loss2"]["value"] is None
  correct, _ = compare.decide({"loss1": {"value": float("nan")}},
                              {"loss1": 0.6})
  assert not correct
