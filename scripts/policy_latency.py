"""Serving-side policy latency: on-device CEM action selection rate.

The reference's robot serving design point is 1-10 Hz policy inference
(/root/reference/README.md:54-56) with CEM at 64 samples x 3
iterations, 10 elites (/root/reference/policies/policies.py:110-116) —
its CEM loop ran numpy on the robot workstation with one TF session
call per iteration. Here the whole argmax_a Q(s,a) loop is one jitted
device call (policies/device_cem.py), so the measurable is a single
round-trip.

Usage (short single-purpose processes):

  python scripts/policy_latency.py cpu   # small-critic smoke
  python scripts/policy_latency.py tpu   # Grasping44 @472 bf16

Prints one JSON line: policy Hz + ms/action at the reference CEM cost.
`tpu` fails (`backend.require_tpu`) where jax finds no TPU.
"""

import json
import sys
import time

sys.path.insert(0, ".")  # run from the repo root

from tensor2robot_tpu.utils import backend

WARMUP = 2
CALLS = 20


def main():
  mode = sys.argv[1] if len(sys.argv) > 1 else "cpu"
  if mode == "tpu":
    backend.require_tpu()
  else:
    backend.pin_cpu()
  import jax

  from tensor2robot_tpu import modes, specs as specs_lib
  from tensor2robot_tpu.parallel import train_step as ts
  from tensor2robot_tpu.policies import device_cem
  from tensor2robot_tpu.research.qtopt import flagship

  device = jax.devices()[0]
  on_tpu = device.platform != "cpu"
  # The shared flagship config — the same network bench.py trains.
  model = flagship.make_flagship_model(device.platform)
  train_features = specs_lib.make_random_numpy(
      model.preprocessor.get_out_feature_specification(modes.TRAIN),
      batch_size=2, seed=0)
  state, _ = ts.create_train_state(model, jax.random.PRNGKey(0),
                                   train_features)
  # Reference CEM serving cost: 64 samples x 3 iterations, 10 elites.
  policy = device_cem.DeviceCEMPolicy(
      model=model, state=state,
      action_size=flagship.ACTION_SIZE if on_tpu else 4,
      cem_samples=64, cem_iterations=3, cem_elites=10, seed=0)
  # One observation: the model's state features, unbatched, without the
  # 'state/' prefix (device_cem's obs contract).
  flat = specs_lib.flatten_spec_structure(
      model.preprocessor.get_out_feature_specification(modes.PREDICT))
  obs = dict(specs_lib.make_random_numpy(
      specs_lib.SpecStruct({key[len("state/"):]: spec
                            for key, spec in flat.items()
                            if key.startswith("state/")}),
      batch_size=None, seed=0).items())
  for _ in range(WARMUP):
    policy.select_action(obs)
  start = time.perf_counter()
  for _ in range(CALLS):
    policy.select_action(obs)  # returns np action: host fetch = barrier
  sec = (time.perf_counter() - start) / CALLS
  print(json.dumps({
      "metric": ("device_cem_actions_per_sec"
                 if on_tpu else "device_cem_actions_per_sec_cpu_smoke"),
      "network": "grasping44_472_bf16" if on_tpu else "small_32_f32",
      "cem": "64x3_elites10",
      "ms_per_action": round(sec * 1e3, 2),
      "actions_per_sec": round(1.0 / sec, 2),
      "reference_design_point_hz": "1-10",
  }), flush=True)


if __name__ == "__main__":
  main()
