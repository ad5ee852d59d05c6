"""Measured train-step baselines for the five driver research configs.

BASELINE.md requires the framework to establish and COMMIT its own
measured per-chip baselines (steps/sec, examples/sec) for: pose_env,
QT-Opt critic, BC-Z, Grasp2Vec, VRGripper MDN — plus the MAML config
(inner+outer step). Models are built FROM the shipped gin configs
(train_eval_model.model resolved by the config engine), so the numbers
measure exactly what `bin/run_t2r_trainer.py --config_files <gin>`
trains.

Usage (one process for each chip: each a separate short process):

  python scripts/family_baselines.py cpu            # f32 CPU smoke
  python scripts/family_baselines.py tpu            # all families
  python scripts/family_baselines.py tpu bcz_resnet_film  # one family
                                   # (short single-purpose process —
                                   # one compile per process)

`tpu` fails (`backend.require_tpu`) where jax finds no TPU; on the chip
tool run it as `chiprun -- python scripts/family_baselines.py tpu`.
Results: one JSON line per family on stdout.
"""

import json
import sys

sys.path.insert(0, ".")  # run from the repo root

from tensor2robot_tpu.utils import backend

CONFIG_ROOT = "tensor2robot_tpu/research"

# (name, config file, extra CPU-mode bindings: f32 + cpu device — the
# configs themselves are written for the TPU target). Batch size comes
# from the config's own DefaultRandomInputGenerator.batch_size binding
# so the measurement cannot drift from what the trainer trains.
FAMILIES = [
    ("pose_env", "pose_env/configs/train_pose_regression.gin", []),
    ("qtopt_grasping44", "qtopt/configs/train_qtopt.gin", [
        "QTOptModel.device_type = 'cpu'",
        "QTOptModel.use_bfloat16 = False",
    ]),
    ("bcz_resnet_film", "bcz/configs/train_bcz.gin", [
        "BCZModel.device_type = 'cpu'",
        "BCZModel.use_bfloat16 = False",
    ]),
    ("grasp2vec", "grasp2vec/configs/train_grasp2vec.gin", [
        "Grasp2VecModel.device_type = 'cpu'",
    ]),
    ("vrgripper_mdn", "vrgripper/configs/train_vrgripper_mdn.gin", [
        "VRGripperRegressionModel.device_type = 'cpu'",
    ]),
    ("maml_pose_env", "pose_env/configs/train_pose_maml.gin", []),
]


def measure_family(name, config_file, overrides, on_tpu, steps,
                   loop_k: int = 1):
  """`loop_k > 1` times the on-device K-step scan loop
  (train_step.make_train_loop) instead of single-step dispatch: the
  round-5 run (old setup, 2026-07) measured small families flat at
  ~8 ms/step — a per-DISPATCH floor, not the chip (the same models step
  in 2-4 ms on a bare CPU core). K steps per dispatch divides that floor
  by K; this mode prices the win per family."""
  import jax
  import numpy as np

  from tensor2robot_tpu import modes, specs as specs_lib
  from tensor2robot_tpu.parallel import train_step as ts
  from tensor2robot_tpu.utils import config

  config.clear_config()
  config.parse_config_file(f"{CONFIG_ROOT}/{config_file}")
  if not on_tpu:
    config.parse_config("\n".join(overrides))
  model = config.query_parameter("train_eval_model.model")
  batch_size = int(config.query_parameter(
      "DefaultRandomInputGenerator.batch_size"))
  device = jax.devices()[0]

  def batches(spec, seed0):
    outs = [specs_lib.make_random_numpy(spec, batch_size=batch_size,
                                        seed=seed0 + i)
            for i in range(loop_k)]
    if loop_k == 1:
      return outs[0]
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *outs)

  feature_spec = model.preprocessor.get_out_feature_specification(
      modes.TRAIN)
  label_spec = model.preprocessor.get_out_label_specification(modes.TRAIN)
  host_features = batches(feature_spec, 0)
  init_features = (host_features if loop_k == 1 else
                   jax.tree_util.tree_map(lambda x: x[0], host_features))
  features = jax.device_put(host_features, device)
  labels = jax.device_put(batches(label_spec, 100), device)
  state, _ = ts.create_train_state(model, jax.random.PRNGKey(0),
                                   init_features)
  if loop_k > 1:
    step = ts.make_train_loop(model, loop_k)
    iters = max(2, steps // loop_k)
  else:
    step = ts.make_train_step(model)
    iters = steps
  sec, _ = backend.time_train_steps(step, state, features, labels,
                                    iters=iters, warmup=2)
  sec /= loop_k
  print(json.dumps({
      "family": name,
      "config": config_file,
      "device": device.device_kind if on_tpu else "cpu_smoke_f32",
      "batch_size": batch_size,
      "loop_steps": loop_k,
      "ms_per_step": round(sec * 1e3, 2),
      "steps_per_sec": round(1.0 / sec, 2),
      "examples_per_sec": round(batch_size / sec, 2),
  }), flush=True)


def main():
  mode = sys.argv[1] if len(sys.argv) > 1 else "cpu"
  # Optional "loopK" token (e.g. "loop32") anywhere after the mode
  # measures the K-step on-device scan loop instead of single-step
  # dispatch; works with or without a family ("tpu loop32" = all
  # families at K steps/dispatch).
  loop_k = 1
  rest = []
  for arg in sys.argv[2:]:
    if arg.startswith("loop"):
      loop_k = int(arg[4:] or "32")
    else:
      rest.append(arg)
  only = rest[0] if rest else None
  families = [f for f in FAMILIES if only is None or f[0] == only]
  if not families:
    raise SystemExit(f"unknown family {only!r}; "
                     f"choose from {[f[0] for f in FAMILIES]}")
  if mode == "tpu":
    if only is None:
      # One family per process, strictly one after another: this parent
      # never initializes a jax backend, so each child has the chip to
      # itself and one family's failure does not lose the others.
      import subprocess

      for family in FAMILIES:
        rc = subprocess.call(
            [sys.executable, __file__, "tpu", family[0]]
            + ([f"loop{loop_k}"] if loop_k > 1 else []))
        if rc:
          sys.exit(rc)
      return
    backend.require_tpu()
    on_tpu, steps = True, 20 if loop_k == 1 else 4 * loop_k
  else:
    backend.pin_cpu()
    on_tpu, steps = False, 5
  for name, config_file, overrides in families:
    measure_family(name, config_file, overrides, on_tpu, steps, loop_k)


if __name__ == "__main__":
  main()
