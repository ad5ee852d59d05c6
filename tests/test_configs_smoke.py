"""Smoke-trains every shipped research config for a couple of steps —
the reference's `test_train_eval_gin` strategy
(/root/reference/utils/train_eval_test_utils.py:68-147)."""

import glob
import os

import pytest

from tensor2robot_tpu import train_eval
from tensor2robot_tpu.utils import config
from tensor2robot_tpu.utils.test_fixture import assert_output_files

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_GLOB = os.path.join(REPO_ROOT, "tensor2robot_tpu", "research", "*",
                           "configs", "*.gin")
def _is_trainer_config(path: str) -> bool:
  with open(path) as f:
    return "train_eval_model" in f.read()


ALL_CONFIGS = sorted(p for p in glob.glob(CONFIG_GLOB)
                     if _is_trainer_config(p))
ACTOR_CONFIGS = sorted(p for p in glob.glob(CONFIG_GLOB)
                       if not _is_trainer_config(p))

# Per-config shrink overrides so CI stays fast on CPU.
_SHRINK = [
    "train_eval_model.max_train_steps = 2",
    "train_eval_model.eval_steps = 1",
    "train_eval_model.eval_every_n_steps = 2",
    "train_eval_model.checkpoint_every_n_steps = 2",
    "train_eval_model.log_every_n_steps = 1",
    "DefaultRandomInputGenerator.batch_size = 2",
    "train_eval_model.mesh_shape = (1, 1, 1)",
]
# Shared by the parity and tuned-throughput QT-Opt configs (same model).
_QTOPT_SHRINK = ["QTOptModel.image_size = 108",
                 "QTOptModel.num_convs = (2, 2, 1)",
                 "QTOptModel.device_type = 'cpu'",
                 "QTOptModel.use_bfloat16 = False"]
_EXTRA = {
    "train_qtopt.gin": _QTOPT_SHRINK,
    "train_qtopt_tpu_tuned.gin": _QTOPT_SHRINK,
    "train_bcz.gin": ["BCZModel.image_size = 32",
                      "BCZModel.network = 'spatial_softmax'",
                      "BCZModel.num_waypoints = 3",
                      "BCZModel.device_type = 'cpu'",
                      "BCZModel.use_bfloat16 = False",
                      "BCZPreprocessor.input_size = (40, 40)",
                      "BCZPreprocessor.crop_size = (36, 36)",
                      "BCZPreprocessor.model_size = (32, 32)"],
    # Keeps network='pipelined_berkeley' (mesh_shape (1,1,1) runs the
    # sequential schedule — same math, no pp axis).
    "train_bcz_pp.gin": ["BCZModel.image_size = 32",
                         "BCZModel.num_waypoints = 3",
                         "BCZModel.device_type = 'cpu'",
                         "BCZModel.use_bfloat16 = False",
                         "BCZPreprocessor.input_size = (40, 40)",
                         "BCZPreprocessor.crop_size = (36, 36)",
                         "BCZPreprocessor.model_size = (32, 32)"],
    "train_grasp2vec.gin": ["Grasp2VecModel.image_size = 32",
                            "Grasp2VecModel.device_type = 'cpu'"],
    "train_vrgripper_mdn.gin": ["VRGripperRegressionModel.episode_length = 2",
                                "VRGripperRegressionModel.image_size = 32",
                                "VRGripperRegressionModel.device_type = 'cpu'"],
    "train_wtl_retrial.gin": ["WTLStateTrialModel.episode_length = 4",
                              "WTLStateTrialModel.obs_size = 8"],
    "train_vrgripper_da_maml.gin": [
        "VRGripperDomainAdaptiveModel.episode_length = 2",
        "VRGripperDomainAdaptiveModel.image_size = 16"],
}


@pytest.fixture(autouse=True)
def _clean_config():
  config.clear_config()
  yield
  config.clear_config()


def test_all_config_families_present():
  names = {os.path.basename(p) for p in ALL_CONFIGS}
  assert {"train_pose_regression.gin", "train_qtopt.gin", "train_bcz.gin",
          "train_grasp2vec.gin", "train_vrgripper_mdn.gin",
          "train_wtl_maml.gin", "train_wtl_retrial.gin",
          "train_vrgripper_da_maml.gin"} <= names


@pytest.mark.parametrize(
    "config_path", ALL_CONFIGS,
    ids=[os.path.basename(p) for p in ALL_CONFIGS])
def test_config_smoke_trains(config_path, tmp_path):
  model_dir = str(tmp_path / "run")
  bindings = list(_SHRINK)
  bindings.extend(_EXTRA.get(os.path.basename(config_path), []))
  bindings.append(f"train_eval_model.model_dir = {model_dir!r}")
  config.parse_config_files_and_bindings([config_path], bindings)
  metrics = train_eval.train_eval_model()
  assert metrics, f"no metrics from {config_path}"
  assert_output_files(model_dir, expect_operative_config=False)


def test_moe_ep_config_trains_on_mesh(tmp_path):
  """EP through the full training path: the train_moe_ep.gin config
  trains a sparse-dispatch MoE model through train_eval_model on a
  (2, 1, 2) mesh with the expert dim sharded over 'model'."""
  config_path = os.path.join(REPO_ROOT, "tensor2robot_tpu", "configs",
                             "train_moe_ep.gin")
  model_dir = str(tmp_path / "moe_ep")
  bindings = [b for b in _SHRINK if "mesh_shape" not in b]
  bindings.append(f"train_eval_model.model_dir = {model_dir!r}")
  bindings.append("DefaultRandomInputGenerator.batch_size = 8")
  config.parse_config_files_and_bindings([config_path], bindings)
  metrics = train_eval.train_eval_model()
  assert metrics
  assert_output_files(model_dir, expect_operative_config=False)


def test_pipelined_pp_config_trains_on_mesh(tmp_path):
  """PP through the full training path: train_pipelined_pp.gin trains the
  GPipe-trunk model through train_eval_model on a ('data', 'pp', 'model')
  = (2, 4, 1) mesh with stage params sharded over 'pp'."""
  config_path = os.path.join(REPO_ROOT, "tensor2robot_tpu", "configs",
                             "train_pipelined_pp.gin")
  model_dir = str(tmp_path / "pp")
  bindings = [b for b in _SHRINK
              if "mesh_shape" not in b and "batch_size" not in b]
  bindings.append(f"train_eval_model.model_dir = {model_dir!r}")
  config.parse_config_files_and_bindings([config_path], bindings)
  metrics = train_eval.train_eval_model()
  assert metrics
  assert_output_files(model_dir, expect_operative_config=False)


def test_pipelined_1f1b_config_trains_on_mesh(tmp_path):
  """Interleaved 1F1B through the full training path:
  train_pipelined_1f1b.gin trains the 8-stage trunk as 2 virtual chunks
  per rank of the 4-wide 'pp' axis ((2, 4, 1) mesh), stage params
  sharded over 'pp' — the schedule twin of the GPipe config above."""
  config_path = os.path.join(REPO_ROOT, "tensor2robot_tpu", "configs",
                             "train_pipelined_1f1b.gin")
  model_dir = str(tmp_path / "pp_1f1b")
  bindings = [b for b in _SHRINK
              if "mesh_shape" not in b and "batch_size" not in b]
  bindings.append(f"train_eval_model.model_dir = {model_dir!r}")
  config.parse_config_files_and_bindings([config_path], bindings)
  metrics = train_eval.train_eval_model()
  assert metrics
  assert_output_files(model_dir, expect_operative_config=False)


def test_bcz_pp_config_trains_on_mesh(tmp_path):
  """Heterogeneous PP through a REAL research family: train_bcz_pp.gin
  trains BCZ with its conv trunk GPipe-pipelined over the 'pp' axis of a
  (2, 4, 1) mesh (VERDICT r2 item 6: not the toy block stack)."""
  config_path = os.path.join(REPO_ROOT, "tensor2robot_tpu", "research",
                             "bcz", "configs", "train_bcz_pp.gin")
  model_dir = str(tmp_path / "bcz_pp")
  bindings = [b for b in _SHRINK
              if "mesh_shape" not in b and "batch_size" not in b]
  bindings.extend(_EXTRA["train_bcz_pp.gin"])
  bindings.append(f"train_eval_model.model_dir = {model_dir!r}")
  bindings.append("DefaultRandomInputGenerator.batch_size = 8")
  config.parse_config_files_and_bindings([config_path], bindings)
  metrics = train_eval.train_eval_model()
  assert metrics
  assert_output_files(model_dir, expect_operative_config=False)


def test_sp_ring_config_trains_on_mesh(tmp_path):
  """SP through the full training path: train_sp_ring.gin trains the
  causal ring-attention model through train_eval_model on a
  ('data', 'sp', 'model') = (2, 2, 1) mesh, sequence batches sharded
  over 'sp' at infeed."""
  config_path = os.path.join(REPO_ROOT, "tensor2robot_tpu", "configs",
                             "train_sp_ring.gin")
  model_dir = str(tmp_path / "sp")
  bindings = [b for b in _SHRINK
              if "mesh_shape" not in b and "batch_size" not in b]
  bindings.append(f"train_eval_model.model_dir = {model_dir!r}")
  # train_and_evaluate: the in-loop eval must place batches with the
  # model's ('data', 'sp') batch_partition_spec too (regression guard —
  # it once used the default 'data'-only placement and mismatched the
  # eval step's committed in_shardings).
  bindings.append("train_eval_model.mode = 'train_and_evaluate'")
  bindings.append("train_eval_model.input_generator_eval = "
                  "@eval/DefaultRandomInputGenerator()")
  config.parse_config_files_and_bindings([config_path], bindings)
  metrics = train_eval.train_eval_model()
  assert metrics
  assert any(k.startswith("eval/") for k in metrics), metrics
  assert_output_files(model_dir, expect_operative_config=False)


def test_longcontext_flash_config_trains(tmp_path):
  """train_longcontext_flash.gin ships on the Pallas flash backend (the
  v5e compiler prices it ~4.6x under XLA attention at the shipped
  T=4096 shape — AOT_ANALYSIS_r05.json seqattn). Smoke-shrunk on CPU
  the kernel runs in interpret mode, so the flash code path itself is
  exercised through the full training loop."""
  config_path = os.path.join(REPO_ROOT, "tensor2robot_tpu", "configs",
                             "train_longcontext_flash.gin")
  model_dir = str(tmp_path / "flash")
  bindings = list(_SHRINK)
  bindings.extend([
      f"train_eval_model.model_dir = {model_dir!r}",
      "SequenceRegressionModel.sequence_length = 128",
      "SequenceRegressionModel.hidden_size = 32",
      "SequenceRegressionModel.num_heads = 4",
      "SequenceRegressionModel.device_type = 'cpu'",
      "SequenceRegressionModel.use_bfloat16 = False",
  ])
  config.parse_config_files_and_bindings([config_path], bindings)
  metrics = train_eval.train_eval_model()
  assert metrics
  assert_output_files(model_dir, expect_operative_config=False)


def test_actor_configs_drive_collect_loop(tmp_path):
  """Non-trainer (actor-side) configs run the collect/eval loop and
  write replay records."""
  from tensor2robot_tpu.data import tfrecord
  from tensor2robot_tpu.envs import run_env

  assert ACTOR_CONFIGS, "expected at least one actor config"
  for config_path in ACTOR_CONFIGS:
    config.clear_config()
    root = str(tmp_path / os.path.basename(config_path))
    config.parse_config_files_and_bindings(
        [config_path], [f"collect_eval_loop.root_dir = {root!r}"])
    stats = run_env.collect_eval_loop()
    assert "collect/episode_reward_mean" in stats
    replays = glob.glob(os.path.join(root, "policy_collect", "*.tfrecord"))
    assert replays, f"{config_path} wrote no replay records"
    assert tfrecord.count_records(replays[0]) > 0


def test_config_runs_in_fresh_process(tmp_path):
  """Guards against configs that only work due to test-process import
  pollution: the trainer CLI must self-register every configurable."""
  import subprocess
  import sys

  model_dir = str(tmp_path / "fresh")
  code = f"""
import jax; jax.config.update('jax_platforms', 'cpu')
import sys
sys.argv = ['t',
  '--config_files', {ALL_CONFIGS[0]!r},
  '--config', "train_eval_model.model_dir = {model_dir!r}",
  '--config', 'train_eval_model.max_train_steps = 2',
  '--config', 'train_eval_model.eval_steps = 1',
  '--config', 'train_eval_model.eval_every_n_steps = 2',
  '--config', 'train_eval_model.checkpoint_every_n_steps = 2',
  '--config', 'train_eval_model.log_every_n_steps = 1',
  '--config', 'train_eval_model.mesh_shape = (1, 1, 1)',
  '--config', 'DefaultRandomInputGenerator.batch_size = 2']
from absl import app
from tensor2robot_tpu.bin import run_t2r_trainer
app.run(run_t2r_trainer.main)
"""
  result = subprocess.run(
      [sys.executable, "-c", code], capture_output=True, text=True,
      timeout=240, env={**os.environ, "PYTHONPATH": REPO_ROOT,
                        "JAX_PLATFORMS": "cpu"})
  assert result.returncode == 0, result.stderr[-2000:]
  assert os.path.isdir(os.path.join(model_dir, "checkpoints"))


def test_loop_config_runs_in_fresh_process(tmp_path):
  """ISSUE 14: `configs/loop_qtopt.gin` drives the full supervised
  actor/learner loop through the `run_graftloop` CLI in a FRESH process
  — the configurable-import enforcement (every referenced configurable
  resolvable without test-process import pollution) covers the loop
  entry binary too, and the loop's own audit invariants hold on the
  config-driven path."""
  import json
  import subprocess
  import sys

  model_dir = str(tmp_path / "loop")
  config_path = os.path.join(REPO_ROOT, "tensor2robot_tpu", "configs",
                             "loop_qtopt.gin")
  code = f"""
import jax; jax.config.update('jax_platforms', 'cpu')
import sys
sys.argv = ['t',
  '--config_files', {config_path!r},
  '--config', "run_graftloop.model_dir = {model_dir!r}",
  '--config', 'run_graftloop.steps_per_round = 4',
  '--config', 'run_graftloop.num_rounds = 1',
  '--config', 'run_graftloop.num_replicas = 1',
  '--config', 'run_graftloop.wall_timeout_s = 200.0']
from absl import app
from tensor2robot_tpu.bin import run_graftloop
app.run(run_graftloop.main)
"""
  result = subprocess.run(
      [sys.executable, "-c", code], capture_output=True, text=True,
      timeout=240, env={**os.environ, "PYTHONPATH": REPO_ROOT,
                        "JAX_PLATFORMS": "cpu"})
  assert result.returncode == 0, result.stderr[-3000:]
  summary = json.loads(result.stdout.strip().splitlines()[-1])
  assert summary["episodes"] > 0
  assert summary["unverified_served"] == []
  assert summary["staleness_bound_held"]
  assert summary["worker_escalations"] == 0
  assert os.path.isdir(os.path.join(model_dir, "checkpoints"))


@pytest.mark.parametrize(
    "config_name,extra_args",
    [("serve_fleet.gin", ["--model", "flagship"]),
     ("loop_qtopt.gin", [])],
    ids=["serve_fleet", "loop_qtopt"])
def test_shipped_configs_audit_clean(config_name, extra_args):
  """ISSUE 16: `graftscope audit` traces every jit entry point the
  shipped deployment configs build (fleet bucket rungs across placed
  replicas; the loop's serve rungs AND its gated train step) and must
  report ZERO jaxpr-audit findings — the same permanently-clean
  contract test_repo_clean pins for file rules.

  The parent runs under the poisoned JAX_PLATFORMS (any backend init in
  the enumeration/report half raises); tracing happens in the audit
  worker subprocess, which self-pins CPU (GRAFTAUDIT_PLATFORM) — that
  discipline is what keeps the audit off the chip entirely."""
  import subprocess
  import sys

  config_path = os.path.join(REPO_ROOT, "tensor2robot_tpu", "configs",
                             config_name)
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "graftlint_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-m", "tensor2robot_tpu.bin.graftscope", "audit",
       config_path] + extra_args,
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
  # rc 0 == no findings AND no per-target trace errors (1 = findings/
  # errors, 2 = enumeration failure).
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "0 finding(s) after suppressions" in result.stdout
