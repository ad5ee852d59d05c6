"""CPU rehearsal of `chip_smoke.py` (ISSUE 22 §2): every phase at a tiny
size through the very functions the chip run calls, the `--multichip`
phases on four virtual devices, and the contract's refusals — no TPU, a
failed phase or a wrong device count means a non-zero exit and no
`"ok": true` line.

The sizes are steered HERE (config bindings handed to the phase
functions); the script itself has no option that makes it a CPU run.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402

TINY_CRITIC = (
    "QTOptModel.network = 'small'",
    "QTOptModel.image_size = 32",
    "QTOptModel.action_size = 4",
    "QTOptModel.grasp_param_names = None",
    "QTOptModel.device_type = 'cpu'",
    "DefaultRandomInputGenerator.batch_size = 8",
)
TINY_SEQUENCE = (
    "SequenceRegressionModel.sequence_length = 64",
    "SequenceRegressionModel.hidden_size = 32",
    "SequenceRegressionModel.num_heads = 4",
    "SequenceRegressionModel.device_type = 'cpu'",
    "DefaultRandomInputGenerator.batch_size = 8",
)
# Two key heads of 128 for the four value heads: the smallest sizes the
# rule's kernels take (the phase runs them interpreted at T 128).
TINY_HYBRID = (
    "HybridDecoderLM.sequence_length = 128",
    "HybridDecoderLM.linear_num_value_heads = 4",
    "HybridDecoderLM.linear_num_key_heads = 2",
    "HybridDecoderLM.device_type = 'cpu'",
    "DefaultRandomInputGenerator.batch_size = 2",
)
# The smallest sizes the scan's kernels take (heads of 64 two to a
# 128-lane block, N 128, chunks of 128): the phase's kernel comparison runs
# them interpreted at T 512; its recurrence check runs T / 8 = 64 tokens.
TINY_MAMBA = (
    "HybridDecoderLM.sequence_length = 512",
    "HybridDecoderLM.mamba_num_heads = 4",
    "HybridDecoderLM.mamba_head_dim = 64",
    "HybridDecoderLM.n_groups = 2",
    "HybridDecoderLM.ssm_state_size = 128",
    "HybridDecoderLM.chunk_size = 128",
    "DefaultRandomInputGenerator.batch_size = 1",
)
TINY_EXPERTS = (
    "HybridDecoderLM.sequence_length = 128",
    "HybridDecoderLM.hidden_size = 128",
    "HybridDecoderLM.n_routed_experts = 8",
    "HybridDecoderLM.experts_held = (0, 4)",
    "HybridDecoderLM.num_experts_per_tok = 2",
    "HybridDecoderLM.moe_intermediate_size = 116",
    "HybridDecoderLM.expert_buffer_factor = 2.0",
    "DefaultRandomInputGenerator.batch_size = 2",
)
# Windowed attention at the gin's head width and window over 256 tokens:
# blocks of 128 (the length's), so the band's lower edge lies inside tiles
# and the loops skip the tiles below it.
TINY_WINDOW = (
    "HybridDecoderLM.sequence_length = 256",
    "HybridDecoderLM.sliding_window = 40",
    "DefaultRandomInputGenerator.batch_size = 1",
)
CPU8 = ("cpu", 8)


@pytest.fixture(scope="module")
def out_dir(tmp_path_factory):
  return str(tmp_path_factory.mktemp("chip_smoke"))


@pytest.fixture(scope="module")
def trained(out_dir):
  """Phase 1 at tiny size; later phases read what it leaves on disk."""
  result = chip_smoke.phase_train(out_dir, TINY_CRITIC, device=CPU8)
  with open(os.path.join(out_dir, "train.json"), "w") as f:
    json.dump(result, f, default=float)
  return result


class TestPhaseRehearsal:

  def test_train_phase(self, trained):
    assert trained["ok"] and trained["device"]["platform"] == "cpu"
    assert trained["steps"] == [1, 2, 3, 4, 5, 6]
    assert trained["final_step"] == chip_smoke.TRAIN_STEPS
    assert any(k.startswith("eval/") for k in trained["final_metrics"])
    assert os.path.isdir(trained["export_bundle"])
    assert trained["train_step_compile"]["cache"]["stored"] is True
    assert trained["seconds_to_first_step"] > 0

  def test_resume_phase_loads_the_train_step_from_the_cache(
      self, trained, out_dir):
    result = chip_smoke.phase_resume(out_dir, TINY_CRITIC, device=CPU8)
    assert result["steps"] == [7, 8]
    assert result["train_step_compile"]["cache"]["hit"] is True
    assert result["graftcache"]["counter/cache/misses"] == 0
    assert (result["train_step_build_seconds"]
            < result["train_step_build_seconds_cold"])

  def test_resume_phase_after_a_warm_first_start(self, trained, tmp_path):
    """A machine that kept the cache from an earlier run: the first start
    loads the train step too, so no clock separates it from the resume.
    The hit is the whole proof."""
    out = str(tmp_path)
    warm = chip_smoke.phase_train(out, TINY_CRITIC, device=CPU8)
    assert warm["train_step_compile"]["cache"]["hit"] is True
    with open(os.path.join(out, "train.json"), "w") as f:
      json.dump(warm, f, default=float)
    result = chip_smoke.phase_resume(out, TINY_CRITIC, device=CPU8)
    assert result["ok"] and result["steps"] == [7, 8]
    assert result["train_step_compile"]["cache"]["hit"] is True

  def test_serve_phase(self, trained, out_dir):
    result = chip_smoke.phase_serve(out_dir, device=CPU8)
    assert result["compiles_after_warmup"] == 0
    assert result["answered"] + sum(result["errors"].values()) \
        == result["requests"]
    assert result["buckets"] == [1, 2, 4, 8, 16]
    assert result["global_step"] >= chip_smoke.TRAIN_STEPS
    # Here the parities have something to disagree about — this critic
    # tells the probe's rows and CEM's actions apart by more than the
    # tolerance — and still agree.
    probe, cem = result["probe"], result["cem"]
    tolerance = chip_smoke.SERVE_RTOL * probe["max_abs_value"]
    assert probe["max_abs_error"] <= tolerance < probe["spread_over_rows"]
    assert abs(cem["score"] - cem["served"]) <= tolerance \
        < cem["served_spread_over_actions"]

  def test_kernels_phase(self, out_dir):
    result = chip_smoke.phase_kernels(out_dir, TINY_SEQUENCE, device=CPU8)
    flash, decode = result["flash"], result["decode"]
    assert len(flash["losses"]["flash"]) == 2
    # The key a second look computes is the trainer's own.
    assert flash["cache"]["hit"] is True
    # Off the TPU the gate stays off by itself and the kernels run
    # interpreted: parity is what the rehearsal can show.
    assert decode["arms"]["auto"]["decode_kernel_active"] is False
    assert decode["horizon"] == 32 and decode["lanes"] == 8
    assert max(decode["max_abs_error"].values()) <= 1e-4

  def test_delta_rule_phase(self, out_dir):
    result = chip_smoke.phase_delta_rule(out_dir, TINY_HYBRID, device=CPU8)
    assert result["shape"] == [2, 2, 4, 64, 64] and result["interpreted"]
    for part, gap in result["max_abs_error"].items():
      assert gap <= result["tolerance"] * result["max_abs_entry"][part]
    kernels = result["kernels"]
    assert kernels["length"] == 128
    assert set(kernels["relative_error"]) == {"o", "state", "q", "k", "v",
                                              "g", "beta"}
    assert max(kernels["relative_error"].values()) <= kernels["tolerance"]

  def test_state_space_phase(self, out_dir):
    result = chip_smoke.phase_state_space(out_dir, TINY_MAMBA, device=CPU8)
    # 64 tokens in a chunk of 128: the chunk is padded
    assert result["shape"] == {"batch": 1, "length": 64, "heads": 4,
                               "head_dim": 64, "groups": 2, "state": 128,
                               "chunk": 128}
    for kind, errors in result["relative_error"].items():
      assert set(errors) == {"values", "dx", "ddt", "db", "dc"}
      assert max(errors.values()) <= result["tolerance"][kind]
    # the bfloat16 arm rounds its operands: it is not the float32 arm again
    assert (result["relative_error"]["bfloat16"]["values"]
            > 10 * result["relative_error"]["float32"]["values"])
    # the kernels (interpreted) against the XLA form, every cotangent
    kernels = result["kernels"]
    assert kernels["length"] == 512
    assert set(kernels["relative_error"]) == {
        "y", "state", "x", "b", "c", "dt", "a_log", "d"}
    assert max(kernels["relative_error"].values()) <= kernels["tolerance"]

  def test_grouped_matmul_phase(self, out_dir):
    result = chip_smoke.phase_grouped_matmul(out_dir, TINY_EXPERTS,
                                             device=CPU8)
    shape = result["shape"]
    # 256 tokens x 2 picks, half the experts held, twice the balanced load
    assert (shape["rows"], shape["groups"], shape["hidden"],
            shape["width"]) == (512, 4, 128, 116)
    assert sum(shape["group_sizes"]) == 512
    assert sum(shape["group_sizes"][:-1]) < 256   # the tail holds the rest
    for product in ("up", "down"):
      errors = result["relative_error"][product]
      assert errors["values"] <= result["tolerance"]["values"]
      assert max(errors["dlhs"], errors["drhs"]) \
          <= result["tolerance"]["cotangents"]
      # the cotangents leave rounded to bfloat16: not the float32 sum again
      assert errors["drhs"] > 10 * errors["values"]

  def test_window_phase(self, out_dir):
    result = chip_smoke.phase_window(out_dir, TINY_WINDOW, device=CPU8)
    assert result["shape"] == {"batch": 1, "length": 256, "heads": 8,
                               "head_dim": 128, "window": 40}
    errors = result["relative_error"]
    assert set(errors) == {"out", "dq", "dk", "dv"}
    assert max(errors.values()) <= result["tolerance"]
    # bfloat16 operands against a float32 reference: not equal by accident
    assert min(errors.values()) > 1e-4

  def test_barrier_phase(self, trained, out_dir):
    result = chip_smoke.phase_barrier(out_dir, TINY_CRITIC, device=CPU8)
    # It timed the step the trainer ran, not a look-alike.
    assert result["train_step_cache"]["hit"] is True
    for close in ("block_until_ready", "host_fetch"):
      assert result[close]["closed_s_per_step"] > 0
    assert result["block_until_ready_over_host_fetch"] > 0


_MULTICHIP_CHILD = """
import json, sys
import chip_smoke
result = getattr(chip_smoke, sys.argv[1])(
    sys.argv[2], json.loads(sys.argv[3]), device=("cpu", 4))
print("RESULT " + json.dumps(result, default=float))
"""


@pytest.mark.parametrize("phase,bindings", [
    ("phase_multichip_dp", TINY_CRITIC),
    ("phase_multichip_sp", TINY_SEQUENCE),
])
def test_multichip_phase_on_four_virtual_devices(tmp_path, phase, bindings):
  env = {**os.environ, "PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
  done = subprocess.run(
      [sys.executable, "-c", _MULTICHIP_CHILD, phase, str(tmp_path),
       json.dumps(list(bindings))],
      capture_output=True, text=True, timeout=900, cwd=REPO_ROOT, env=env)
  assert done.returncode == 0, done.stderr[-3000:]
  (line,) = [l for l in done.stdout.splitlines() if l.startswith("RESULT ")]
  result = json.loads(line[len("RESULT "):])
  assert result["ok"] and result["device"]["count"] == 4
  if phase == "phase_multichip_dp":
    assert result["four_chips"]["spread"]["sharded_param_leaves"] > 0
    assert len(result["four_chips"]["losses"]) == chip_smoke.MULTICHIP_STEPS
  else:
    assert set(result) >= {"ring", "ulysses_flash", "one_chip_reference"}


class TestContractRefusals:

  def test_script_fails_and_prints_no_ok_without_a_tpu(
      self, monkeypatch, tmp_path, capfd):
    """The driver's own call on this CPU-only machine: the real `main`
    and a real child, writing under a temporary output directory so that
    the last chip run's records under `chiprun_out/` stay."""
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert chip_smoke.main([]) != 0
    out = capfd.readouterr().out
    assert '"ok": true' not in out
    assert "needs 1 tpu device" in out  # the train phase's refusal
    # It stopped at the first phase: nothing after it was started.
    assert out.count('"phase"') == 1

  @pytest.mark.parametrize("results", [
      # a phase raised
      {"kernels": {"phase": "kernels", "ok": False, "error": "boom"}},
      # every phase passed, on the wrong platform
      {name: {"phase": name, "ok": True,
              "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
       for name in chip_smoke.ONE_CHIP_PHASES},
      # four chips where the one-chip mode was asked for
      {name: {"phase": name, "ok": True,
              "device": {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 4}}
       for name in chip_smoke.ONE_CHIP_PHASES},
  ], ids=["phase-failed", "not-a-tpu", "wrong-count"])
  def test_main_refuses(self, results, monkeypatch, tmp_path, capsys):
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(
        chip_smoke, "_run_child",
        lambda name: results.get(
            name, {"phase": name, "ok": True, "device": tpu}))
    assert chip_smoke.main([]) == 1
    assert '"ok": true' not in capsys.readouterr().out

  def test_main_prints_the_device_line_last_when_all_passed(
      self, monkeypatch, tmp_path, capsys):
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path / "out"))
    monkeypatch.setattr(
        chip_smoke, "_run_child",
        lambda name: {"phase": name, "ok": True, "device": tpu})
    assert chip_smoke.main(["--multichip"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": tpu}
    assert chip_smoke.main(["--cpu"]) == 2  # no other option exists


# A path of the checkout as a script names it; a sentence's full stop
# is not part of it.
_SCRIPT_PATH = re.compile(
    r"\b(?:tensor2robot_tpu|tests|scripts|benchmarks|docs)(?:/[\w.-]*\w)+")
_SCRIPT_IMPORT = re.compile(
    r"^\s*from (tensor2robot_tpu[\w.]*) import (\w+)", re.M)


@pytest.mark.parametrize("script", ["scripts/lint.sh",
                                    "scripts/obs_report.sh"])
def test_shell_wrapper_parses_and_names_what_exists(script):
  """All that `scripts/` holds: the shell parses it (`bash -n`), every
  path of the checkout it names is there, and so is every module its
  embedded Python imports."""
  path = os.path.join(REPO_ROOT, script)
  checked = subprocess.run(["bash", "-n", path], capture_output=True,
                           text=True)
  assert checked.returncode == 0, checked.stderr
  with open(path) as f:
    text = f.read()
  named = set(_SCRIPT_PATH.findall(text))
  assert script in named  # its own usage line, at the least
  for rel in sorted(named):
    assert os.path.exists(os.path.join(REPO_ROOT, rel)), (script, rel)
  imports = _SCRIPT_IMPORT.findall(text)
  assert imports
  for package, name in imports:
    base = os.path.join(REPO_ROOT, *package.split("."))
    assert (os.path.isfile(os.path.join(base, name + ".py"))
            or os.path.isfile(base + ".py")), (script, package, name)
