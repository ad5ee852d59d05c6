"""Tests for graftscope-xray (`obs/xray.py`) and the run history
(`obs/runlog.py`) + `bin.graftscope` diff/history CLI.

Contracts (on the forced 8-device virtual CPU mesh, conftest.py):

* `analyze_jit` reads the REAL XLA cost analysis: a known matmul's
  FLOPs are exactly 2*M*K*N, and the train step's declared donated
  bytes equal the TrainState pytree's byte size (semantic, not shape);
* `memory_accounting` prices sharded leaves per shard (data-sharded
  batch = global/8) and replicated leaves at full bytes per device;
* `runs.jsonl` records round-trip exactly, carry their schema version
  (tier-1), and corrupt lines are skipped with a warning counter;
* `diff_records` is direction-aware (a throughput GAIN never flags)
  and `graftscope diff` on two real CPU-mesh train runs reports
  compile-time / FLOPs-per-step / memory-watermark / examples-per-sec
  deltas and exits 3 on an injected regression beyond threshold
  (ISSUE 3 acceptance).
"""

import json
import os

import jax
import numpy as np
import pytest

from tensor2robot_tpu import train_eval
from tensor2robot_tpu.bin import graftscope
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import runlog
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.obs import xray
from tensor2robot_tpu.parallel import mesh as mesh_lib
from tensor2robot_tpu.parallel import train_step as ts
from tensor2robot_tpu.utils import backend as backend_lib
from tensor2robot_tpu.utils import config, mocks
from tensor2robot_tpu import modes


@pytest.fixture(autouse=True)
def _hermetic_graftscope_state():
  """Fresh process-wide graftscope state per test: the global metrics
  registry is SWAPPED (snapshot/restore via `metrics.isolated`, so
  other suites' counters survive), the tracer and the xray compile
  collector cleared."""
  with metrics_lib.isolated():
    trace_lib.clear()
    trace_lib.disable()
    xray.clear_records()
    yield
  trace_lib.clear()
  trace_lib.disable()
  xray.clear_records()


@pytest.fixture(autouse=True)
def _clean_config():
  config.clear_config()
  yield
  config.clear_config()


# ---------------------------------------------------------------------------
# Compile telemetry: cost analysis semantics.
# ---------------------------------------------------------------------------


class TestAnalyzeJit:

  def test_matmul_cost_analysis_flops_exact(self):
    m, k, n = 256, 128, 64
    fn = jax.jit(lambda a, b: a @ b)
    a = np.ones((m, k), np.float32)
    b = np.ones((k, n), np.float32)
    compiled, record = xray.analyze_jit("test/matmul", fn, a, b)
    # XLA prices a dense [M,K]x[K,N] matmul at exactly 2*M*K*N flops.
    assert record["flops"] == 2 * m * k * n
    # Bytes accessed covers at least both operands and the output.
    assert record["bytes_accessed"] >= a.nbytes + b.nbytes + 4 * m * n
    assert record["arithmetic_intensity"] == pytest.approx(
        record["flops"] / record["bytes_accessed"])
    assert record["roofline_ms"] > 0
    assert record["jaxpr_eqns"] >= 1
    assert record["compile_s"] > 0 and record["trace_s"] >= 0
    assert record["donated_bytes"] == 0.0  # nothing declared donated
    assert record["undonated_bytes"] == a.nbytes + b.nbytes
    # The returned executable computes the same function.
    np.testing.assert_allclose(np.asarray(compiled(a, b)), a @ b)
    # The collector carries the analysis, the registry counts it; the
    # record is the one place its numbers live (no `xray/<name>/...`
    # gauges since PR 37: nothing read them).
    assert [r["name"] for r in xray.records()] == ["test/matmul"]
    assert xray.records()[0]["flops"] == record["flops"]
    snap = metrics_lib.snapshot()
    assert not [k for k in snap if k.startswith("gauge/xray/")]
    assert snap["counter/xray/analyses"] == 1.0

  def test_train_step_donated_bytes_match_state_pytree(self):
    """The train step donates its TrainState (arg 0): the declared
    donated bytes must equal the state pytree's byte size, and the
    batch (undonated) accounts for the rest."""
    model = mocks.MockT2RModel(device_type="cpu")
    generator = mocks.MockInputGenerator(batch_size=8)
    generator.set_specification_from_model(model, modes.TRAIN)
    batch = next(generator.create_dataset(modes.TRAIN))
    mesh = mesh_lib.create_mesh()
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), batch["features"], mesh=mesh)
    step = ts.make_train_step(model, mesh=mesh, shardings=shardings)
    features, labels = mesh_lib.place_batch(mesh, batch)
    _, record = xray.analyze_jit("test/train_step", step,
                                 state, features, labels)
    state_bytes = sum(leaf.nbytes
                      for leaf in jax.tree_util.tree_leaves(state))
    batch_bytes = sum(leaf.nbytes for leaf in
                      jax.tree_util.tree_leaves((features, labels)))
    assert record["donated_bytes"] == state_bytes
    assert record["undonated_bytes"] == batch_bytes
    # The step does real math: non-zero flops, a real jaxpr.
    assert record["flops"] > 0
    assert record["jaxpr_eqns"] > 10

  def test_xrayed_function_lazy_records_once_and_executes(self):
    fn = jax.jit(lambda x: x * 2.0)
    wrapped = xray.XrayedFunction("test/double", fn)
    x = np.arange(4.0, dtype=np.float32)
    np.testing.assert_allclose(np.asarray(wrapped(x)), x * 2.0)
    assert len(xray.records()) == 1
    np.testing.assert_allclose(np.asarray(wrapped(x)), x * 2.0)
    assert len(xray.records()) == 1  # analyzed exactly once

  def test_xrayed_function_falls_back_on_unanalyzable_fn(self):
    wrapped = xray.XrayedFunction("test/plain", lambda x: x + 1)
    assert wrapped(1) == 2  # no .trace: analysis fails, call survives
    assert xray.records() == []
    assert metrics_lib.snapshot()["counter/xray/analyze_failures"] == 1.0

  def test_xrayed_function_falls_back_on_shape_change(self):
    fn = jax.jit(lambda x: x + 1.0)
    wrapped = xray.XrayedFunction("test/reshape", fn)
    small = np.zeros((2,), np.float32)
    big = np.zeros((5,), np.float32)
    assert np.asarray(wrapped(small)).shape == (2,)
    # The frozen AOT executable rejects the new shape; the wrapper must
    # degrade to the plain jit, not raise.
    assert np.asarray(wrapped(big)).shape == (5,)
    snap = metrics_lib.snapshot()
    assert snap["counter/xray/compiled_call_fallbacks"] == 1.0


# ---------------------------------------------------------------------------
# Memory accounting.
# ---------------------------------------------------------------------------


class TestMemoryAccounting:

  def test_sharded_batch_counts_per_shard_replicated_counts_full(self):
    mesh = mesh_lib.create_mesh()  # (8, 1, 1) data mesh
    from jax.sharding import NamedSharding, PartitionSpec

    sharded = jax.device_put(
        np.zeros((16, 4), np.float32),
        NamedSharding(mesh, PartitionSpec("data")))
    replicated = jax.device_put(np.zeros((3, 3), np.float32),
                                NamedSharding(mesh, PartitionSpec()))
    assert xray.pytree_bytes({"a": sharded}) == 16 * 4 * 4
    assert xray.pytree_shard_bytes({"a": sharded}) == 16 * 4 * 4 // 8
    assert xray.pytree_shard_bytes({"b": replicated}) == 3 * 3 * 4

  def test_host_batch_divided_by_data_shards(self):
    batch = {"x": np.zeros((32, 2), np.float32)}
    out = xray.memory_accounting(batch=batch, num_data_shards=8)
    assert out["batch_bytes"] == 32 * 2 * 4
    assert out["batch_bytes_per_shard"] == 32 * 2 * 4 // 8

  def test_train_state_accounting_and_watermark(self):
    model = mocks.MockT2RModel(device_type="cpu")
    generator = mocks.MockInputGenerator(batch_size=8)
    generator.set_specification_from_model(model, modes.TRAIN)
    batch = next(generator.create_dataset(modes.TRAIN))
    mesh = mesh_lib.create_mesh()
    state, _ = ts.create_train_state(
        model, jax.random.PRNGKey(0), batch["features"], mesh=mesh)
    memory = xray.memory_accounting(state, batch=batch,
                                    num_data_shards=8)
    params_bytes = sum(leaf.nbytes for leaf in
                       jax.tree_util.tree_leaves(state.params))
    assert memory["params_bytes"] == params_bytes
    assert memory["state_bytes"] >= params_bytes  # + step/opt/ema/rng
    assert memory["batch_bytes"] > 0
    temp = memory["params_bytes_per_shard"] + 1000.0  # temp wins the max
    watermark = xray.hbm_watermark_estimate(
        memory, [{"temp_bytes": temp}])
    assert watermark == (memory["state_bytes_per_shard"]
                         + memory["batch_bytes_per_shard"] + temp)
    # Without temp bytes the scratch floor is the param (grad) bytes.
    floor = xray.hbm_watermark_estimate(memory, [])
    assert floor == (memory["state_bytes_per_shard"]
                     + memory["batch_bytes_per_shard"]
                     + memory["params_bytes_per_shard"])

  def test_device_memory_stats_is_clientside_and_counts(self):
    anchor = jax.device_put(np.zeros((64,), np.float32))
    stats = backend_lib.device_memory_stats()
    assert stats["live_arrays"] >= 1
    assert stats["live_bytes"] >= anchor.nbytes


# ---------------------------------------------------------------------------
# Run history: schema round-trip, tolerant reader, diffing.
# ---------------------------------------------------------------------------


class TestRunlog:

  def _record(self, eps=1000.0, step_ms=10.0, watermark=1e9,
              compile_s=1.0, flops=5e9):
    return runlog.make_record(
        "train",
        platform="cpu",
        step_stats={"examples_per_sec_mean": eps, "step_ms_mean": step_ms},
        compile_records=[{"name": "train_step", "trace_s": 0.1,
                          "lower_s": 0.1, "compile_s": compile_s,
                          "jaxpr_eqns": 100, "flops": flops,
                          "bytes_accessed": 1e9}],
        memory={"hbm_watermark_bytes": watermark})

  def test_record_roundtrips_and_carries_schema_version(self, tmp_path):
    """Tier-1 (ISSUE 3 satellite): the runs.jsonl record schema
    round-trips through disk and is schema-versioned."""
    path = str(tmp_path / "runs.jsonl")
    first, second = self._record(), self._record(eps=2000.0)
    runlog.append_record(path, first)
    runlog.append_record(path, second)
    loaded = runlog.load_records(path)
    assert loaded == [first, second]  # exact round-trip, order kept
    for record in loaded:
      assert record["schema"] == runlog.SCHEMA == "graftscope-run-v1"
      assert record["schema_version"] == runlog.SCHEMA_VERSION == 1
      assert record["kind"] == "train" and record["run_id"]

  def test_corrupt_lines_skipped_with_warning_counter(self, tmp_path):
    path = tmp_path / "runs.jsonl"
    good = self._record()
    path.write_text(json.dumps(good) + "\n"
                    + '{"torn": \n'           # truncated tail line
                    + "\x00\x01 not json\n"   # binary garbage
                    + '"a bare string"\n'     # valid JSON, not a record
                    + json.dumps(good) + "\n")
    loaded = runlog.load_records(str(path))
    assert loaded == [good, good]
    assert metrics_lib.snapshot()["counter/runlog/corrupt_lines"] == 3.0

  def test_missing_file_is_empty_history(self, tmp_path):
    assert runlog.load_records(str(tmp_path / "absent.jsonl")) == []

  def test_diff_is_direction_aware(self):
    base = self._record()
    slower = self._record(eps=800.0, step_ms=12.5, watermark=1.5e9)
    deltas = {d["metric"]: d for d in runlog.diff_records(base, slower)}
    assert deltas["examples_per_sec"]["regressed"]       # -20% > 10%
    assert deltas["step_ms"]["regressed"]                # +25% > 10%
    assert deltas["hbm_watermark_bytes"]["regressed"]    # +50% > 10%
    assert not deltas["flops_per_step"]["regressed"]     # unchanged
    # Improvements never flag: faster + smaller is not a regression.
    faster = self._record(eps=2000.0, step_ms=5.0, watermark=0.5e9)
    assert not any(d["regressed"]
                   for d in runlog.diff_records(base, faster))

  def test_diff_threshold_overrides(self):
    base = self._record()
    slower = self._record(eps=800.0)
    loose = runlog.diff_records(
        base, slower, thresholds={"examples_per_sec": ("down", 0.5)})
    assert not next(d for d in loose
                    if d["metric"] == "examples_per_sec")["regressed"]

  def test_cross_platform_diff_warns_not_comparable(self):
    """A TPU round diffed against a CPU-smoke round (both land in one
    runs.jsonl) must shout that the deltas are not
    comparable instead of silently flagging a bogus regression."""
    tpu = runlog.make_record(
        "bench", platform="tpu",
        bench={"metric": "qtopt_grasps_per_sec_per_chip",
               "value": 2480.0, "unit": "examples/sec"})
    cpu = runlog.make_record(
        "bench", platform="cpu",
        bench={"metric": "qtopt_grasps_per_sec_cpu_smoke",
               "value": 3643.0, "unit": "examples/sec"})
    warnings = runlog.comparability_warnings(tpu, cpu)
    assert any("platform differs" in w for w in warnings)
    assert any("bench metric differs" in w for w in warnings)
    out = runlog.format_diff(tpu, cpu,
                             runlog.diff_records(tpu, cpu))
    assert "WARNING" in out and "not be comparable" in out
    # Same-platform train runs warn about nothing.
    assert runlog.comparability_warnings(
        self._record(), self._record()) == []

  def test_metric_in_only_one_record_listed_not_flagged(self):
    base = self._record()
    bare = runlog.make_record("train",
                              step_stats={"step_ms_mean": 10.0})
    deltas = {d["metric"]: d for d in runlog.diff_records(base, bare)}
    assert deltas["examples_per_sec"]["rel"] is None
    assert not deltas["examples_per_sec"]["regressed"]

  def test_resolve_run_selectors(self, tmp_path):
    path = str(tmp_path / "runs.jsonl")
    first, second = self._record(), self._record(eps=2000.0)
    runlog.append_record(path, first)
    runlog.append_record(path, second)
    assert runlog.resolve_run(path)[0] == second            # latest
    assert runlog.resolve_run(f"{path}#0")[0] == first      # index
    assert runlog.resolve_run(f"{path}#-2")[0] == first     # negative
    assert runlog.resolve_run(                              # run_id
        f"{path}#{first['run_id']}")[0] == first
    assert runlog.resolve_run(str(tmp_path))[0] == second   # model_dir
    with pytest.raises(runlog.RunResolveError):
      runlog.resolve_run(f"{path}#no-such-run")
    with pytest.raises(runlog.RunResolveError):
      runlog.resolve_run(str(tmp_path / "missing"))


# ---------------------------------------------------------------------------
# Acceptance: diff of two CPU-mesh train runs + injected regression.
# ---------------------------------------------------------------------------


class TestGraftscopeDiffCLI:

  def _train(self, model_dir):
    from tensor2robot_tpu.obs import excache

    config.clear_config()
    # Both runs must COMPILE their step: with the shared cache root the
    # second would load the first one's (compile_s == 0), and jax's own
    # persistent cache would serve its backend compile.
    with excache.xla_cache_bypassed():
      return train_eval.train_eval_model(
          model=mocks.MockT2RModel(device_type="cpu"),
          model_dir=model_dir,
          mode="train",
          max_train_steps=4,
          checkpoint_every_n_steps=100,
          input_generator_train=mocks.MockInputGenerator(batch_size=8),
          log_every_n_steps=2,
          executable_cache_dir=None)

  def _inject_regression(self, model_dir, eps_scale=0.1,
                         watermark_scale=10.0, compile_scale=10.0):
    path = os.path.join(model_dir, runlog.RUNS_FILENAME)
    (record,) = runlog.load_records(path)
    record["step_stats"]["examples_per_sec_mean"] *= eps_scale
    record["memory"]["hbm_watermark_bytes"] *= watermark_scale
    for compile_record in record["compile"]:
      compile_record["compile_s"] *= compile_scale
      compile_record["flops"] *= 2.0
    with open(path, "w") as f:
      f.write(json.dumps(record) + "\n")

  def test_diff_reports_deltas_and_flags_injected_regression(
      self, tmp_path, capsys):
    """ISSUE 3 acceptance: diff on two CPU-mesh runs produced in-test
    reports compile-time / FLOPs-per-step / memory-watermark /
    examples-per-sec deltas and flags an injected regression."""
    dir_a, dir_b = str(tmp_path / "a"), str(tmp_path / "b")
    self._train(dir_a)
    self._train(dir_b)
    # Both runs recorded real telemetry.
    for model_dir in (dir_a, dir_b):
      (record,) = runlog.load_records(
          os.path.join(model_dir, runlog.RUNS_FILENAME))
      assert record["schema_version"] == runlog.SCHEMA_VERSION
      assert record["compile"][0]["name"] == "train_step"
      assert record["compile"][0]["flops"] > 0
      assert record["memory"]["hbm_watermark_bytes"] > 0
      assert record["step_stats"]["examples_per_sec_mean"] > 0
    self._inject_regression(dir_b)
    rc = graftscope.main(["diff", dir_a, dir_b])
    out = capsys.readouterr().out
    assert rc == 3  # regression beyond threshold
    assert "REGRESSED" in out
    # All four acceptance metric families are present in the diff.
    for metric in ("compile_time_s", "flops_per_step",
                   "hbm_watermark_bytes", "examples_per_sec", "step_ms"):
      assert metric in out, out
    regressed = {line.split()[0] for line in out.splitlines()
                 if "REGRESSED" in line}
    assert {"examples_per_sec", "hbm_watermark_bytes",
            "compile_time_s", "flops_per_step"} <= regressed

  def test_identical_records_diff_clean(self, tmp_path, capsys):
    model_dir = str(tmp_path / "a")
    self._train(model_dir)
    rc = graftscope.main(["diff", f"{model_dir}#-1", f"{model_dir}#-1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no regressions beyond thresholds" in out

  def test_history_lists_runs(self, tmp_path, capsys):
    model_dir = str(tmp_path / "a")
    self._train(model_dir)
    self._train(model_dir)  # second run appends (history grows)
    rc = graftscope.main(["history", model_dir])
    out = capsys.readouterr().out
    assert rc == 0
    assert "2 record(s)" in out
    assert "examples_per_sec=" in out

  def test_diff_missing_reference_exits_2(self, tmp_path, capsys):
    model_dir = str(tmp_path / "a")
    self._train(model_dir)
    missing = str(tmp_path / "nope")
    assert graftscope.main(["diff", missing, model_dir]) == 2
    err = capsys.readouterr().err
    assert "nope" in err

  def test_report_includes_xray_and_run_history(self, tmp_path, capsys):
    model_dir = str(tmp_path / "a")
    self._train(model_dir)
    assert graftscope.main([model_dir]) == 0
    out = capsys.readouterr().out
    assert "run history" in out
    assert "xray compile telemetry" in out
    assert "train_step" in out
    assert "hbm_watermark" in out
