"""Tests for graftforge (`obs/forge.py`): the ahead-of-time compile
farm, its `graftscope forge` CLI, the donating-mesh round trip, the
warmup load/compile split, the rollout ladder pre-forge, and the
`warmup-unforgeable` lint rule.

Contracts (ISSUE 15):

* enumeration is spec-complete and BACKEND-FREE: `plan_from_config`
  lists every executable a research config deploys (bucket rungs x
  replicas, decode rungs + slot reset, train/eval steps with
  num_virtual_stages) without building a model or touching a backend,
  and plain-jit targets are enumerated as unforgeable with the reason
  attached;
* a forge entry is BYTE-IDENTICAL in key to what the live process
  computes: process A runs `graftscope forge` against an empty cache,
  process B builds the fleet and pins `engine_compiles == [0, 0]`,
  `cache_loads == ladder x replicas`, served-output parity vs a
  cold-built fleet, and every loaded key present in the manifest;
* an executable that DONATES mesh-sharded inputs — the trainer's step —
  round-trips through the cache and dispatches on placed arrays, so
  train targets are forgeable;
* `warmup_ms` splits into `warmup_load_ms`/`warmup_compile_ms` with
  per-rung provenance, so a forge regression is attributable;
* `rollout(ladder=...)` pre-forges new rungs inside the drained window
  (`engine.reladder`) before any replica swap.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tensor2robot_tpu.analysis import forge_check
from tensor2robot_tpu.analysis import lint as lint_lib
from tensor2robot_tpu.bin import graftscope
from tensor2robot_tpu.obs import excache
from tensor2robot_tpu.obs import forge
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import runlog
from tensor2robot_tpu.serving import engine as engine_lib
from tensor2robot_tpu.utils import config as config_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO_ROOT, "tensor2robot_tpu", "configs")


def _cfg(name):
  return os.path.join(CONFIGS, name)


@pytest.fixture(autouse=True)
def _hermetic():
  # plan_from_config parses research configs into the process-global
  # binding registry; leaked bindings would contaminate later tests.
  with metrics_lib.isolated():
    yield
  config_lib.clear_config()


def _mock_predictor():
  from tensor2robot_tpu.predictors import predictors as predictors_lib
  from tensor2robot_tpu.utils import mocks

  predictor = predictors_lib.CheckpointPredictor(
      model=mocks.MockT2RModel(device_type="cpu"),
      model_dir="/nonexistent")
  predictor.init_randomly()
  return predictor


# ---------------------------------------------------------------------------
# Enumeration: spec-complete plans for the four shipped deployments.
# ---------------------------------------------------------------------------


class TestPlanEnumeration:

  def test_serve_fleet_plan(self):
    plan = forge.plan_from_config([_cfg("serve_fleet.gin")])
    targets = plan["targets"]
    assert [t["family"] for t in targets] == ["serve", "serve"]
    for index, target in enumerate(targets):
      # max_batch_size 16 -> the doubling ladder; 2 PLACED replicas
      # (disjoint device groups -> per-replica keys -> one target each).
      assert target["buckets"] == [1, 2, 4, 8, 16]
      assert target["replica_index"] == index
      assert target["num_replicas"] == 2
      assert target["placed"] is True
      assert target["forgeable"] is True
      assert target["name"] == "serve/engine"
    # Serving-only config: no model binding — the CLI demands one.
    assert plan["model"] is None

  def test_serve_session_plan(self):
    plan = forge.plan_from_config([_cfg("serve_session.gin")])
    (target,) = plan["targets"]
    assert target["family"] == "session"
    assert target["buckets"] == [1, 2, 4, 8]
    assert target["max_sessions"] == 64
    assert target["executables"] == 5  # 4 decode rungs + slot reset
    assert target["forgeable"] is True

  def test_loop_plan_shares_one_entry_set_across_replicas(self):
    plan = forge.plan_from_config([_cfg("loop_qtopt.gin")])
    families = {t["family"]: t for t in plan["targets"]}
    serve = families["serve"]
    # The loop's fleet has NO device carve (devices=None): every
    # replica computes identical keys, so the plan forges ONE shared
    # `serve/loop` entry set — forge once, every replica deserializes.
    assert serve["name"] == "serve/loop"
    assert serve["buckets"] == [1, 2, 4, 8]
    assert serve["num_replicas"] == 2
    assert serve["placed"] is False
    train = families["train"]
    assert train["forgeable"] is True
    assert train["mesh_shape"] == [1, 1, 1]
    assert plan["model"] == {"kind": "configurable",
                             "name": "PoseEnvContinuousMCModel"}

  def test_pipelined_train_plan_enumerated(self):
    plan = forge.plan_from_config([_cfg("train_pipelined_1f1b.gin")])
    (train,) = plan["targets"]
    assert train["family"] == "train"
    assert train["num_virtual_stages"] == 2  # the 1F1B chunking
    assert train["mesh_shape"] == [2, 4, 1]
    assert train["forgeable"] is True
    assert plan["model"] == {"kind": "configurable",
                             "name": "PipelinedRegressionModel"}
    rendered = forge.format_plan(plan)
    assert "UNFORGEABLE" not in rendered and "v=2" in rendered

  def test_unbound_mesh_shape_records_default_not_single_device(self):
    # train_eval builds the all-devices default mesh when mesh_shape is
    # unbound — the worker must key THAT executable, not a one-chip one
    # (None is reserved for hand-built one-chip plans).
    plan = forge.plan_from_config(
        [_cfg("train_pipelined_1f1b.gin")],
        ["train_eval_model.mesh_shape = None"])
    (train,) = plan["targets"]
    assert train["mesh_shape"] == "default"
    assert "mesh default" in forge.format_plan(plan)

  def test_iterations_per_loop_enumerates_the_scan_loop_executable(self):
    # The K-step loop is a DIFFERENT program ([K, B] scan) than the
    # plain step — it gets its own target carrying loop_k so the worker
    # forges make_train_loop, never the plain step under the loop name.
    plan = forge.plan_from_config(
        [_cfg("train_pipelined_1f1b.gin")],
        ["train_eval_model.iterations_per_loop = 8"])
    names = {t["name"]: t for t in plan["targets"]}
    assert set(names) == {"train_step", "train_loop_k8"}
    assert "loop_k" not in names["train_step"]
    assert names["train_loop_k8"]["loop_k"] == 8
    assert "K=8 scan loop" in forge.format_plan(plan)

  def test_trainer_mode_with_eval_enumerates_eval_step(self):
    plan = forge.plan_from_config(
        [_cfg("train_pipelined_1f1b.gin")],
        ["train_eval_model.mode = 'train_and_evaluate'"])
    families = [t["family"] for t in plan["targets"]]
    assert families == ["train", "eval"]
    eval_target = plan["targets"][1]
    assert eval_target["forgeable"] is False
    assert "plain-jit" in eval_target["reason"]

  def test_ladder_twin_pinned_against_engine(self):
    # plan enumeration carries a local ladder (backend-free import
    # surface); it must never drift from the engine's.
    for max_batch in (1, 2, 3, 7, 8, 12, 16, 17):
      assert forge._bucket_ladder(max_batch) == \
          engine_lib.bucket_ladder(max_batch)


# ---------------------------------------------------------------------------
# Satellite: the donating-mesh round trip (no gate on jax 0.9.0).
# ---------------------------------------------------------------------------


class TestDonatingMeshRoundTrip:
  """The repro the old version-keyed gate documented — (1) a serialize
  round-trip of an executable that (2) DONATES inputs with a mesh-typed
  sharding, (3) dispatched on device_put arrays — run in-suite: on
  jax 0.9.0 it is clean, so the gate is gone and these executables are
  cached like any other."""

  @pytest.mark.parametrize("n_devices", [1, 8])
  def test_donating_mesh_executable_round_trips(self, tmp_path, n_devices):
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu.obs import xray

    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:n_devices]).reshape(n_devices), ("data",))
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    rows = jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec("data"))
    fn = jax.jit(lambda w, x: (w - 0.1 * jnp.mean(x, axis=0), jnp.sum(x)),
                 in_shardings=(repl, rows), out_shardings=(repl, repl),
                 donate_argnums=(0,))
    x = jax.device_put(np.ones((8, 4), np.float32), rows)
    place = lambda: jax.device_put(np.zeros((4,), np.float32), repl)
    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    _, cold = xray.analyze_jit("step", fn, place(), x, cache=cache)
    assert cold["cache"]["stored"] is True
    loaded, warm = xray.analyze_jit("step", fn, place(), x, cache=cache)
    assert warm["cache"]["hit"] is True
    w = place()
    for _ in range(20):
      w, total = loaded(w, x)  # each call donates the last result
      jax.block_until_ready(w)  # see conftest.py: one step in flight
    np.testing.assert_allclose(np.asarray(w), -2.0, rtol=1e-5)
    assert float(total) == 32.0

  @pytest.mark.parametrize("device_index", [0, 3])
  def test_loaded_executable_keeps_its_devices(self, tmp_path,
                                               device_index):
    """jax 0.9.0 loads over every local device unless told otherwise:
    the sidecar records the executable's own devices, so a one-device
    executable loads — and runs — on that device in an 8-device
    process."""
    import jax

    from tensor2robot_tpu.obs import xray

    device = jax.devices()[device_index]
    fn = jax.jit(lambda a: a * 2.0)
    a = jax.device_put(np.ones((4,), np.float32), device)
    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    _, cold = xray.analyze_jit("double", fn, a, cache=cache)
    (entry,) = cache.entries()
    assert entry["device_ids"] == [device.id]
    loaded, warm = xray.analyze_jit("double", fn, a, cache=cache)
    assert warm["cache"]["hit"] is True
    out = loaded(a)
    assert out.devices() == {device}
    np.testing.assert_allclose(np.asarray(out), 2.0)

  def test_train_targets_are_forgeable(self):
    plan = forge.plan_from_config([_cfg("train_pipelined_1f1b.gin")])
    (train,) = plan["targets"]
    assert train["forgeable"] is True
    assert "reason" not in train

  def test_train_worker_keys_the_loop_scan_not_the_plain_step(self):
    """The program-identity pin: a `loop_k` target must trace
    `make_train_loop`'s [K, B] scan (trace-only verify path), which keys
    DIFFERENTLY from the plain step; forging the plain step under the
    loop name would store an entry the live trainer never looks up."""
    import tensor2robot_tpu.utils.mocks  # noqa: F401 - registers the model

    spec = {"model": {"kind": "configurable", "name": "MockT2RModel"},
            "cache_dir": "/nonexistent-unused"}
    step_target = {"name": "train_step", "family": "train",
                   "mesh_shape": [1, 1, 1], "batch_size": 4}
    loop_target = {"name": "train_loop_k2", "family": "train",
                   "mesh_shape": [1, 1, 1], "batch_size": 4,
                   "loop_k": 2}
    (step_key,) = forge._forge_train_target(spec, step_target,
                                            verify=True)
    (loop_key,) = forge._forge_train_target(spec, loop_target,
                                            verify=True)
    assert step_key["key"] and loop_key["key"]
    assert step_key["key"] != loop_key["key"]


# ---------------------------------------------------------------------------
# Satellite: warmup load/compile split + per-rung provenance.
# ---------------------------------------------------------------------------


class TestWarmupSplit:

  def test_cold_warmup_is_all_compile(self):
    engine = serving_engine(max_batch_size=4)
    engine.warmup()
    assert engine.warmup_compile_ms > 0
    assert engine.warmup_load_ms == 0
    provenance = engine.warmup_provenance
    assert [p["rung"] for p in provenance] == [1, 2, 4]
    assert all(p["source"] == "compile" for p in provenance)
    assert all(p["ms"] > 0 for p in provenance)
    # The split covers the rung wall (warmup_ms adds bundle
    # bookkeeping on top).
    assert engine.warmup_ms >= engine.warmup_compile_ms

  def test_forged_warmup_is_all_load_with_keys(self, tmp_path):
    cache_dir = str(tmp_path / "exc")
    serving_engine(max_batch_size=2, cache=cache_dir).warmup()
    engine = serving_engine(max_batch_size=2, cache=cache_dir)
    engine.warmup()
    assert engine.compile_count == 0
    assert engine.cache_loads == 2
    assert engine.warmup_compile_ms == 0
    assert engine.warmup_load_ms > 0
    for entry in engine.warmup_provenance:
      assert entry["source"] == "cache"
      assert entry["key"]  # attributable: the exact entry each rung hit
    snap = metrics_lib.snapshot()
    assert snap["gauge/serve/engine/warmup_load_ms"] > 0
    assert snap["gauge/serve/engine/warmup_compile_ms"] == 0

  def test_cache_namespace_shares_keys_across_engine_names(self,
                                                           tmp_path):
    # Two engines with per-replica NAMES but one namespace compute the
    # same keys — the loop-fleet sharing graftforge relies on.
    a = serving_engine(max_batch_size=2, name="serve/loop/replica0",
                       cache_namespace="serve/loop")
    b = serving_engine(max_batch_size=2, name="serve/loop/replica1",
                       cache_namespace="serve/loop")
    assert a.rung_cache_keys() == b.rung_cache_keys()
    c = serving_engine(max_batch_size=2, name="serve/loop/replica0")
    assert c.rung_cache_keys() != a.rung_cache_keys()


def serving_engine(max_batch_size=4, cache=None, name="serve/engine",
                   cache_namespace=None):
  from tensor2robot_tpu import serving

  return serving.BucketedEngine(predictor=_mock_predictor(),
                                max_batch_size=max_batch_size,
                                name=name, cache=cache,
                                cache_namespace=cache_namespace)


# ---------------------------------------------------------------------------
# Rollout ladder pre-forge (engine.reladder + fleet.rollout(ladder=)).
# ---------------------------------------------------------------------------


class _SwapOkPredictor:
  """restore() always finds a 'new checkpoint', so rollout() proceeds."""

  def __init__(self, predictor):
    self._predictor = predictor

  def restore(self):
    return True

  def __getattr__(self, name):
    return getattr(self._predictor, name)


class TestReladder:

  def test_reladder_warms_new_rungs_before_swap(self, tmp_path):
    engine = serving_engine(max_batch_size=4)
    engine.warmup()
    compiles = engine.compile_count
    engine.reladder([1, 3, 4])
    assert engine.buckets == [1, 3, 4]
    # ONE new rung (3) compiled; 1 and 4 kept their executables.
    assert engine.compile_count == compiles + 1
    assert engine.warmup_provenance[-1]["rung"] == 3
    # A reladder back is free — every rung still cached.
    engine.reladder([1, 2, 4])
    assert engine.compile_count == compiles + 1
    # Traffic at the new top routes through warm executables.
    spec = engine.get_feature_specification()
    from tensor2robot_tpu import specs as specs_lib

    request = specs_lib.make_random_numpy(spec, batch_size=3, seed=1)
    out = engine.predict(request)
    assert next(iter(out.values())).shape[0] == 3
    assert metrics_lib.snapshot().get(
        "counter/serve/engine/exec_fallbacks", 0.0) == 0.0

  def test_rollout_ladder_preforges_inside_drained_window(self):
    from tensor2robot_tpu import serving

    def factory(index, devices):
      return serving.BucketedEngine(
          predictor=_SwapOkPredictor(_mock_predictor()),
          max_batch_size=4, name=f"serve/t/replica{index}")

    with serving.ServingFleet(replica_factory=factory,
                              num_replicas=2, max_batch_size=4,
                              warmup=True) as fleet:
      report = fleet.rollout(ladder=[1, 3, 4])
      assert report["swapped"] == 2
      for index, entry in enumerate(report["replicas"]):
        # The new rung's provenance is stamped into the report — and it
        # was forged BEFORE restore()/re-admission (drained window).
        assert [p["rung"] for p in entry["reladder"]] == [3]
        assert fleet.replica(index).buckets == [1, 3, 4]
      # Honest accounting: an uncached reladder rung IS a fresh compile
      # inside the rollout window (a forge-warmed cache makes it 0).
      assert report["fresh_compiles"] == 2


# ---------------------------------------------------------------------------
# ISSUE 15 acceptance: cross-process forge pin (satellite 3).
# ---------------------------------------------------------------------------


_FLEET_CHILD = """
import json, sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
from tensor2robot_tpu import serving, specs as specs_lib
from tensor2robot_tpu.predictors import predictors as predictors_lib
from tensor2robot_tpu.research.qtopt import flagship

cache_dir = sys.argv[1]

def make_fleet(cache):
  def make_replica(index, group):
    model = flagship.make_flagship_model("cpu", smoke=True)
    p = predictors_lib.CheckpointPredictor(model=model,
                                           model_dir="/nonexistent")
    p.init_randomly()
    if group:
      p.place_on_device(group[0])
    return serving.BucketedEngine(predictor=p, max_batch_size=4,
                                  name=f"serve/engine/replica{index}",
                                  cache=cache,
                                  cache_namespace="serve/engine")
  return serving.ServingFleet(replica_factory=make_replica,
                              num_replicas=2, devices=jax.devices(),
                              max_batch_size=4, warmup=True)

forged = make_fleet(cache_dir)
request = dict(specs_lib.make_random_numpy(
    forged.replica(0).get_feature_specification(), batch_size=2,
    seed=7).items())
forged_out = {k: np.asarray(v).tolist()
              for k, v in forged.replica(0)._predict_chunk(
                  {k: np.asarray(v) for k, v in request.items()},
                  2).items()}
result = {
    "engine_compiles": forged.compile_counts(),
    "cache_loads": [forged.replica(i).cache_loads for i in range(2)],
    "loaded_keys": sorted(p["key"] for p in forged.warmup_provenance()),
    "compile_ms": [forged.replica(i).warmup_compile_ms
                   for i in range(2)],
}
forged.close()

cold = make_fleet(None)  # same seed/init: the parity reference
cold_out = {k: np.asarray(v).tolist()
            for k, v in cold.replica(0)._predict_chunk(
                {k: np.asarray(v) for k, v in request.items()},
                2).items()}
result["parity_ok"] = (
    set(forged_out) == set(cold_out)
    and all(np.allclose(forged_out[k], cold_out[k], rtol=1e-5,
                        atol=1e-6) for k in cold_out))
cold.close()
print("FORGE_RESULT " + json.dumps(result))
"""


@pytest.mark.slow
def test_cross_process_forge_warms_a_live_fleet(tmp_path):
  """Process A: `graftscope forge` on serve_fleet.gin (empty cache).
  Process B: builds the fleet and pins engine_compiles == [0, 0],
  cache_loads == ladder x replicas, every loaded key present in the
  manifest, and served-output parity vs a cold-built fleet."""
  cache_dir = str(tmp_path / "exc")
  runs_path = str(tmp_path / "runs.jsonl")
  env = {**os.environ, "PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu"}

  # -- process A: the forge CLI over an EMPTY cache dir ------------------
  result = subprocess.run(
      [sys.executable, "-m", "tensor2robot_tpu.bin.graftscope", "forge",
       os.path.join("tensor2robot_tpu", "configs", "serve_fleet.gin"),
       "--model", "flagship", "--cache-dir", cache_dir, "--jobs", "2",
       "--binding", "BucketedEngine.max_batch_size = 4",
       "--runs", runs_path],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
      env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])

  # The forge-manifest-v1 record landed in runs.jsonl: 2 replicas x
  # [1, 2, 4] rungs, every one freshly compiled, no errors.
  records = runlog.load_records(runs_path)
  manifests = [r["extra"]["forge"] for r in records
               if (r.get("extra") or {}).get("forge")]
  assert len(manifests) == 1
  manifest = manifests[0]
  assert manifest["schema"] == "forge-manifest-v1"
  assert manifest["counts"] == {"forged": 6, "cached": 0, "fallback": 0,
                                "errors": 0, "unforgeable": 0}
  manifest_keys = {e["key"] for e in manifest["executables"]}
  assert len(manifest_keys) == 6  # placed replicas: per-replica keys
  assert all(e["compile_s"] > 0 for e in manifest["executables"])

  # -- process B: the live fleet ----------------------------------------
  result = subprocess.run(
      [sys.executable, "-c", _FLEET_CHILD, cache_dir],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
      env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  line = [l for l in result.stdout.splitlines()
          if l.startswith("FORGE_RESULT ")][0]
  report = json.loads(line[len("FORGE_RESULT "):])
  assert report["engine_compiles"] == [0, 0]
  assert report["cache_loads"] == [3, 3]  # ladder x replicas
  assert report["compile_ms"] == [0, 0]
  # The spec-completeness pin: every key the live fleet's first
  # dispatch set loaded is in the forge manifest.
  assert set(report["loaded_keys"]) <= manifest_keys
  assert len(report["loaded_keys"]) == 6
  assert report["parity_ok"] is True

  # -- --verify against the populated cache ------------------------------
  result = subprocess.run(
      [sys.executable, "-m", "tensor2robot_tpu.bin.graftscope", "forge",
       os.path.join("tensor2robot_tpu", "configs", "serve_fleet.gin"),
       "--model", "flagship", "--cache-dir", cache_dir,
       "--binding", "BucketedEngine.max_batch_size = 4", "--verify"],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
      env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "6 present, 0 missing, 0 corrupt" in result.stdout

  # Corrupting one entry flips --verify to exit 1 (the `graftscope
  # cache` exit-code conventions).
  victim = sorted(manifest_keys)[0]
  os.unlink(os.path.join(cache_dir, victim + ".bin"))
  os.unlink(os.path.join(cache_dir, victim + ".json"))
  result = subprocess.run(
      [sys.executable, "-m", "tensor2robot_tpu.bin.graftscope", "forge",
       os.path.join("tensor2robot_tpu", "configs", "serve_fleet.gin"),
       "--model", "flagship", "--cache-dir", cache_dir,
       "--binding", "BucketedEngine.max_batch_size = 4", "--verify"],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
      env=env)
  assert result.returncode == 1
  assert "MISSING" in result.stdout


@pytest.mark.slow
def test_session_and_loop_key_sets_subset_of_forge_enumeration(tmp_path):
  """Spec-completeness for the session + loop families: the keys a LIVE
  engine computes for its first dispatches are a subset of what the
  forge enumeration keys for the same config — traced in a SEPARATE
  worker process (verify mode: no compiles), so cross-process key
  stability rides the same pin."""
  # -- serve_session.gin -------------------------------------------------
  plan = forge.plan_from_config([_cfg("serve_session.gin")],
                                model="SequenceRegressionModel")
  report = forge.verify_plan(plan, str(tmp_path / "empty"))
  assert not report["errors"], report["errors"]
  enumerated = {e["key"] for e in report["missing"]}
  assert len(enumerated) == 5  # 4 decode rungs + slot reset

  from tensor2robot_tpu.predictors import predictors as predictors_lib
  from tensor2robot_tpu.serving import session as session_lib

  # Live engine under the SAME config bindings (sequence_length = 32).
  model = config_lib.get_configurable("SequenceRegressionModel")()
  predictor = predictors_lib.CheckpointPredictor(model=model,
                                                 model_dir="/nonexistent")
  predictor.init_randomly()
  live = session_lib.SessionEngine(predictor=predictor, max_sessions=64,
                                   max_tick_batch=8)
  live_keys = set(live.rung_cache_keys().values())
  assert live_keys <= enumerated
  assert len(live_keys) == 5

  # -- loop_qtopt.gin (the fleet half; the learner is gated) -------------
  plan = forge.plan_from_config([_cfg("loop_qtopt.gin")])
  report = forge.verify_plan(plan, str(tmp_path / "empty2"))
  assert not report["errors"], report["errors"]
  enumerated = {e["key"] for e in report["missing"]}
  assert len(enumerated) == 4  # one shared entry set for both replicas

  from tensor2robot_tpu.serving import engine as live_engine_lib

  model = config_lib.get_configurable("PoseEnvContinuousMCModel")()
  predictor = predictors_lib.CheckpointPredictor(model=model,
                                                 model_dir="/nonexistent")
  predictor.init_randomly()
  live = live_engine_lib.BucketedEngine(
      predictor=predictor, max_batch_size=8,
      name="serve/loop/replica0", cache_namespace="serve/loop")
  live_keys = set(live.rung_cache_keys().values())
  assert live_keys <= enumerated
  assert len(live_keys) == 4


# ---------------------------------------------------------------------------
# CLI surface + exit codes.
# ---------------------------------------------------------------------------


class TestForgeCLI:

  def test_plan_exits_zero_and_prints_enumeration(self, capsys):
    assert graftscope.main(
        ["forge", _cfg("train_pipelined_1f1b.gin"), "--plan",
         "--binding", "train_eval_model.mode = 'train_and_evaluate'"]) == 0
    out = capsys.readouterr().out
    assert "UNFORGEABLE" in out and "train_step" in out

  def test_missing_config_exits_two(self, capsys):
    assert graftscope.main(["forge", "/nonexistent.gin", "--plan"]) == 2

  def test_forgeable_targets_without_model_exit_two(self, capsys):
    assert graftscope.main(
        ["forge", _cfg("serve_fleet.gin"), "--cache-dir",
         "/tmp/unused"]) == 2
    assert "no model source" in capsys.readouterr().err

  def test_cache_dir_auto_is_the_cache_root(self, capsys):
    """`--cache-dir auto` (the default) is where trainer and servers
    look: `excache.cache_root()`, no model_dir involved."""
    assert graftscope.main(
        ["forge", _cfg("serve_session.gin"), "--model",
         "SequenceRegressionModel", "--verify"]) == 1  # nothing forged yet
    assert excache.cache_root() in capsys.readouterr().out


# ---------------------------------------------------------------------------
# graftlint: warmup-unforgeable.
# ---------------------------------------------------------------------------


_FLAGGED = """
from tensor2robot_tpu import serving
ladder = serving.engine.traffic_bucket_ladder(sizes, 16)
engine = serving.BucketedEngine(predictor=p, buckets=ladder)
session = serving.SessionEngine(predictor=p,
                                buckets=derive_buckets_somehow())
"""

_CLEAN = """
from tensor2robot_tpu import serving
from tensor2robot_tpu.serving.engine import bucket_ladder
MY_BUCKETS = (1, 2, 4)
a = serving.BucketedEngine(predictor=p)                     # default ladder
b = serving.BucketedEngine(predictor=p, buckets=[1, 2, 8])  # literal
c = serving.BucketedEngine(predictor=p, buckets=None)
d = serving.BucketedEngine(predictor=p, buckets=MY_BUCKETS)
e = serving.BucketedEngine(predictor=p, buckets=bucket_ladder(16))
f = serving.SessionEngine(predictor=p, **kwargs)            # splat
"""

_SUPPRESSED = """
from tensor2robot_tpu import serving
engine = serving.BucketedEngine(  # graftlint: disable=warmup-unforgeable
    predictor=p, buckets=derived())
"""


class TestWarmupUnforgeableRule:

  def test_flags_runtime_derived_ladders(self):
    findings = forge_check.check_python_source("x.py", _FLAGGED)
    assert len(findings) == 2
    assert all(f.rule == "warmup-unforgeable" for f in findings)
    assert "cannot enumerate" in findings[0].message

  def test_accepts_spec_derivable_ladders(self):
    assert forge_check.check_python_source("x.py", _CLEAN) == []

  def test_suppression(self, tmp_path):
    path = tmp_path / "x.py"
    path.write_text(_SUPPRESSED)
    assert forge_check.check_python_file(str(path)) == []

  def test_repo_pinned_clean(self):
    findings = [f for f in lint_lib.run(
        [os.path.join(REPO_ROOT, "tensor2robot_tpu")])
        if f.rule == "warmup-unforgeable"]
    assert findings == []


# ---------------------------------------------------------------------------
# Tier-1: forge enumeration + CLI are backend-free (poisoned trap).
# ---------------------------------------------------------------------------


def test_forge_plan_backend_free():
  """`obs/forge.py` must import, enumerate a full plan, render it, and
  run the CLI `--plan` path without initializing any JAX backend — the
  repo-standard poisoned-platform trap (the farm's WORKERS are where
  jax lives, in their own subprocesses)."""
  code = """
from tensor2robot_tpu.obs import forge

plan = forge.plan_from_config(
    ["tensor2robot_tpu/configs/serve_fleet.gin"])
assert len(plan["targets"]) == 2
assert plan["targets"][0]["buckets"] == [1, 2, 4, 8, 16]
rendered = forge.format_plan(plan)
assert "serve/engine" in rendered

plan = forge.plan_from_config(
    ["tensor2robot_tpu/configs/train_pipelined_1f1b.gin"])
assert plan["targets"][0]["forgeable"] is True

from tensor2robot_tpu.bin import graftscope
assert graftscope.main(
    ["forge", "tensor2robot_tpu/configs/serve_session.gin",
     "--plan"]) == 0

from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("FORGE_NO_BACKEND_OK")
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "forge_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", code],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT,
      env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "FORGE_NO_BACKEND_OK" in result.stdout


# ---------------------------------------------------------------------------
# Runlog: forge metrics are diff-gated.
# ---------------------------------------------------------------------------


class TestForgeRunlogGates:

  def test_thresholds_registered(self):
    assert runlog.DEFAULT_THRESHOLDS["forged_vs_cold"] == ("down", 0.30)
    assert runlog.DEFAULT_THRESHOLDS["forged_start_ms"][0] == "up"
    assert runlog.DEFAULT_THRESHOLDS["forge_compile_share"] == ("up", 0.0)

  def test_key_metrics_reads_forge_headline(self):
    record = runlog.make_record("bench", bench={
        "metric": "qtopt_forged_start_ms_cpu_smoke",
        "forged_vs_cold": 3.3, "forged_start_ms": 1800.0,
        "forge_compile_share": 0.0})
    metrics = runlog.key_metrics(record)
    assert metrics["forged_vs_cold"] == 3.3
    assert metrics["forged_start_ms"] == 1800.0
    assert metrics["forge_compile_share"] == 0.0

  def test_compile_share_regression_flags(self):
    a = runlog.make_record("bench", bench={"forge_compile_share": 0.0})
    b = runlog.make_record("bench", bench={"forge_compile_share": 0.2})
    deltas = runlog.diff_records(a, b)
    flagged = {d["metric"]: d["regressed"] for d in deltas}
    assert flagged["forge_compile_share"] is True
