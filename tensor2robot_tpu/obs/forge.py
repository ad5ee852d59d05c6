"""graftforge: an ahead-of-time compile farm that warms every executable
a deployment needs BEFORE any process starts.

graftcache (obs/excache.py, PR 7) killed recompiles per process on one
topology; PRs 10-14 multiplied the executable surface — serving bucket
ladders x replica counts x decode-step rungs x slot resets x train/eval
steps — and a cold fleet, loop, or trainer still pays its first-process
compiles serially at startup (5.2 s cold vs 1.8 s warm on the CPU
smoke). The reference
had the same shape at export time: SavedModel signature generation
enumerated every serving entry point from specs alone
(/root/reference/export_generators/default_export_generator.py:37-115)
— graftforge is that enumeration pointed at compiled XLA executables
(PAPERS.md: "Automatic Full Compilation of Julia Programs and ML Models
to Cloud TPUs" — whole-program offline compilation; "Scalable Training
of Language Models using JAX pjit and TPUv4" — compile cost as a
first-class scaling axis; ROADMAP item 5 verbatim).

Three layers:

* **ENUMERATION** (`plan_from_config`, backend-free): from a parsed
  research config and its specs alone — no devices, no checkpoint, no
  traffic — list the complete executable set the deployment will need:
  every `BucketedEngine` bucket rung (x replica placement), every
  `SessionEngine` decode rung + the slot-reset executable, the train
  step (with `num_virtual_stages` for pipelined trunks), the eval step.
  Targets that never route through `analyze_jit` (plain-jit eval steps)
  are enumerated as UNFORGEABLE with the reason attached — the plan is
  the honest coverage statement.
* **THE FARM** (`run_forge`): forgeable targets are partitioned over a
  pool of worker subprocesses (`--jobs`), each of which builds exactly
  the objects the live process would build (predictor + engine for
  serving rungs, TrainState + train step for the trainer) and compiles
  through the SAME `obs.xray.analyze_jit` + graftcache path the live
  warmup takes — so a forged entry is byte-identical in key to what the
  live process computes (pinned by tests/test_forge.py). Per-target
  subprocesses parallelize the farm.
* **THE MANIFEST**: one `forge-manifest-v1` record — per-executable
  key, family, compile_s, sizes, per-target errors, the unforgeable
  remainder — appended to runs.jsonl, so `graftscope diff`/`history`
  see forge coverage next to every other run artifact.

Consumers (the three cold-start seams): `train_eval(executable_cache_dir
="auto")` reads `excache.cache_root()` — the forge CLI's default cache
directory; `ServingFleet.warmup()` deserializes every replica's
ladder (N replicas x ladder = N x the win — replicas sharing a
`cache_namespace` deserialize ONE forged entry set); `GraftLoop`
startup threads its cache dir into both the fleet factory and the
learner rounds, so the loop's first serve starts compile-free. A
traffic-derived ladder change pre-forges its new rungs inside
`ServingFleet.rollout(ladder=...)` before the canary swap
(`engine.reladder`).

CLI: `python -m tensor2robot_tpu.bin.graftscope forge <config.gin>`
(`--plan` dry-run enumeration, `--jobs N`, `--verify` against an
existing cache; exit codes match `graftscope cache`: 0 ok, 1 bad/
missing entries, 2 usage). Backend-free at import like the rest of
`obs/` — workers are where jax lives.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from tensor2robot_tpu.obs import graftrace
from tensor2robot_tpu.utils import config

__all__ = ["FORGE_SCHEMA", "plan_from_config", "run_forge", "verify_plan",
           "forge_config", "format_plan", "graftforge", "build_train_step",
           "build_rung_engine"]

FORGE_SCHEMA = "forge-manifest-v1"
FORGE_SCHEMA_VERSION = 1

# Families the farm knows how to build. "eval" is enumerated (the plan
# is the coverage statement) but never farmed: train_eval's eval step is
# a plain jit that only ever rides the XLA-cache backstop tier.
FAMILIES = ("serve", "session", "train", "eval")


@config.configurable
def graftforge(model=None,
               model_dir: Optional[str] = None,
               export_dir: Optional[str] = None,
               jobs: int = 2):
  """Config-engine surface for forge inputs a research config wants to
  pin (`graftforge.model = @MyModel` names the model whose executables
  a serving-only config deploys; serving configs otherwise carry no
  model binding). Returns the bound values — the CLI merges them under
  its own flags."""
  return {"model": model, "model_dir": model_dir,
          "export_dir": export_dir, "jobs": jobs}


# ---------------------------------------------------------------------------
# Enumeration (backend-free).
# ---------------------------------------------------------------------------


def _ref_name(value) -> Optional[str]:
  """The configurable name behind an (unresolved) @reference binding."""
  name = getattr(value, "name", None)
  if isinstance(name, str):
    return name.rsplit(".", 1)[-1]
  if isinstance(value, str):
    return value.rsplit(".", 1)[-1]
  return None


def _bucket_ladder(max_batch_size: int) -> List[int]:
  # Local twin of serving.engine.bucket_ladder: enumeration must stay
  # importable under a poisoned backend without pulling the serving
  # package's import surface; tests pin the two ladders against each
  # other so they cannot drift.
  ladder, b = [], 1
  while b < max_batch_size:
    ladder.append(b)
    b *= 2
  ladder.append(max_batch_size)
  return ladder


def _resolve_model_source(model: Optional[str] = None,
                          export_dir: Optional[str] = None
                          ) -> Optional[Dict[str, Any]]:
  """Model-source resolution, most explicit first: caller argument,
  `graftforge.model` binding, the trainer/loop model bindings a full
  config already carries. Serving-only configs (serve_fleet.gin) carry
  no model — callers pass `--model`/`--export-dir` or the plan records
  `model: None` and the farm refuses with exit 2."""
  if export_dir:
    return {"kind": "export", "dir": str(export_dir)}
  if model == "flagship":
    return {"kind": "flagship"}
  if model:
    return {"kind": "configurable", "name": str(model)}
  for dotted in ("graftforge.model", "train_eval_model.model",
                 "run_graftloop.model_ctor"):
    # Raw binding on purpose: `@Name()` references resolve to a BUILT
    # model, and enumeration must not construct one at plan time.
    bound = config.raw_binding(dotted)
    if bound is not None:
      name = _ref_name(bound)
      if name == "flagship":
        return {"kind": "flagship"}
      if name:
        return {"kind": "configurable", "name": name}
  return None


def plan_from_config(config_files: Sequence[str],
                     bindings: Sequence[str] = (),
                     model: Optional[str] = None,
                     export_dir: Optional[str] = None,
                     model_dir: Optional[str] = None) -> Dict[str, Any]:
  """Enumerates the executable set a research config deploys.

  Parses the config (fresh registry) and reads its bindings — nothing
  is built, no backend is touched (the `--plan` path runs under a
  poisoned JAX_PLATFORMS, pinned by test). Returns the plan dict the
  farm, the verifier, and the `--plan` renderer all consume:
  `{"targets": [...], "model": ..., "config_files": [...]}` where each
  target carries family, name (= cache namespace), the rung/replica
  grid, and `forgeable` + `reason` for targets the toolchain gates.
  """
  config.clear_config()
  config.parse_config_files_and_bindings(list(config_files),
                                         list(bindings))
  bound = config.bound_configurables()
  query = config.query_parameter_or
  model_source = _resolve_model_source(model=model, export_dir=export_dir)
  model_dir = model_dir or query("graftforge.model_dir") \
      or query("run_graftloop.model_dir")
  targets: List[Dict[str, Any]] = []

  # -- serving bucket ladders (BucketedEngine behind a fleet or solo) ------
  has_loop = "run_graftloop" in bound
  has_fleet = "ServingFleet" in bound
  has_serve = (has_fleet or has_loop or "BucketedEngine" in bound
               or "MicroBatcher" in bound)
  if has_serve:
    buckets = query("BucketedEngine.buckets")
    if buckets is None:
      max_batch = int(query("BucketedEngine.max_batch_size")
                      or query("ServingFleet.max_batch_size")
                      or query("run_graftloop.max_batch_size") or 8)
      buckets = _bucket_ladder(max_batch)
    else:
      buckets = sorted({int(b) for b in buckets})
    replicas = int(query("ServingFleet.num_replicas")
                   or query("run_graftloop.num_replicas") or 1)
    # Placement: a ServingFleet deployment (run_graftserve --replicas)
    # carves disjoint device groups and pins each replica's state, so
    # rung keys diverge per replica (the sharding key component) — one
    # target per replica. The loop builds its fleet without a device
    # carve (devices=None): every replica computes identical keys, so
    # ONE forged entry set warms all of them (forge once, every replica
    # deserializes) — one target, replicas recorded for the plan table.
    placed = has_fleet and not has_loop and replicas > 1
    namespace = "serve/loop" if has_loop else "serve/engine"
    for index in range(replicas if placed else 1):
      targets.append({
          "family": "serve",
          "name": namespace,
          "buckets": list(buckets),
          "replica_index": index,
          "num_replicas": replicas,
          "placed": placed,
          "executables": len(buckets),
          "forgeable": True,
      })

  # -- session decode ladders ----------------------------------------------
  if "SessionEngine" in bound:
    buckets = query("SessionEngine.buckets")
    if buckets is None:
      buckets = _bucket_ladder(int(query("SessionEngine.max_tick_batch")
                                   or 8))
    else:
      buckets = sorted({int(b) for b in buckets})
    targets.append({
        "family": "session",
        "name": "serve/session",
        "buckets": list(buckets),
        "max_sessions": int(query("SessionEngine.max_sessions") or 64),
        "executables": len(buckets) + 1,  # + the slot-reset executable
        "forgeable": True,
    })

  # -- train / eval steps --------------------------------------------------
  has_trainer = config.raw_binding("train_eval_model.model") is not None
  if has_trainer or has_loop:
    if has_trainer:
      # An unbound mesh_shape is NOT single-device: train_eval builds
      # the default all-devices mesh — record it so the worker compiles
      # (and keys) the executable the trainer actually dispatches.
      # (None is reserved for hand-built one-chip plans.)
      mesh_shape = query("train_eval_model.mesh_shape") or "default"
      mode = str(query("train_eval_model.mode") or "train_and_evaluate")
      loop_k = int(query("train_eval_model.iterations_per_loop") or 1)
    else:  # the loop's learner: train rounds on a (1,1,1) mesh
      mesh_shape = (1, 1, 1)
      mode = "train"
      loop_k = 1
    model_name = _ref_name(config.raw_binding("train_eval_model.model")
                           or config.raw_binding(
                               "run_graftloop.model_ctor"))
    virtual_stages = None
    if model_name:
      virtual_stages = config.query_parameter_or(
          f"{model_name}.num_virtual_stages")
    step_specs = [("train_step", 1)]
    if loop_k > 1:
      step_specs.append((f"train_loop_k{loop_k}", loop_k))
    for step_name, k in step_specs:
      target = {
          "family": "train",
          "name": step_name,
          "mesh_shape": (list(mesh_shape)
                         if isinstance(mesh_shape, (list, tuple))
                         else mesh_shape),
          "batch_size": int(
              query("run_graftloop.train_batch_size")
              or query("DefaultRandomInputGenerator.batch_size")
              or query("DefaultRecordInputGenerator.batch_size") or 16),
          "executables": 1,
          "forgeable": True,
      }
      if k > 1:
        target["loop_k"] = k  # the [K,B] scan loop, not K plain steps
      if virtual_stages is not None:
        target["num_virtual_stages"] = int(virtual_stages)
      targets.append(target)
    if "evaluate" in mode or "eval" in mode.replace("evaluate", ""):
      targets.append({
          "family": "eval",
          "name": "eval_step",
          "executables": 1,
          "forgeable": False,
          "reason": ("plain-jit executable (never routed through "
                     "analyze_jit); the XLA compilation-cache backstop "
                     "tier covers it in eval modes"),
      })

  return {
      "schema": FORGE_SCHEMA,
      "schema_version": FORGE_SCHEMA_VERSION,
      "config_files": [str(p) for p in config_files],
      "bindings": [str(b) for b in bindings],
      "model": model_source,
      "model_dir": model_dir,
      "targets": targets,
  }


def format_plan(plan: Dict[str, Any]) -> str:
  """The `--plan` table: one line per target, unforgeable reasons
  spelled out (a rung forge can't enumerate is a rung the farm can't
  warm — the graftlint `warmup-unforgeable` rule polices the code side
  of the same contract)."""
  lines = [f"graftforge plan: {', '.join(plan['config_files'])} "
           f"(model: {json.dumps(plan.get('model'))})"]
  lines.append(f"  {'family':<9}{'name':<18}{'executables':>12}  detail")
  total = forgeable = 0
  for target in plan["targets"]:
    count = int(target.get("executables") or 0)
    total += count
    detail = []
    if target.get("buckets"):
      detail.append(f"rungs {target['buckets']}")
    if target["family"] == "session":
      detail.append("+ slot reset")
      detail.append(f"max_sessions {target.get('max_sessions')}")
    if target.get("placed"):
      detail.append(f"replica {target['replica_index']}"
                    f"/{target['num_replicas']} (placed)")
    elif int(target.get("num_replicas") or 1) > 1:
      detail.append(f"shared by {target['num_replicas']} replicas")
    if target.get("num_virtual_stages") is not None:
      detail.append(f"v={target['num_virtual_stages']} (1F1B)")
    if target.get("loop_k"):
      detail.append(f"K={target['loop_k']} scan loop")
    shape = target.get("mesh_shape")
    if shape:
      detail.append(f"mesh {tuple(shape) if isinstance(shape, list) else shape}")
    if target["forgeable"]:
      forgeable += count
    else:
      detail.append(f"UNFORGEABLE: {target.get('reason')}")
    lines.append(f"  {target['family']:<9}{target['name']:<18}"
                 f"{count:>12}  {'; '.join(detail)}")
  lines.append(f"  total {total} executable(s), {forgeable} forgeable")
  return "\n".join(lines)


# ---------------------------------------------------------------------------
# The farm (parent side).
# ---------------------------------------------------------------------------


def _worker_env(device_count: Optional[int]) -> Dict[str, str]:
  env = dict(os.environ)
  if device_count:
    flags = env.get("XLA_FLAGS", "")
    # Replace any inherited count: the forge must match the DEPLOYED
    # topology, not the parent's (mesh_fingerprint is a key component).
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count="
        f"{int(device_count)}").strip()
  # Cross-process tracing: when the parent armed graftrace, workers
  # export their own trace/metrics shards into the same directory
  # (`graftrace.init_from_env` in `_worker_main`), so `graftscope
  # timeline` merges the farm's compile windows with everything else.
  trace_dir = graftrace.export_dir()
  if trace_dir:
    env["GRAFTRACE_DIR"] = trace_dir
    env.setdefault("GRAFTRACE_ROLE", "forge-worker")
  return env


def _run_workers(plan: Dict[str, Any], cache_dir: str, jobs: int,
                 verify: bool, device_count: Optional[int],
                 timeout_s: float) -> List[Dict[str, Any]]:
  """Partitions forgeable targets round-robin over `jobs` worker
  subprocesses and collects their per-target results. Workers re-parse
  the config themselves (a configurable model ctor needs its bindings)
  and write results to a JSON file each — stdout stays human."""
  forgeable = [t for t in plan["targets"] if t["forgeable"]]
  if not forgeable:
    return []
  jobs = max(1, min(int(jobs), len(forgeable)))
  shards: List[List[Dict[str, Any]]] = [[] for _ in range(jobs)]
  for index, target in enumerate(forgeable):
    shards[index % jobs].append(target)
  env = _worker_env(device_count)
  procs: List[Tuple[subprocess.Popen, str, List[Dict[str, Any]]]] = []
  results: List[Dict[str, Any]] = []
  with tempfile.TemporaryDirectory(prefix="graftforge-") as tmp:
    for shard_index, shard in enumerate(shards):
      spec = {
          "config_files": plan["config_files"],
          "bindings": plan["bindings"],
          "model": plan.get("model"),
          "model_dir": plan.get("model_dir"),
          "cache_dir": cache_dir,
          "verify": bool(verify),
          "targets": shard,
      }
      spec_path = os.path.join(tmp, f"spec-{shard_index}.json")
      result_path = os.path.join(tmp, f"result-{shard_index}.json")
      with open(spec_path, "w") as f:
        json.dump(spec, f)
      procs.append((subprocess.Popen(
          [sys.executable, "-m", "tensor2robot_tpu.obs.forge",
           "--worker", spec_path, result_path], env=env), result_path,
          shard))
    deadline = time.monotonic() + timeout_s
    for proc, result_path, shard in procs:
      remaining = max(deadline - time.monotonic(), 1.0)
      try:
        proc.wait(timeout=remaining)
      except subprocess.TimeoutExpired:
        # NEVER SIGKILL a possibly-mid-TPU-init child (CLAUDE.md); over
        # a CPU farm terminate is safe and the worker's targets are
        # reported as errors, not silently dropped.
        proc.terminate()
        try:
          proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
          # Stuck in a native compile (SIGTERM lands between Python
          # bytecodes only): ABANDON it — never SIGKILL — and report
          # its targets as errors; completed shards still count.
          pass
      if os.path.isfile(result_path):
        try:
          with open(result_path) as f:
            results.extend(json.load(f))
          continue
        except (OSError, ValueError):
          pass
      results.extend({
          "name": t["name"], "family": t["family"], "status": "error",
          "error": f"worker exited {proc.returncode} without a result",
      } for t in shard)
  return results


def run_forge(plan: Dict[str, Any], cache_dir: str, jobs: int = 2,
              device_count: Optional[int] = None,
              timeout_s: float = 1200.0,
              runs_path: Optional[str] = None) -> Dict[str, Any]:
  """Runs the compile farm over a plan and returns (+ optionally
  appends) the `forge-manifest-v1` manifest."""
  start = time.perf_counter()
  results = _run_workers(plan, cache_dir, jobs, verify=False,
                         device_count=device_count, timeout_s=timeout_s)
  executables: List[Dict[str, Any]] = []
  errors: List[Dict[str, Any]] = []
  for result in results:
    if result.get("status") == "ok":
      executables.extend(result.get("executables") or [])
    else:
      errors.append({"name": result.get("name"),
                     "family": result.get("family"),
                     "error": result.get("error")})
  unforgeable = [{"name": t["name"], "family": t["family"],
                  "reason": t.get("reason")}
                 for t in plan["targets"] if not t["forgeable"]]
  manifest = {
      "schema": FORGE_SCHEMA,
      "schema_version": FORGE_SCHEMA_VERSION,
      "config_files": plan["config_files"],
      "bindings": plan["bindings"],
      "cache_dir": str(cache_dir),
      "jobs": int(jobs),
      "wall_s": round(time.perf_counter() - start, 3),
      "executables": executables,
      "errors": errors,
      "unforgeable": unforgeable,
      "counts": {
          "forged": sum(1 for e in executables
                        if e.get("action") == "compiled"),
          "cached": sum(1 for e in executables
                        if e.get("action") == "cached"),
          # AOT-less degrades: the engine ran its plain-jit fallback, so
          # NOTHING was stored — a farm full of fallbacks warmed nothing
          # and must not read as clean coverage (the CLI exits 1 on it).
          "fallback": sum(1 for e in executables
                          if e.get("action") == "fallback"),
          "errors": len(errors),
          "unforgeable": len(unforgeable),
      },
      "total_compile_s": round(sum(float(e.get("compile_s") or 0.0)
                                   for e in executables), 3),
  }
  if runs_path:
    from tensor2robot_tpu.obs import runlog as runlog_lib

    record = runlog_lib.make_record("bench",
                                    extra={"forge": manifest})
    runlog_lib.append_record(runs_path, record)
  return manifest


def verify_plan(plan: Dict[str, Any], cache_dir: str,
                device_count: Optional[int] = None,
                timeout_s: float = 600.0) -> Dict[str, Any]:
  """Checks an existing cache against the plan WITHOUT compiling:
  workers trace each forgeable target's executables for their keys
  (`engine.rung_cache_keys` — the same synthesis warmup compiles
  through), and the parent checks presence + checksum against the
  cache's backend-free sidecar metadata."""
  from tensor2robot_tpu.obs import excache as excache_lib

  results = _run_workers(plan, cache_dir, jobs=1, verify=True,
                         device_count=device_count, timeout_s=timeout_s)
  cache = excache_lib.ExecutableCache(cache_dir)
  ok_keys, bad_keys = cache.verify()
  present, missing, corrupt = [], [], []
  errors: List[Dict[str, Any]] = []
  for result in results:
    if result.get("status") != "ok":
      errors.append({"name": result.get("name"),
                     "error": result.get("error")})
      continue
    for executable in result.get("executables") or []:
      key = executable.get("key")
      entry = dict(executable)
      if key in bad_keys:
        corrupt.append(entry)
      elif key in ok_keys:
        present.append(entry)
      else:
        missing.append(entry)
  return {"present": present, "missing": missing, "corrupt": corrupt,
          "errors": errors}


def forge_config(config_files: Sequence[str],
                 bindings: Sequence[str] = (),
                 cache_dir: str = ".graftcache",
                 jobs: int = 2,
                 model: Optional[str] = None,
                 export_dir: Optional[str] = None,
                 model_dir: Optional[str] = None,
                 device_count: Optional[int] = None,
                 runs_path: Optional[str] = None
                 ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
  """Enumerate + farm one research config; returns (plan, manifest)."""
  plan = plan_from_config(config_files, bindings, model=model,
                          export_dir=export_dir, model_dir=model_dir)
  manifest = run_forge(plan, cache_dir, jobs=jobs,
                       device_count=device_count, runs_path=runs_path)
  return plan, manifest


# ---------------------------------------------------------------------------
# Worker side (fresh subprocess; the only half that touches jax).
# ---------------------------------------------------------------------------


def _build_model(source: Dict[str, Any]):
  if source["kind"] == "flagship":
    import jax

    from tensor2robot_tpu.research.qtopt import flagship

    # `--model flagship` names the QT-Opt SMOKE critic (CLI help).
    return flagship.make_flagship_model(jax.devices()[0].platform,
                                        smoke=True)
  if source["kind"] == "configurable":
    return config.get_configurable(source["name"])()
  raise ValueError(f"unknown model source {source!r}")


def _build_predictor(spec: Dict[str, Any], target: Dict[str, Any]):
  """Exactly what the live deployment builds: an export-bundle
  predictor when serving exports, else a checkpoint predictor that
  restores when the model_dir already has checkpoints and random-inits
  otherwise (the GraftLoop fresh-start rule; cache keys fingerprint
  shapes/shardings, not values, so both warm the same entries)."""
  from tensor2robot_tpu.predictors import predictors as predictors_lib

  source = spec.get("model")
  if source is None:
    raise ValueError(
        "no model source: pass --model/--export-dir or bind "
        "graftforge.model in the config")
  if source["kind"] == "export":
    predictor = predictors_lib.ExportedModelPredictor(
        export_dir=source["dir"])
    if not predictor.restore():
      raise RuntimeError(f"no valid export bundle under {source['dir']}")
  else:
    predictor = predictors_lib.CheckpointPredictor(
        model=_build_model(source),
        model_dir=spec.get("model_dir") or "/nonexistent")
    if not predictor.restore():
      predictor.init_randomly()
  if target.get("placed"):
    import jax

    from tensor2robot_tpu.parallel import mesh as mesh_lib

    groups = mesh_lib.replica_device_groups(
        int(target["num_replicas"]), jax.devices())
    group = groups[int(target["replica_index"])]
    if group:
      predictor.place_on_device(group[0])
  return predictor


def _engine_result(target: Dict[str, Any], engine,
                   verify: bool) -> List[Dict[str, Any]]:
  if verify:
    return [{"name": f"{target['name']}/{rung}", "family": target["family"],
             "rung": rung if isinstance(rung, str) else int(rung),
             "key": key}
            for rung, key in engine.rung_cache_keys().items()]
  engine.warmup()
  by_name = {str(r.get("name")): r for r in engine.compile_records}
  out = []
  for entry in engine.warmup_provenance:
    rung = entry["rung"]
    rec_name = (f"{target['name']}/reset_slot" if rung == "reset" else
                f"{target['name']}/"
                f"{'decode' if target['family'] == 'session' else 'bucket'}"
                f"{rung}")
    record = by_name.get(rec_name, {})
    cache_block = record.get("cache") or {}
    out.append({
        "name": rec_name,
        "family": target["family"],
        "rung": rung,
        "key": entry.get("key") or cache_block.get("key"),
        "action": ("cached" if entry["source"] == "cache" else
                   "compiled" if entry["source"] == "compile" else
                   "fallback"),
        "compile_s": round(float(record.get("compile_s") or 0.0), 4),
        "ms": round(float(entry.get("ms") or 0.0), 2),
        "stored": bool(cache_block.get("stored", entry["source"]
                                       == "cache")),
    })
  return out


def build_train_step(spec: Dict[str, Any],
                     target: Dict[str, Any]) -> Tuple[Any, Tuple]:
  """Builds the trainer's first-dispatch executable, exactly as
  train_eval pays it, and returns `(step, args)` ready to
  `.trace(*args)` or dispatch: the plain step at [B], or — for
  `loop_k` targets — the `make_train_loop` [K, B] scan program (a
  DIFFERENT jaxpr; forging the plain step under the loop name would
  store an entry the trainer never looks up). `mesh_shape=None` is the
  one-chip deployment shape (SingleDeviceSharding donation —
  serializes safely; hand-built plans only); "default" is train_eval's
  unbound-mesh_shape case (all devices on the data axis); an explicit
  shape mirrors the config. Shared by the farm worker
  (`_forge_train_target`) and the jaxpr audit worker
  (`analysis.jaxpr_audit`): whatever either traces is the program the
  live trainer dispatches."""
  import jax
  import numpy as np

  from tensor2robot_tpu import modes as modes_lib
  from tensor2robot_tpu import specs as specs_lib
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.parallel import train_step as ts

  model = _build_model(spec["model"])
  batch = int(target.get("batch_size") or 16)
  loop_k = int(target.get("loop_k") or 1)
  feature_spec = model.preprocessor.get_out_feature_specification(
      modes_lib.TRAIN)
  label_spec = model.preprocessor.get_out_label_specification(
      modes_lib.TRAIN)
  features = specs_lib.make_random_numpy(feature_spec, batch_size=batch,
                                         seed=0)
  labels = specs_lib.make_random_numpy(label_spec, batch_size=batch,
                                       seed=100)
  mesh_shape = target.get("mesh_shape")
  if mesh_shape is None:
    if loop_k > 1:
      raise ValueError("loop_k targets need a mesh recipe (the live "
                       "K-step loop only exists on the train_eval path)")
    device = jax.devices()[0]
    features = jax.device_put(features, device)
    labels = jax.device_put(labels, device)
    state, _ = ts.create_train_state(model, jax.random.PRNGKey(0),
                                     features)
    step = ts.make_train_step(model)
    args = (state, features, labels)
  else:
    mesh = mesh_lib.create_mesh(
        mesh_shape=None if mesh_shape == "default"
        else tuple(mesh_shape))
    if hasattr(model, "set_mesh"):
      model.set_mesh(mesh)
    state, shardings = ts.create_train_state(
        model, jax.random.PRNGKey(0), features, mesh=mesh)
    batch_spec = getattr(model, "batch_partition_spec", None)
    if loop_k > 1:
      # The live loop stacks K host batches on a leading scan axis
      # (train_eval._stacked_group) and places under the loop spec.
      stack = lambda tree: jax.tree_util.tree_map(  # noqa: E731
          lambda a: np.stack([a] * loop_k), tree)
      features, labels = stack(features), stack(labels)
      batch_spec = ts.loop_batch_spec(batch_spec)
      step = ts.make_train_loop(model, loop_k, mesh=mesh,
                                shardings=shardings,
                                batch_spec=getattr(
                                    model, "batch_partition_spec", None))
    else:
      step = ts.make_train_step(model, mesh=mesh, shardings=shardings,
                                batch_spec=batch_spec)
    placed_features, placed_labels = mesh_lib.place_batch(
        mesh, {"features": features, "labels": labels},
        batch_spec=batch_spec)
    args = (state, placed_features, placed_labels)
  return step, args


def _forge_train_target(spec: Dict[str, Any], target: Dict[str, Any],
                        verify: bool) -> List[Dict[str, Any]]:
  """Compiles (or --verify key-checks) the train-step executable that
  `build_train_step` assembles, through the SAME analyze_jit +
  graftcache path the live trainer takes."""
  from tensor2robot_tpu.obs import excache as excache_lib
  from tensor2robot_tpu.obs import xray as xray_lib

  step, args = build_train_step(spec, target)
  cache = excache_lib.ExecutableCache(spec["cache_dir"])
  if verify:
    traced = step.trace(*args)
    key = excache_lib.cache_key(
        target["name"],
        **excache_lib.key_components_from_traced(traced, args))
    return [{"name": target["name"], "family": "train", "key": key}]
  _, record = xray_lib.analyze_jit(target["name"], step, *args,
                                   cache=cache)
  cache_block = record.get("cache") or {}
  return [{
      "name": target["name"],
      "family": "train",
      "key": cache_block.get("key"),
      "action": "cached" if cache_block.get("hit") else "compiled",
      "compile_s": round(float(record.get("compile_s") or 0.0), 4),
      "stored": bool(cache_block.get("stored", cache_block.get("hit"))),
  }]


def build_rung_engine(spec: Dict[str, Any], target: Dict[str, Any]):
  """The serving engine a "serve"/"session" target deploys, built
  exactly as the live process builds it (predictor + spec-derived
  ladder). Shared by the farm worker (`_forge_target`) and the jaxpr
  audit worker (`analysis.jaxpr_audit`), so both reason over the SAME
  engine the deployment runs."""
  if target["family"] == "serve":
    from tensor2robot_tpu.serving import engine as engine_lib

    # The farm worker IS the enumeration: target["buckets"] came from
    # plan_from_config's spec walk, so the ladder is spec-derived by
    # construction.
    return engine_lib.BucketedEngine(  # graftlint: disable=warmup-unforgeable
        predictor=_build_predictor(spec, target),
        buckets=target["buckets"],
        name=target["name"],
        cache=spec["cache_dir"],
        cache_namespace=target["name"])
  if target["family"] == "session":
    from tensor2robot_tpu.serving import session as session_lib

    # Spec-derived by construction, same as above.
    return session_lib.SessionEngine(  # graftlint: disable=warmup-unforgeable
        predictor=_build_predictor(spec, target),
        max_sessions=int(target.get("max_sessions") or 64),
        buckets=target["buckets"],
        name=target["name"],
        cache=spec["cache_dir"],
        cache_namespace=target["name"])
  raise ValueError(f"no rung engine for family {target['family']!r}")


def _forge_target(spec: Dict[str, Any],
                  target: Dict[str, Any]) -> Dict[str, Any]:
  verify = bool(spec.get("verify"))
  try:
    if target["family"] in ("serve", "session"):
      engine = build_rung_engine(spec, target)
      executables = _engine_result(target, engine, verify)
    elif target["family"] == "train":
      executables = _forge_train_target(spec, target, verify)
    else:
      raise ValueError(f"cannot forge family {target['family']!r}")
  except Exception as e:  # noqa: BLE001 - one bad target != a dead farm
    return {"name": target["name"], "family": target["family"],
            "status": "error", "error": f"{type(e).__name__}: {e}"}
  return {"name": target["name"], "family": target["family"],
          "status": "ok", "executables": executables}


def _worker_main(spec_path: str, result_path: str) -> int:
  with open(spec_path) as f:
    spec = json.load(f)
  if os.environ.get("GRAFTFORGE_PLATFORM", "cpu") == "cpu":
    # Default-safe: a forge worker must never take the chip by
    # accident.
    from tensor2robot_tpu.utils import backend

    backend.pin_cpu()
  graftrace.init_from_env()  # arm shard export when the parent did
  config.clear_config()
  config.parse_config_files_and_bindings(list(spec["config_files"]),
                                         list(spec["bindings"]))
  results = [_forge_target(spec, target) for target in spec["targets"]]
  with open(result_path, "w") as f:
    json.dump(results, f)
  graftrace.flush()
  return 0 if all(r["status"] == "ok" for r in results) else 1


if __name__ == "__main__":
  if len(sys.argv) == 4 and sys.argv[1] == "--worker":
    sys.exit(_worker_main(sys.argv[2], sys.argv[3]))
  print("usage: python -m tensor2robot_tpu.obs.forge --worker "
        "<spec.json> <result.json>\n(operators drive the farm through "
        "`python -m tensor2robot_tpu.bin.graftscope forge`)",
        file=sys.stderr)
  sys.exit(2)
