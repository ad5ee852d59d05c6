"""graftserve CLI: load-test the serving runtime against a real artifact.

The reference has no serving CLI — exports were exercised through
TF-Serving or ad-hoc robot clients against
`ExportedSavedModelPredictor`
(/root/reference/predictors/exported_savedmodel_predictor.py:53-359).

Restores a predictor from an export bundle (the same timestamped dirs
`ExportedModelPredictor` polls), fronts it with the graftserve stack
(BucketedEngine + MicroBatcher — or, with `--replicas N`, a
`ServingFleet` of N replicas on disjoint device groups behind the
load-aware router), warms every shape bucket, then drives a
closed-loop load test and prints ONE JSON stats line — QPS, latency
percentiles, per-bucket compile economics, shed/SLO counters
(`serving.loadgen` machinery, pointed at real checkpoints).

Usage:
  python -m tensor2robot_tpu.bin.run_graftserve \
      --export_dir /tmp/run/export \
      --concurrency 8 --requests_per_thread 100 [--replicas 2] \
      [--config_files tensor2robot_tpu/configs/serve_fleet.gin]
"""

from __future__ import annotations

import json
import sys

from absl import app, flags

from tensor2robot_tpu.utils import config

FLAGS = flags.FLAGS
flags.DEFINE_multi_string("config_files", [],
                          "Config (.gin) files to parse (e.g. the shipped "
                          "serve_qtopt.gin batching policy).")
flags.DEFINE_multi_string("config", [],
                          "Individual binding strings, applied last.")
flags.DEFINE_string("export_dir", None,
                    "Export root with timestamped bundle dirs.")
flags.DEFINE_integer("concurrency", 8, "Closed-loop client threads.")
flags.DEFINE_integer("requests_per_thread", 100, "Requests per client.")
flags.DEFINE_float("deadline_ms", 0.0,
                   "Per-request admission deadline (0 disables); expired "
                   "requests are shed and counted as SLO breaches.")
flags.DEFINE_integer("replicas", 1,
                     "Replica count: 1 serves through a single "
                     "BucketedEngine+MicroBatcher; >1 builds a "
                     "ServingFleet over disjoint device groups "
                     "(parallel.mesh.replica_device_groups).")
flags.DEFINE_string("executable_cache_dir", None,
                    "graftcache directory for the engine bucket "
                    "ladder(s). Pre-populate it with `graftscope forge "
                    "<config> --export-dir <dir>` (graftforge) and "
                    "warmup deserializes instead of compiling. "
                    "NOTE for --replicas N > 1: replica "
                    "placement is a cache-key component, so the forge "
                    "plan must see the same replica count — bind "
                    "ServingFleet.num_replicas = N in the config (or "
                    "pass the same --binding to graftscope forge); a "
                    "plan forged for a different count warms only the "
                    "matching placements. Replicas share the "
                    "'serve/engine' cache namespace.")


def main(argv):
  del argv
  config.parse_config_files_and_bindings(FLAGS.config_files, FLAGS.config)
  if not FLAGS.export_dir:
    raise app.UsageError("--export_dir is required.")

  from tensor2robot_tpu import serving, specs as specs_lib
  from tensor2robot_tpu.obs import metrics as obs_metrics
  from tensor2robot_tpu.predictors import predictors as predictors_lib
  from tensor2robot_tpu.serving import loadgen

  predictor = predictors_lib.ExportedModelPredictor(
      export_dir=FLAGS.export_dir)
  if not predictor.restore():
    print(f"no valid export bundle under {FLAGS.export_dir!r}",
          file=sys.stderr)
    return 2
  request = dict(specs_lib.make_random_numpy(
      predictor.get_feature_specification(), batch_size=1,
      seed=0).items())
  if FLAGS.replicas > 1:
    # Fleet mode: each replica restores its OWN predictor from the
    # export (per-replica state, per-replica device group) behind the
    # load-aware router; the first predictor above validated the
    # bundle and provides the spec.
    import jax

    def make_replica(index, devices):
      p = (predictor if index == 0
           else predictors_lib.ExportedModelPredictor(
               export_dir=FLAGS.export_dir))
      if index > 0 and not p.restore():
        raise RuntimeError(f"replica {index}: export restore failed")
      if devices:
        p.place_on_device(devices[0])
      return serving.BucketedEngine(
          predictor=p, cache=FLAGS.executable_cache_dir,
          cache_namespace="serve/engine")

    with serving.ServingFleet(replica_factory=make_replica,
                              num_replicas=FLAGS.replicas,
                              devices=jax.devices(),
                              warmup=True) as fleet:
      result = loadgen.run_load(
          fleet.predict, lambda i: request,
          concurrency=FLAGS.concurrency,
          requests_per_thread=FLAGS.requests_per_thread,
          deadline_ms=FLAGS.deadline_ms or None)
      engine_compiles = fleet.compile_counts()
      buckets = fleet.replica(0).buckets
      compile_records = [r for i in range(fleet.num_replicas)
                         for r in fleet.replica(i).compile_records]
  else:
    engine = serving.BucketedEngine(
        predictor=predictor, cache=FLAGS.executable_cache_dir,
        cache_namespace="serve/engine")
    engine.warmup()
    with serving.MicroBatcher(backend=engine) as batcher:
      result = loadgen.run_load(
          batcher.predict, lambda i: request,
          concurrency=FLAGS.concurrency,
          requests_per_thread=FLAGS.requests_per_thread,
          deadline_ms=FLAGS.deadline_ms or None)
    engine_compiles = engine.compile_count
    buckets = engine.buckets
    compile_records = engine.compile_records
  snap = obs_metrics.snapshot(prefix="serve/")
  print(json.dumps({
      "global_step": predictor.global_step,
      "replicas": FLAGS.replicas,
      "qps": round(result["qps"], 2),
      "ok": result["ok"],
      "errors": result["errors"],
      "concurrency": result["concurrency"],
      "latency_ms": {k: round(v, 3)
                     for k, v in loadgen.latency_percentiles().items()},
      "buckets": buckets,
      "engine_compiles": engine_compiles,
      "compile_sec": [round(float(r.get("compile_s") or 0.0), 3)
                      for r in compile_records],
      "shed_deadline": snap.get("counter/serve/batcher/shed_deadline", 0.0),
      "shed_queue_full": snap.get("counter/serve/batcher/shed_queue_full",
                                  0.0),
      "fleet_shed": snap.get("counter/serve/fleet/shed", 0.0),
      "slo_breaches": snap.get("counter/serve/slo_breaches", 0.0),
  }))
  return 0


if __name__ == "__main__":
  app.run(main)
