"""graftscope: unified tracing, metrics, and step-time telemetry.

The reference's only observability is TF summaries plumbed through TPU
`host_call` (/root/reference/models/abstract_model.py:873-936). This
package is the permanent instrumentation layer replacing the ad-hoc
timing that diagnosed every perf round by hand (PERFORMANCE.md):

* `trace`     — low-overhead span tracer exporting Chrome-trace-event
  JSON (Perfetto-loadable);
* `metrics`   — process-wide counters / gauges / streaming histograms,
  snapshotted into the JSONL event stream (`utils/summaries.py`);
* `stepstats` — per-train-step breakdown (data-wait vs device time via
  `utils/backend.sync` semantics, compile-event detection, throughput,
  live-array gauges);
* `xray`      — below-dispatch introspection: per-executable compile
  timing, jaxpr equation counts, donation byte accounting, XLA
  cost/memory analysis, analytic MFU/roofline, and per-shard
  state/batch/HBM-watermark accounting;
* `runlog`    — schema-versioned append-only run history
  (`runs.jsonl`) with direction-aware regression diffing;
* `excache`   — graftcache: persistent on-disk executable/AOT cache
  (content-addressed `serialize_executable` round-trips of the xray
  AOT executables + the XLA compilation-cache backstop), so trainer
  restarts and serving cold starts deserialize warm
  executables instead of recompiling; read back / maintained with
  `graftscope cache`;
* `sentinel`  — online anomaly detection over the stepstats stream:
  EWMA/MAD step-time spikes, data starvation, non-finite divergence
  (piggybacked on the barrier fetch — no extra fetch),
  HBM-watermark drift; emits `graftscope-incident-v1` records;
* `faultlab`  — graftguard's seeded deterministic fault-injection
  plane: named injection points threaded through the data/checkpoint/
  train/serving seams, every injected fault counted and stamped into
  the run record so a run under a fault plan is attributable;
* `flightrec` — crash/hang flight recorder: bounded ring buffers of
  recent steps/incidents dumped as a `graftscope-postmortem-v1` bundle
  on unhandled exception, SIGTERM (host-side state only),
  watchdog hang timeout, or a fatal sentinel incident; read back with
  `graftscope postmortem`.

Backend-free by construction: importing this package (and using trace /
metrics / runlog) never touches a JAX backend — the same discipline as
`analysis/` (tests/test_observability.py proves it under a poisoned
JAX_PLATFORMS). Only `stepstats` and the `xray` analysis functions
touch the backend, lazily, from inside a live train loop where the
backend is already up.

Read telemetry back with `python -m tensor2robot_tpu.bin.graftscope
<model_dir>` (or `scripts/obs_report.sh`); compare runs with
`... graftscope diff <runA> <runB>` / `... graftscope history <dir>`.
"""

from tensor2robot_tpu.obs import (excache, faultlab, flightrec, metrics,
                                  runlog, sentinel, stepstats, trace, xray)

__all__ = ["excache", "faultlab", "flightrec", "metrics", "runlog",
           "sentinel", "stepstats", "trace", "xray"]
