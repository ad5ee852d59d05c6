"""chip_smoke: the system's main path, once, on the chip.

    python chip_smoke.py              # one chip: train, resume, serve,
                                      # kernels, delta_rule, state_space,
                                      # grouped_matmul, barrier
    python chip_smoke.py --multichip  # four chips: dp x fsdp training and
                                      # ring / ulysses sequence parallelism,
                                      # each against its one-chip reference

Every phase goes through the entry point a user would call — the
trainer CLI's path (`config.parse_config_files_and_bindings` +
`train_eval.train_eval_model`), the graftserve CLI's path
(`ExportedModelPredictor` -> `BucketedEngine` -> `MicroBatcher` ->
`loadgen.run_load`), `DeviceCEMPolicy.select_action`, `SessionEngine` —
at the shipped width of a shipped config, with weights made from a seed.
Each phase prints one JSON line; the LAST line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as jax reports it. The script exits non-zero, and
prints no such line, when jax finds no TPU, when the device count is
not the one the mode needs (1, or 4 with --multichip), or when any
phase failed. There is no option or environment variable that turns
this into a CPU run: the CPU rehearsal of each phase lives in
tests/test_chip_smoke.py, which calls the phase functions with tiny
sizes.

One process for each chip: THIS process never initializes a jax backend
(it does not even import jax). It starts one child per phase, strictly
one after another, waits for each to exit (and kills one that outlives
its time limit), and reads the child's result from a file. A child
holds the chip alone for exactly its phase.

What it writes: `chiprun_out/chip_smoke/` beside this file (model
directories and one `<phase>.json` each — removed and made anew on every
run, checkpoints and export bundles removed again at its end) and the
compile cache (`JAX_COMPILATION_CACHE_DIR` if set, else
`.graftcache/` in the checkout), plus the native library the data layer
builds from the committed sources. It needs nothing that git does not
track.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
PACKAGE = os.path.join(ROOT, "tensor2robot_tpu")
TUNED_GIN = os.path.join(PACKAGE, "research", "qtopt", "configs",
                         "train_qtopt_tpu_tuned.gin")
FLASH_GIN = os.path.join(PACKAGE, "configs", "train_longcontext_flash.gin")
SERVE_GIN = os.path.join(PACKAGE, "configs", "serve_qtopt.gin")
SESSION_GIN = os.path.join(PACKAGE, "configs", "serve_session.gin")
HYBRID_GIN = os.path.join(PACKAGE, "configs",
                          "train_qwen3next_ep16share.gin")
MAMBA_GIN = os.path.join(PACKAGE, "configs",
                         "train_nemotron3nano_ep16share.gin")
LAGUNA_GIN = os.path.join(PACKAGE, "configs",
                          "train_laguna_xs2_ep16share.gin")

ONE_CHIP_PHASES = ("train", "resume", "serve", "kernels", "delta_rule",
                   "state_space", "grouped_matmul", "window", "barrier")
MULTICHIP_PHASES = ("multichip_dp", "multichip_sp")
# A phase that needs what an earlier one left on disk is skipped (and
# the run failed) when that one failed.
NEEDS = {"resume": "train", "serve": "train"}
PHASE_TIMEOUT_S = 900.0

# Tolerances, stated once. bf16 carries 8 bits of mantissa (2^-9 ~ 2e-3
# relative per rounding); a loss after a whole bf16 forward+backward
# agrees with its reference to a few roundings, RELATIVE to the loss
# (chip runs of PR 22: 8e-4 at worst, dp x fsdp against one chip; 2e-5
# flash against reference). Neighbouring steps' losses differ by 5e-2 or
# more in every config here, so a swapped step or another batch is
# outside the bound.
LOSS_RTOL = 1e-2
# The session model is f32 with outputs up to ~5, but the TPU runs an
# f32 matmul at default precision as one bf16 pass, so a bf16 bound
# holds between the decode kernel (exact f32 on the VPU) and the XLA
# tick it replaces. The stateless full-prefix forward contracts other
# shapes through a dozen chained matmuls, so it is held to the wider
# bound (first chip run of PR 22: 0.0066 between the arms, 0.0205
# against stateless).
DECODE_ATOL = 2e-2
DECODE_VS_STATELESS_ATOL = 5e-2
# A served value against the predictor's own for the same row and a CEM
# score against the served value for its action, RELATIVE (chip and CPU
# runs of PR 22: the served rows are bit-identical to the predictor's).
SERVE_RTOL = 2e-2
# `gdn_inverse` against XLA's ten float32 products: the values, and the
# closed-form backward against autodiff through the ten RELATIVE to its
# largest entry. Mosaic's float32 contraction is what the interpreted
# tests cannot show (chip runs of PR 34: the values equal to the bit, the
# backward 1.3e-7 of its largest entry).
INVERSE_TOL = 1e-5
# The chunked state-space scan against the token-by-token recurrence,
# RELATIVE to the largest entry of the values and of each gradient:
# float32 products at `highest` differ by the order of their sums alone
# (CPU: 3e-6); with bfloat16 operands, as the training path holds them,
# each product rounds its operands to 8 bits of mantissa.
SCAN_F32_TOL = 1e-4
SCAN_BF16_TOL = 3e-2
# `ops/state_space.ssd_scan`'s Mosaic kernels against autodiff of the XLA
# form they replaced, both with bfloat16 operands, RELATIVE to the largest
# entry of y, the last state and each cotangent: the same products of the
# same rounded operands summed in another order, where one operand that
# rounds the other way moves its term by 2^-8 of itself, and the
# cotangent of the convolution's result leaves in bfloat16 (one rounding,
# up to 2^-7 of the largest entry; CPU, interpreted: 7e-3 at most; the
# chip at the shipped length: 6.4e-3).
SCAN_KERNEL_TOL = 1e-2
# `ops/linear_attention.gated_delta_rule`'s Mosaic kernels against autodiff
# of the XLA form they replaced, both with bfloat16 operands, RELATIVE to
# the largest entry of o, the last state and each cotangent: as
# SCAN_KERNEL_TOL (CPU, interpreted: 7.3e-3 at most, the q columns of the
# convolution's cotangent, which leaves in bfloat16).
RULE_KERNEL_TOL = 1e-2
# `ops/grouped_matmul` with the bfloat16 operands the experts hand it
# against a float32 loop over the groups at `highest`, RELATIVE to the
# largest entry: the forward result is a float32 sum of exact products
# (the order of the sum alone differs); a cotangent leaves in bfloat16,
# the float32 sum rounded once (2^-9 of an entry).
GROUPED_F32_TOL = 1e-4
GROUPED_BF16_TOL = 2.0 ** -7
# The windowed flash kernels (`flash_window_fwd` / `flash_window_bwd`) with
# the bfloat16 operands a sliding layer hands them, against the reference
# `attention(..., window=W)` in float32 at `highest`, RELATIVE to the largest
# entry of the output and of each cotangent: the kernels feed the MXU bf16
# and accumulate in float32, and each output leaves rounded to bfloat16
# (2^-9 of an entry), as the causal kernels do.
WINDOW_TOL = 1e-2


class PhaseFailed(RuntimeError):
  """A check of a phase did not hold."""


def _check(condition, message: str) -> None:
  if not condition:
    raise PhaseFailed(message)


def _close(a: float, b: float, rtol: float) -> bool:
  return abs(a - b) <= rtol * max(abs(a), abs(b))


# ---------------------------------------------------------------------------
# Child side. Everything below imports jax, and runs in a phase's child.
# ---------------------------------------------------------------------------


def _device_record(want) -> dict:
  """jax's device, checked: the phase runs on the platform and device
  count of `want`, or not at all."""
  import jax

  devices = jax.devices()
  record = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
  _check((record["platform"], record["count"]) == tuple(want),
         f"this phase needs {want[1]} {want[0]} device(s); jax has "
         f"{record}")
  return record


def _peak_device_bytes() -> list:
  import jax

  return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
          for d in jax.devices()]


class _XlaCacheLog:
  """Which modules jax's persistent compilation cache served and which
  it compiled, read off jax's own (debug-level) log lines."""

  def __init__(self):
    import logging

    self.hits, self.misses = [], []
    outer = self

    class Handler(logging.Handler):
      def emit(self, record):
        message = record.getMessage()
        if message.startswith("Persistent compilation cache hit"):
          outer.hits.append(str(record.args[0]))
        elif message.startswith("PERSISTENT COMPILATION CACHE MISS"):
          outer.misses.append(str(record.args[0]))

    logger = logging.getLogger("jax._src.compiler")
    logger.setLevel(logging.DEBUG)
    logger.propagate = False  # debug lines stay out of the output
    logger.addHandler(Handler())

  def block(self) -> dict:
    return {"hits": len(self.hits), "misses": len(self.misses),
            "missed_modules": sorted(set(self.misses))}


def _read_jsonl(path: str) -> list:
  with open(path) as f:
    return [json.loads(line) for line in f if line.strip()]


def _median(values):
  values = sorted(values)
  return values[len(values) // 2] if values else None


def _make_step_clock():
  """A trainer hook (the user-facing extension point) that stamps the
  host clock after `jax.block_until_ready` on each step's new state, and
  keeps each step's loss."""
  import jax

  from tensor2robot_tpu.hooks import core as hooks_lib

  class StepClock(hooks_lib.Hook):

    def __init__(self):
      self.step_times = []   # (step, host seconds) after the barrier
      self.losses = []       # (step, loss)
      self.final_state = None

    def after_step(self, ctx, step, metrics):
      jax.block_until_ready(ctx.get_state())
      self.step_times.append((int(step), time.perf_counter()))
      self.losses.append((int(step), float(metrics["loss"])))

    def end(self, ctx):
      self.final_state = ctx.get_state()

  class Builder(hooks_lib.HookBuilder):

    def __init__(self):
      self.clock = StepClock()

    def create_hooks(self, model, model_dir):
      return [self.clock]

  return Builder()


def _run_trainer(config_files, bindings, **kwargs):
  """`bin/run_t2r_trainer.py`'s own path, plus the step clock; returns
  (final metrics, clock, seconds from the call to each step's end)."""
  from tensor2robot_tpu import train_eval
  from tensor2robot_tpu.utils import config

  config.clear_config()
  config.parse_config_files_and_bindings(list(config_files), list(bindings))
  builder = _make_step_clock()
  start = time.perf_counter()
  final = train_eval.train_eval_model(hook_builders=[builder], **kwargs)
  clock = builder.clock
  since_start = [(step, t - start) for step, t in clock.step_times]
  return final, clock, since_start


def _trainer_summary(model_dir, final, clock, since_start) -> dict:
  """The numbers every trainer phase prints, from the clock hook and the
  run's own records (`runs.jsonl`, `train/metrics.jsonl`)."""
  import numpy as np

  record = _read_jsonl(os.path.join(model_dir, "runs.jsonl"))[-1]
  losses = [loss for _, loss in clock.losses]
  _check(losses and all(np.isfinite(losses)),
         f"non-finite training loss: {clock.losses}")
  stamps = [t for _, t in clock.step_times]
  walls = [b - a for a, b in zip(stamps, stamps[1:])]
  stats = [r for r in _read_jsonl(os.path.join(model_dir, "train",
                                               "metrics.jsonl"))
           if "device_wait_ms" in r and not r.get("compile")]
  compile_records = {r["name"]: r for r in record.get("compile", [])}
  step_record = compile_records.get("train_step", {})
  return {
      "steps": [step for step, _ in clock.losses],
      "loss_first": losses[0], "loss_last": losses[-1],
      "final_metrics": {k: v for k, v in final.items()
                        if np.isfinite(v)},
      "seconds_to_first_step": since_start[0][1],
      # Host clock between two steps' ends, each closed by
      # block_until_ready; includes the host making the next batch.
      "step_seconds_wall_median": _median(walls),
      "stepstats_device_wait_ms_median": _median(
          [r["device_wait_ms"] for r in stats]),
      "stepstats_data_wait_ms_median": _median(
          [r["data_wait_ms"] for r in stats]),
      "train_step_compile": {
          k: step_record.get(k) for k in ("trace_s", "lower_s",
                                          "compile_s", "cache")},
      "graftcache": record["extra"]["cache"],
      "record_platform": record.get("platform"),
      "final_step": record["extra"]["final_step"],
  }


def _step_build_seconds(compile_record: dict) -> float:
  """Seconds a start spent on the train step's executable: lowering and
  compiling it, or loading it from the cache."""
  return (compile_record["lower_s"] + compile_record["compile_s"]
          + (compile_record["cache"] or {}).get("load_ms", 0.0) / 1e3)


TRAIN_STEPS = 6
RESUME_STEPS = 2


def _train_bindings(model_dir: str, max_steps: int, extra=()) -> list:
  return [
      f"train_eval_model.model_dir = '{model_dir}'",
      f"train_eval_model.max_train_steps = {max_steps}",
      "train_eval_model.eval_steps = 1",
      f"train_eval_model.eval_every_n_steps = {max_steps}",
      f"train_eval_model.checkpoint_every_n_steps = {TRAIN_STEPS}",
      "train_eval_model.log_every_n_steps = 1",
      *extra]


def phase_train(out_dir: str, extra_bindings=(), device=("tpu", 1)) -> dict:
  """Phase 1: the trainer on `train_qtopt_tpu_tuned.gin`, unchanged in
  width — Grasping44, 472x472, batch 256, bf16, EMA. A handful of
  steps, one eval, one checkpoint, one export bundle."""
  device = _device_record(device)
  xla_log = _XlaCacheLog()
  import tensor2robot_tpu.export.export_generator as export_lib
  from tensor2robot_tpu import native

  # The data layer's native library, built here from the committed
  # sources: the CRC32C check vector of RFC 3720, in TFRecord's mask.
  native.require()
  crc = 0xE3069283
  _check(native.masked_crc32c(b"123456789")
         == ((crc >> 15 | crc << 17) + 0xA282EAD8) & 0xFFFFFFFF,
         "the native library's masked_crc32c fails the RFC 3720 vector")

  model_dir = os.path.join(out_dir, "train_model")
  final, clock, since_start = _run_trainer(
      [TUNED_GIN], _train_bindings(model_dir, TRAIN_STEPS, extra_bindings),
      export_generators=[export_lib.DefaultExportGenerator()])
  summary = _trainer_summary(model_dir, final, clock, since_start)
  _check(summary["final_step"] == TRAIN_STEPS, f"ended at {summary}")
  _check(summary["record_platform"] == device["platform"],
         f"run record says platform {summary['record_platform']!r}")
  _check(any(k.startswith("eval/") for k in summary["final_metrics"]),
         f"no eval ran: {summary['final_metrics']}")
  checkpoints = os.listdir(os.path.join(model_dir, "checkpoints"))
  _check(str(TRAIN_STEPS) in checkpoints, f"no checkpoint: {checkpoints}")
  export_root = os.path.join(model_dir, "export")
  bundles = [d for d in os.listdir(export_root) if d.isdigit()]
  _check(len(bundles) == 1, f"expected one export bundle: {bundles}")
  return {"phase": "train", "ok": True, "device": device,
          "config": os.path.relpath(TUNED_GIN, ROOT),
          "export_bundle": os.path.join(export_root, bundles[0]),
          **summary, "xla_cache": xla_log.block(),
          "native_library": "built",
          "peak_device_bytes": _peak_device_bytes()}


def phase_resume(out_dir: str, extra_bindings=(), device=("tpu", 1)) -> dict:
  """Phase 1, second start: a NEW process resumes the same model_dir and
  takes two more steps. The train step — it donates its mesh-sharded
  state — must come out of the cache the first start filled."""
  device = _device_record(device)
  xla_log = _XlaCacheLog()
  model_dir = os.path.join(out_dir, "train_model")
  cold = json.load(open(os.path.join(out_dir, "train.json")))
  final, clock, since_start = _run_trainer(
      [TUNED_GIN],
      _train_bindings(model_dir, TRAIN_STEPS + RESUME_STEPS,
                      extra_bindings))
  summary = _trainer_summary(model_dir, final, clock, since_start)
  _check(summary["steps"] == list(range(TRAIN_STEPS + 1,
                                        TRAIN_STEPS + RESUME_STEPS + 1)),
         f"did not resume from step {TRAIN_STEPS}: {summary['steps']}")
  cache = summary["graftcache"]
  _check(cache.get("counter/cache/hits", 0) >= 1
         and cache.get("counter/cache/misses", 0) == 0
         and (summary["train_step_compile"]["cache"] or {}).get("hit"),
         f"the resumed train step was meant to hit the cache: {cache} "
         f"{summary['train_step_compile']}")
  # What this start still compiled (jax's plain-jit cache tier), by
  # module name, and which of those names the first start compiled too
  # (same name, other shapes: a restore has jits a fresh start has not).
  xla = xla_log.block()
  xla["missed_modules_cold_compiled_too"] = sorted(
      set(xla["missed_modules"])
      & set(cold["xla_cache"]["missed_modules"]))
  # Seconds are compared only where the first start did compile the
  # step: on a machine that kept the cache from an earlier run it loaded
  # it too, and the hit above is the whole proof.
  built_s = _step_build_seconds(summary["train_step_compile"])
  cold_built_s = _step_build_seconds(cold["train_step_compile"])
  _check((cold["train_step_compile"]["cache"] or {}).get("hit")
         or built_s < 0.5 * cold_built_s,
         f"loading the train step took {built_s:.2f} s, compiling it "
         f"{cold_built_s:.2f} s")
  return {"phase": "resume", "ok": True, "device": device, **summary,
          "train_step_build_seconds": built_s,
          "train_step_build_seconds_cold": cold_built_s,
          "seconds_to_first_step_cold": cold["seconds_to_first_step"],
          "xla_cache": xla, "peak_device_bytes": _peak_device_bytes()}


SERVE_REQUEST_ROWS = (1, 2, 3, 5, 8, 16, 64)
SERVE_CONCURRENCY = 4
SERVE_REQUESTS_PER_THREAD = 12
CEM_CALLS = 5


def phase_serve(out_dir: str, extra_bindings=(), device=("tpu", 1)) -> dict:
  """Phase 2: `bin/run_graftserve.py`'s path against the bundle phase 1
  exported, under `configs/serve_qtopt.gin`; then `DeviceCEMPolicy` at
  the reference cost (64 samples x 3 iterations, 10 elites) on the same
  weights."""
  device = _device_record(device)
  import numpy as np

  from tensor2robot_tpu import serving, specs as specs_lib
  from tensor2robot_tpu.obs import excache
  from tensor2robot_tpu.obs import metrics as obs_metrics
  from tensor2robot_tpu.policies import device_cem
  from tensor2robot_tpu.predictors import predictors as predictors_lib
  from tensor2robot_tpu.serving import loadgen
  from tensor2robot_tpu.utils import config

  config.clear_config()
  config.parse_config_files_and_bindings([SERVE_GIN], list(extra_bindings))
  export_dir = os.path.join(out_dir, "train_model", "export")
  predictor = predictors_lib.ExportedModelPredictor(export_dir=export_dir)
  _check(predictor.restore(), f"no valid export bundle under {export_dir}")
  spec = predictor.get_feature_specification()

  def make_request(i):
    rows = SERVE_REQUEST_ROWS[i % len(SERVE_REQUEST_ROWS)]
    return dict(specs_lib.make_random_numpy(spec, batch_size=rows,
                                            seed=i).items())

  engine = serving.BucketedEngine(predictor=predictor,
                                  cache=excache.cache_root(),
                                  cache_namespace="serve/engine")
  start = time.perf_counter()
  engine.warmup()
  warmup_s = time.perf_counter() - start
  warm_compiles = engine.compile_count
  with serving.MicroBatcher(backend=engine) as batcher:
    # What comes out is right: the batched, padded, bucketed answer to a
    # small request is the predictor's own answer to it, row by row, on
    # the critic's least saturated output (a young bf16 critic's Q is
    # exactly 0.5, its logit is not). The rows' images run from black to
    # full brightness; how far the answers spread over them is printed,
    # because it bounds what this parity can see (run 4 of PR 22: a
    # six-step Grasping44 answers every image alike — PERF.md).
    probe = make_request(2)
    rows = len(probe["state/image"])
    probe["state/image"] = (
        probe["state/image"]
        * np.linspace(0.0, 1.0, rows).reshape(-1, 1, 1, 1)).astype(np.uint8)
    want = predictor.predict(probe)
    got = batcher.predict(probe, deadline_ms=60_000.0)
    q_key = "logits" if "logits" in want else "q_predicted"
    _check(set(got) == set(want) and all(
        got[key].shape == want[key].shape
        and np.all(np.isfinite(got[key]))
        and np.allclose(got[key], want[key], rtol=SERVE_RTOL, atol=0.0)
        for key in want),
           f"served {got} for the predictor's {want}")
    load = loadgen.run_load(batcher.predict, make_request,
                            concurrency=SERVE_CONCURRENCY,
                            requests_per_thread=SERVE_REQUESTS_PER_THREAD)
  snap = obs_metrics.snapshot(prefix="serve/")
  compiles_after_warmup = engine.compile_count - warm_compiles
  fallbacks = snap.get("counter/serve/engine/exec_fallbacks", 0.0)
  _check(compiles_after_warmup == 0 and fallbacks == 0,
         f"{compiles_after_warmup} compile(s), {fallbacks} fallback(s) "
         "after warm-up")
  sheds = {k.rsplit("/", 1)[-1]: v for k, v in snap.items()
           if k.startswith("counter/serve/batcher/shed_")}
  _check(load["ok"] + sum(load["errors"].values()) == load["requests"]
         and load["ok"] > 0
         and set(load["errors"]) <= {"ShedError", "DeadlineError"},
         f"load test lost requests: {load}")

  # CEM on the same weights: the state the predictor serves, scored on
  # the same output as the probe above.
  model = predictor.model
  state = predictor.serving_bundle().get_state()
  image_spec = spec["state/image"]
  action_size = int(spec["action/action"].shape[-1])
  policy = device_cem.DeviceCEMPolicy(model=model, state=state,
                                      action_size=action_size, q_key=q_key)
  image = np.random.RandomState(0).randint(
      0, 255, tuple(image_spec.shape), np.uint8)
  cem_ms = []
  for _ in range(1 + CEM_CALLS):  # the first call compiles
    start = time.perf_counter()
    action = policy.select_action({"image": image})
    cem_ms.append((time.perf_counter() - start) * 1e3)
    _check(action.shape == (action_size,) and np.all(np.isfinite(action))
           and np.all(np.abs(action) <= 1.0),
           f"CEM action out of range: {action}")
  # The score CEM reports for its action is the served critic's value for
  # that image and action. Served beside it: the same image under two
  # other actions, which shows how far these weights tell actions apart.
  actions = np.stack([action, -action, np.zeros_like(action)])
  served = np.ravel(predictor.predict({
      "state/image": np.repeat(image[None], len(actions), axis=0),
      "action/action": actions.astype(np.float32)})[q_key])
  _check(_close(policy.last_q_value, float(served[0]), SERVE_RTOL),
         f"CEM score {policy.last_q_value} != served {q_key} {served[0]}")
  return {
      "phase": "serve", "ok": True, "device": device,
      "config": os.path.relpath(SERVE_GIN, ROOT),
      "global_step": predictor.global_step, "buckets": engine.buckets,
      "warmup_seconds": warmup_s, "warmup_compiles": warm_compiles,
      "warmup_cache_loads": engine.cache_loads,
      "requests": load["requests"], "answered": load["ok"],
      "errors": load["errors"], "sheds": sheds,
      "compiles_after_warmup": compiles_after_warmup,
      "request_ms": loadgen.latency_percentiles(),
      "probe": {"output": q_key, "rows": rows, "rtol": SERVE_RTOL,
                "max_abs_error": float(np.max(np.abs(
                    got[q_key] - want[q_key]))),
                "max_abs_value": float(np.max(np.abs(want[q_key]))),
                "spread_over_rows": float(np.ptp(want[q_key]))},
      "cem": {"samples": 64, "iterations": 3, "elites": 10,
              "first_call_ms": cem_ms[0],
              "ms_per_action_median": _median(cem_ms[1:]),
              "output": q_key, "score": policy.last_q_value,
              "served": float(served[0]),
              "served_spread_over_actions": float(np.ptp(served))},
      "timing_note": "smoke timing, not a result",
      "peak_device_bytes": _peak_device_bytes()}


def _trainer_step(config_file, bindings):
  """The train step `train_eval_model` built for a config in an earlier
  run of this script, built again the trainer's way — through
  `analyze_jit` and the cache root. The key computed here must be the
  trainer's own, so the executable comes back out of the cache: a miss
  means this recipe has drifted from the trainer's, and fails the phase.
  Returns (compiled, its xray record, a fresh state, one placed batch's
  features and labels)."""
  import jax

  from tensor2robot_tpu import modes
  from tensor2robot_tpu.obs import excache, xray
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.parallel import train_step as ts
  from tensor2robot_tpu.utils import config

  config.clear_config()
  config.parse_config_files_and_bindings([config_file], list(bindings))
  model = config.query_parameter("train_eval_model.model")
  generator = config.query_parameter(
      "train_eval_model.input_generator_train")
  generator.set_specification_from_model(model, modes.TRAIN)
  batch = next(generator.create_dataset(modes.TRAIN))
  mesh = mesh_lib.create_mesh()
  if hasattr(model, "set_mesh"):
    model.set_mesh(mesh)
  state, shardings = ts.create_train_state(
      model, jax.random.PRNGKey(0), batch["features"], mesh=mesh)
  step = ts.make_train_step(model, mesh=mesh, shardings=shardings)
  features, labels = mesh_lib.place_batch(mesh, batch)
  compiled, record = xray.analyze_jit(
      "train_step", step, state, features, labels,
      cache=excache.cache_root())
  _check((record.get("cache") or {}).get("hit"),
         f"not the step the trainer ran: {record.get('cache')}")
  return compiled, record, state, features, labels


def phase_kernels(out_dir: str, extra_bindings=(), device=("tpu", 1)
                  ) -> dict:
  """Phase 3: both Pallas kernels on the main path. (a) two train steps
  of `train_longcontext_flash.gin` as shipped against the same seed on
  reference attention; (b) a `SessionEngine` from `serve_session.gin`
  whose decode-kernel gate resolves by itself, tick by tick over one
  full horizon against an engine with the kernel off."""
  device = _device_record(device)
  import numpy as np

  from tensor2robot_tpu import serving
  from tensor2robot_tpu.predictors import predictors as predictors_lib
  from tensor2robot_tpu.utils import config

  # -- (a) flash attention inside the train step ----------------------------
  losses = {}
  for backend in ("flash", "reference"):
    model_dir = os.path.join(out_dir, f"seq_{backend}")
    _, clock, _ = _run_trainer(
        [FLASH_GIN],
        [f"train_eval_model.model_dir = '{model_dir}'",
         "train_eval_model.max_train_steps = 2",
         "train_eval_model.log_every_n_steps = 1",
         f"SequenceRegressionModel.attention_backend = '{backend}'",
         *extra_bindings])
    losses[backend] = [loss for _, loss in clock.losses]
  _check(len(losses["flash"]) == 2 and all(
      np.isfinite(a) and _close(a, b, LOSS_RTOL)
      for a, b in zip(losses["flash"], losses["reference"])),
      f"flash and reference losses disagree: {losses}")
  compiled, record, *_ = _trainer_step(FLASH_GIN, extra_bindings)
  flash_step = {
      "cache": record.get("cache"),
      "tpu_custom_calls": compiled.as_text().count("tpu_custom_call")}
  on_tpu = device["platform"] == "tpu"
  _check(flash_step["tpu_custom_calls"] > 0 or not on_tpu,
         "the compiled flash train step holds no tpu_custom_call")

  # -- (b) the fused decode tick in a session engine ------------------------
  config.clear_config()
  config.parse_config_files_and_bindings([SESSION_GIN], [])
  from tensor2robot_tpu.models import sequence_model

  model = sequence_model.SequenceRegressionModel()
  predictor = predictors_lib.CheckpointPredictor(model=model,
                                                 model_dir="/nonexistent")
  predictor.init_randomly()
  horizon = model.decode_max_ticks
  arms = {}
  outputs = {}
  for name, requested in (("auto", None), ("kernel_off", False)):
    engine = serving.SessionEngine(predictor=predictor,
                                   use_decode_kernel=requested)
    engine.warmup()
    lanes = engine.buckets[-1]
    obs = np.random.RandomState(7).randn(
        lanes, horizon, int(model.decode_observation_spec[
            "observation"].shape[-1])).astype(np.float32)
    sids = [engine.open() for _ in range(lanes)]
    ticks, tick_ms = [], []
    for t in range(horizon):
      start = time.perf_counter()
      out = engine.step_many([(sid, {"observation": obs[lane, t]})
                              for lane, sid in enumerate(sids)])
      tick_ms.append((time.perf_counter() - start) * 1e3)
      ticks.append(np.stack([o["action"] for o in out]))
    for sid in sids:
      engine.close_session(sid)
    outputs[name] = np.stack(ticks, axis=1)  # [lanes, T, action]
    arms[name] = {"decode_kernel_active": engine.decode_kernel_active,
                  "reason": engine.decode_kernel_reason,
                  "ms_per_tick_median": _median(tick_ms[1:]),
                  "compiles": engine.compile_count}
  if on_tpu:
    _check(arms["auto"]["decode_kernel_active"] is True,
           f"the decode-kernel gate did not resolve on: {arms['auto']}")
  _check(arms["kernel_off"]["decode_kernel_active"] is False, str(arms))
  stateless = predictor.predict({"observation": obs})["action"]
  errors = {
      "auto_vs_kernel_off": float(np.max(np.abs(
          outputs["auto"] - outputs["kernel_off"]))),
      "auto_vs_stateless": float(np.max(np.abs(
          outputs["auto"] - stateless))),
  }
  _check(np.all(np.isfinite(outputs["auto"]))
         and errors["auto_vs_kernel_off"] <= DECODE_ATOL
         and errors["auto_vs_stateless"] <= DECODE_VS_STATELESS_ATOL,
         f"decode arms disagree beyond {DECODE_ATOL} (each other) / "
         f"{DECODE_VS_STATELESS_ATOL} (stateless): {errors}")
  return {"phase": "kernels", "ok": True, "device": device,
          "flash": {"config": os.path.relpath(FLASH_GIN, ROOT),
                    "losses": losses, "loss_rtol": LOSS_RTOL,
                    **flash_step},
          "decode": {"config": os.path.relpath(SESSION_GIN, ROOT),
                     "horizon": horizon, "lanes": lanes, "arms": arms,
                     "max_abs_error": errors,
                     "max_abs_output": float(np.max(np.abs(stateless))),
                     "atol": {"auto_vs_kernel_off": DECODE_ATOL,
                              "auto_vs_stateless":
                                  DECODE_VS_STATELESS_ATOL}},
          "timing_note": "smoke timing, not a result",
          "peak_device_bytes": _peak_device_bytes()}


def phase_delta_rule(out_dir: str, extra_bindings=(), device=("tpu", 1)
                     ) -> dict:
  """Phase 4: the delta rule's triangular inverse, `gdn_inverse`, at the
  chunked layout `train_qwen3next_ep16share.gin` gives it (64 chunks x 1
  x 32 heads of [64, 64] as shipped), the way the chunked form takes it
  (Mosaic on the TPU), against the XLA doubling product it replaced:
  the values, and the closed-form backward against autodiff. Then the op
  the model runs, `gated_delta_rule` (Mosaic kernels on the TPU), against
  autodiff of `gated_delta_rule_chunked` at the shipped length, batch and
  heads, q, k and v read from one [B, T, 2 H_k d_k + H_v d_v] operand as
  the mixer hands them: o, the last state and every cotangent (the q, k
  and v columns, g, beta)."""
  del out_dir  # leaves nothing on disk
  device = _device_record(device)
  import jax
  import jax.numpy as jnp
  import numpy as np

  from tensor2robot_tpu.ops import linear_attention
  from tensor2robot_tpu.utils import config

  config.clear_config()
  try:
    config.parse_config_files_and_bindings([HYBRID_GIN],
                                           list(extra_bindings))
    sizes = [config.query_parameter(name) for name in (
        "HybridDecoderLM.sequence_length",
        "DefaultRandomInputGenerator.batch_size",
        "HybridDecoderLM.linear_num_value_heads",
        "HybridDecoderLM.linear_num_key_heads",
        "HybridDecoderLM.linear_key_head_dim",
        "HybridDecoderLM.linear_value_head_dim")]
    interpret = config.query_parameter(
        "HybridDecoderLM.device_type") != "tpu"
  finally:
    config.clear_config()
  chunk = 64
  shape = (sizes[0] // chunk, sizes[1], sizes[2], chunk, chunk)
  rng = np.random.default_rng(34)
  a = jnp.asarray(np.tril(rng.normal(size=shape) * 0.25, -1), jnp.float32)
  probe = jnp.asarray(rng.normal(size=shape), jnp.float32)
  strictly_lower = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

  arms, text = {}, {}
  for name, inverse in (
      ("kernel", lambda x: linear_attention._inverse_of_unit_lower(
          x, interpret)),
      ("xla", linear_attention._doubling_inverse)):
    forward = jax.jit(inverse)
    backward = jax.jit(jax.grad(
        lambda x, inverse=inverse: jnp.sum(
            inverse(jnp.where(strictly_lower, x, 0.0)) * probe)))
    text[name] = forward.lower(a).compile().as_text()
    arms[name] = (np.asarray(forward(a)), np.asarray(backward(a)))
  errors = {
      "values": float(np.max(np.abs(arms["kernel"][0] - arms["xla"][0]))),
      "backward": float(np.max(np.abs(arms["kernel"][1] - arms["xla"][1]))),
  }
  largest = {"values": float(np.max(np.abs(arms["xla"][0]))),
             "backward": float(np.max(np.abs(arms["xla"][1])))}
  _check(all(np.all(np.isfinite(x)) for x in arms["kernel"])
         and errors["values"] <= INVERSE_TOL * max(1.0, largest["values"])
         and errors["backward"] <= INVERSE_TOL * largest["backward"],
         f"gdn_inverse and the XLA product disagree beyond {INVERSE_TOL}: "
         f"{errors} where the largest entries are {largest}")
  if device["platform"] == "tpu":
    _check("gdn_inverse" in text["kernel"]
           and "tpu_custom_call" in text["kernel"]
           and "tpu_custom_call" not in text["xla"],
           "the compiled inverse holds no gdn_inverse custom call")
  kernels = _rule_kernels_against_xla(sizes[0], sizes[1], *sizes[2:], chunk,
                                      rng)
  mosaic = kernels.pop("mosaic")
  _check(device["platform"] != "tpu" or mosaic,
         "the compiled op holds no gdn_scan / gdn_scan_bwd custom call")
  return {"phase": "delta_rule", "ok": True, "device": device,
          "config": os.path.relpath(HYBRID_GIN, ROOT), "shape": list(shape),
          "interpreted": interpret, "max_abs_error": errors,
          "max_abs_entry": largest, "tolerance": INVERSE_TOL,
          "kernels": kernels, "peak_device_bytes": _peak_device_bytes()}


def _rule_kernels_against_xla(t, b, v_heads, k_heads, d_k, d_v, chunk,
                              rng) -> dict:
  """`gated_delta_rule` against autodiff of `gated_delta_rule_chunked` on
  one [B, T, 2 H_k d_k + H_v d_v] operand, bfloat16 operands, at the given
  sizes; checked against RULE_KERNEL_TOL. Returns the errors relative to
  each largest entry and whether the compiled op holds both kernels."""
  import jax
  import jax.numpy as jnp
  import numpy as np

  from tensor2robot_tpu.ops import linear_attention

  normal = lambda *shape: jnp.asarray(  # noqa: E731
      rng.normal(size=shape), jnp.float32)
  key_dim = k_heads * d_k
  args = (normal(b, t, 2 * key_dim + v_heads * d_v).astype(jnp.bfloat16),
          # g as the decoder makes it: -A softplus(.), A from 1 to 16
          -jnp.linspace(1.0, 16.0, v_heads) * jax.nn.softplus(
              normal(b, t, v_heads) - 4.0),
          jax.nn.sigmoid(normal(b, t, v_heads)))
  cotangents = (normal(b, t, v_heads, d_v), normal(b, v_heads, d_k, d_v))

  def xla(mixed, g, beta):
    q, k = (jnp.repeat(mixed[..., i * key_dim:(i + 1) * key_dim].reshape(
        b, t, k_heads, d_k), v_heads // k_heads, axis=2) for i in (0, 1))
    v = mixed[..., 2 * key_dim:].reshape(b, t, v_heads, d_v)
    return linear_attention.gated_delta_rule_chunked(
        q, k, v, g, beta, chunk_size=chunk, matmul_dtype=jnp.bfloat16)

  def kernel(*xs):
    return linear_attention.gated_delta_rule(
        *xs, k_heads, d_k, chunk_size=chunk, matmul_dtype=jnp.bfloat16)

  def arm(rule):
    def run(*xs):
      out, vjp = jax.vjp(rule, *xs)
      return out + vjp(cotangents)
    return jax.jit(run)

  names = ("o", "state", "mixed", "g", "beta")
  want = [np.asarray(v, np.float32) for v in arm(xla)(*args)]
  got = [np.asarray(v, np.float32) for v in arm(kernel)(*args)]
  parts = dict(zip(names, zip(got, want)))
  cotangent = parts.pop("mixed")
  for i, name in enumerate("qkv"):
    cols = slice(i * key_dim, (i + 1) * key_dim if i < 2 else None)
    parts[name] = tuple(v[..., cols] for v in cotangent)
  errors = {k: float(np.max(np.abs(x - w)) / np.max(np.abs(w)))
            for k, (x, w) in parts.items()}
  _check(all(np.isfinite(e) and e <= RULE_KERNEL_TOL
             for e in errors.values()),
         f"gated_delta_rule and autodiff of gated_delta_rule_chunked "
         f"disagree beyond {RULE_KERNEL_TOL} of the largest entry: {errors}")
  text = arm(kernel).lower(*args).compile().as_text()
  return {"length": t, "relative_error": errors,
          "tolerance": RULE_KERNEL_TOL,
          "mosaic": "%gdn_scan." in text and "%gdn_scan_bwd." in text
                    and "tpu_custom_call" in text}


def phase_state_space(out_dir: str, extra_bindings=(), device=("tpu", 1)
                      ) -> dict:
  """Phase 5: the Mamba-2 scan in chunks (`ops/state_space.ssd_chunked`,
  the training path) against the recurrence it stands for
  (`ssd_recurrent`), at the heads, groups, state and chunk
  `train_nemotron3nano_ep16share.gin` gives it and an eighth of its length
  (the recurrence keeps a [64, 64, 128] state a token for its backward):
  values and gradients, with float32 products at `highest` and with the
  bfloat16 operands the model hands it. Then the op the model runs,
  `ssd_scan` (Mosaic kernels on the TPU), against autodiff of
  `ssd_chunked` at the shipped length and batch, x, B and C read from one
  [B, T, H P + 2 G N] operand as the mixer hands them: y, the last state
  and every cotangent (x, B, C, dt, a_log, D)."""
  del out_dir  # leaves nothing on disk
  device = _device_record(device)
  import jax
  import jax.numpy as jnp
  import numpy as np

  from tensor2robot_tpu.ops import state_space
  from tensor2robot_tpu.utils import config

  config.clear_config()
  try:
    config.parse_config_files_and_bindings([MAMBA_GIN], list(extra_bindings))
    t, b, h, p, g, n, chunk = [config.query_parameter(name) for name in (
        "HybridDecoderLM.sequence_length",
        "DefaultRandomInputGenerator.batch_size",
        "HybridDecoderLM.mamba_num_heads", "HybridDecoderLM.mamba_head_dim",
        "HybridDecoderLM.n_groups", "HybridDecoderLM.ssm_state_size",
        "HybridDecoderLM.chunk_size")]
  finally:
    config.clear_config()
  length, t = t, max(t // 8, 1)
  rng = np.random.default_rng(35)
  normal = lambda *shape: jnp.asarray(  # noqa: E731
      rng.normal(size=shape), jnp.float32)
  args = (normal(b, t, h, p),
          jax.nn.softplus(normal(b, t, h) - 3.0),       # dt of 0.01-0.3
          jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
          normal(b, t, g, n), normal(b, t, g, n), jnp.ones((h,)))
  probe = normal(b, t, h, p)

  def arm(scan):
    def loss(*xs):
      y, state = scan(*xs)
      return jnp.sum(y * probe) + jnp.sum(state * state)
    return jax.jit(lambda *xs: (scan(*xs)[0],)
                   + jax.grad(loss, argnums=(0, 1, 3, 4))(*xs))

  with jax.default_matmul_precision("highest"):
    want = [np.asarray(x) for x in arm(state_space.ssd_recurrent)(*args)]
    got = {"float32": arm(lambda *xs: state_space.ssd_chunked(
        *xs, chunk_size=chunk))(*args)}
  got["bfloat16"] = arm(lambda *xs: state_space.ssd_chunked(
      *xs, chunk_size=chunk, matmul_dtype=jnp.bfloat16))(*args)
  names = ("values", "dx", "ddt", "db", "dc")
  largest = {k: float(np.max(np.abs(w))) for k, w in zip(names, want)}
  errors = {
      kind: {k: float(np.max(np.abs(np.asarray(x) - w))) / largest[k]
             for k, x, w in zip(names, outs, want)}
      for kind, outs in got.items()}
  for kind, tolerance in (("float32", SCAN_F32_TOL),
                          ("bfloat16", SCAN_BF16_TOL)):
    _check(all(np.isfinite(e) and e <= tolerance
               for e in errors[kind].values()),
           f"the chunked scan ({kind}) and the recurrence disagree beyond "
           f"{tolerance} of the largest entry: {errors[kind]}")
  kernels = _scan_kernels_against_xla(length, b, h, p, g, n, chunk, rng)
  mosaic = kernels.pop("mosaic")
  _check(device["platform"] != "tpu" or mosaic,
         "the compiled op holds no ssd_scan / ssd_scan_bwd custom call")
  return {"phase": "state_space", "ok": True, "device": device,
          "config": os.path.relpath(MAMBA_GIN, ROOT),
          "shape": {"batch": b, "length": t, "heads": h, "head_dim": p,
                    "groups": g, "state": n, "chunk": chunk},
          "relative_error": errors, "max_abs_entry": largest,
          "tolerance": {"float32": SCAN_F32_TOL, "bfloat16": SCAN_BF16_TOL},
          "kernels": kernels, "peak_device_bytes": _peak_device_bytes()}


def _scan_kernels_against_xla(t, b, h, p, g, n, chunk, rng) -> dict:
  """`ssd_scan` against autodiff of `ssd_chunked` on one [B, T, H P + 2 G N]
  operand, bfloat16 operands, at the given sizes; checked against
  SCAN_KERNEL_TOL. Returns the errors relative to each largest entry and
  whether the compiled op holds both kernels."""
  import jax
  import jax.numpy as jnp
  import numpy as np

  from tensor2robot_tpu.ops import state_space

  normal = lambda *shape: jnp.asarray(  # noqa: E731
      rng.normal(size=shape), jnp.float32)
  x_end, b_end = h * p, h * p + g * n
  args = (normal(b, t, b_end + g * n).astype(jnp.bfloat16),
          jax.nn.softplus(normal(b, t, h) - 3.0),
          jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)), normal(h))
  cotangents = (normal(b, t, h * p), normal(b, h, p, n))

  def xla(mixed, dt, a_log, d):
    y, last = state_space.ssd_chunked(
        mixed[..., :x_end].reshape(b, t, h, p), dt, a_log,
        mixed[..., x_end:b_end].reshape(b, t, g, n),
        mixed[..., b_end:].reshape(b, t, g, n), d, chunk_size=chunk,
        matmul_dtype=jnp.bfloat16)
    return y.reshape(b, t, h * p), last

  def kernel(*xs):
    return state_space.ssd_scan(*xs, g, n, chunk_size=chunk,
                                matmul_dtype=jnp.bfloat16)

  def arm(scan):
    def run(*xs):
      out, vjp = jax.vjp(scan, *xs)
      return out + vjp(cotangents)
    return jax.jit(run)

  names = ("y", "state", "mixed", "dt", "a_log", "d")
  want = [np.asarray(v, np.float32) for v in arm(xla)(*args)]
  got = [np.asarray(v, np.float32) for v in arm(kernel)(*args)]
  parts = dict(zip(names, zip(got, want)))
  cotangent = parts.pop("mixed")
  for name, cols in (("x", slice(0, x_end)), ("b", slice(x_end, b_end)),
                     ("c", slice(b_end, None))):
    parts[name] = tuple(v[..., cols] for v in cotangent)
  errors = {k: float(np.max(np.abs(x - w)) / np.max(np.abs(w)))
            for k, (x, w) in parts.items()}
  _check(all(np.isfinite(e) and e <= SCAN_KERNEL_TOL
             for e in errors.values()),
         f"ssd_scan and autodiff of ssd_chunked disagree beyond "
         f"{SCAN_KERNEL_TOL} of the largest entry: {errors}")
  text = arm(kernel).lower(*args).compile().as_text()
  return {"length": t, "relative_error": errors,
          "tolerance": SCAN_KERNEL_TOL,
          "mosaic": "%ssd_scan." in text and "%ssd_scan_bwd." in text
                    and "tpu_custom_call" in text}


def phase_grouped_matmul(out_dir: str, extra_bindings=(), device=("tpu", 1)
                         ) -> dict:
  """Phase 6: the experts' grouped products (`ops/grouped_matmul`, the way
  the layer calls it: Mosaic on the TPU) at the buffer, widths and held
  experts `train_nemotron3nano_ep16share.gin` gives an expert layer, up
  and down, bfloat16 operands: the forward result and both cotangents
  against a float32 loop over the groups at `highest`. The balanced load
  is dealt over the groups at random; the rows that hold no pair go to
  the last group, as the layer gives them."""
  del out_dir  # leaves nothing on disk
  device = _device_record(device)
  import jax
  import jax.numpy as jnp
  import numpy as np

  from tensor2robot_tpu.layers import moe
  from tensor2robot_tpu.ops import grouped_matmul
  from tensor2robot_tpu.utils import config

  config.clear_config()
  try:
    config.parse_config_files_and_bindings([MAMBA_GIN], list(extra_bindings))
    t, b, hidden, experts, held, top_k, width, factor = [
        config.query_parameter(name) for name in (
            "HybridDecoderLM.sequence_length",
            "DefaultRandomInputGenerator.batch_size",
            "HybridDecoderLM.hidden_size", "HybridDecoderLM.n_routed_experts",
            "HybridDecoderLM.experts_held",
            "HybridDecoderLM.num_experts_per_tok",
            "HybridDecoderLM.moe_intermediate_size",
            "HybridDecoderLM.expert_buffer_factor")]
  finally:
    config.clear_config()
  groups = held[1]
  rows = moe.ShardedExpertsMoE(
      num_experts=experts, experts_held=tuple(held), top_k=top_k,
      buffer_factor=factor).buffer_rows(t * b)
  rng = np.random.default_rng(36)
  sizes = rng.multinomial(int(rows / factor), np.full(groups, 1.0 / groups))
  sizes[-1] += rows - sizes.sum()
  group_sizes = jnp.asarray(sizes, jnp.int32)
  highest = jax.lax.Precision.HIGHEST

  def loop(product):
    ends = np.cumsum(sizes)
    return [product(g, slice(int(end - size), int(end)))
            for g, (size, end) in enumerate(zip(sizes, ends))]

  def arm(lhs, rhs, cotangent):
    out, vjp = jax.vjp(
        lambda x, w: grouped_matmul.grouped_matmul(x, w, group_sizes),
        lhs, rhs)
    return (out,) + vjp(cotangent)

  def reference(lhs, rhs, cotangent):
    lhs, rhs = lhs.astype(jnp.float32), rhs.astype(jnp.float32)
    dot = lambda x, y: jnp.dot(x, y, precision=highest)  # noqa: E731
    return (
        jnp.concatenate(loop(lambda g, r: dot(lhs[r], rhs[g]))),
        jnp.concatenate(loop(lambda g, r: dot(cotangent[r], rhs[g].T))),
        jnp.stack(loop(lambda g, r: dot(lhs[r].T, cotangent[r]))))

  names = ("values", "dlhs", "drhs")
  errors, largest, text = {}, {}, ""
  for product, (k, n) in (("up", (hidden, width)), ("down", (width, hidden))):
    lhs = jnp.asarray(rng.normal(size=(rows, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(groups, k, n)) * 0.02, jnp.bfloat16)
    cotangent = jnp.asarray(rng.normal(size=(rows, n)), jnp.float32)
    compiled = jax.jit(arm).lower(lhs, rhs, cotangent).compile()
    text += compiled.as_text()
    got = compiled(lhs, rhs, cotangent)
    _check(got[0].dtype == jnp.float32 and got[1].dtype == lhs.dtype
           and got[2].dtype == rhs.dtype,
           f"grouped_matmul ({product}) returned {[x.dtype for x in got]}")
    want = jax.jit(reference)(
        lhs, rhs, cotangent.astype(jnp.bfloat16).astype(jnp.float32))
    largest[product] = {
        name: float(jnp.max(jnp.abs(w))) for name, w in zip(names, want)}
    errors[product] = {
        name: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w)))
        / largest[product][name]
        for name, g, w in zip(names, got, want)}
    _check(np.isfinite(list(errors[product].values())).all()
           and errors[product]["values"] <= GROUPED_F32_TOL
           and max(errors[product]["dlhs"], errors[product]["drhs"])
           <= GROUPED_BF16_TOL,
           f"grouped_matmul ({product}) and the loop over the groups "
           f"disagree: {errors[product]} of the largest entries "
           f"{largest[product]}")
  if device["platform"] == "tpu":
    _check("grouped_matmul" in text and "grouped_matmul_t" in text
           and "tpu_custom_call" in text and "ragged-dot" not in text,
           "the compiled products hold no grouped_matmul custom call")
  return {"phase": "grouped_matmul", "ok": True, "device": device,
          "config": os.path.relpath(MAMBA_GIN, ROOT),
          "shape": {"rows": rows, "groups": groups, "hidden": hidden,
                    "width": width, "group_sizes": sizes.tolist()},
          "relative_error": errors, "max_abs_entry": largest,
          "tolerance": {"values": GROUPED_F32_TOL,
                        "cotangents": GROUPED_BF16_TOL},
          "peak_device_bytes": _peak_device_bytes()}


BARRIER_WINDOWS = 5
BARRIER_STEPS = 4


def phase_window(out_dir: str, extra_bindings=(), device=("tpu", 1)) -> dict:
  """Phase 7: the sliding-window flash kernels at the window, head width,
  group and length `train_laguna_xs2_ep16share.gin` gives its sliding layers
  (8 of their 64 query heads and the one key/value head those share: the
  reference holds a head's whole [T, T] scores), the op the model calls
  (Mosaic on the TPU) with k and v at their own head count against
  `attention(..., window=W)` over k and v repeated to the query heads: the
  output and dq, dk, dv, and the compiled op holds the two windowed kernels
  and no causal one."""
  del out_dir  # leaves nothing on disk
  device = _device_record(device)
  import jax
  import jax.numpy as jnp
  import numpy as np

  from tensor2robot_tpu.ops import attention
  from tensor2robot_tpu.utils import config

  config.clear_config()
  try:
    config.parse_config_files_and_bindings([LAGUNA_GIN], list(extra_bindings))
    t, b, d, window, kv_heads, layer_heads, kinds = [
        config.query_parameter(name) for name in (
            "HybridDecoderLM.sequence_length",
            "DefaultRandomInputGenerator.batch_size",
            "HybridDecoderLM.head_dim", "HybridDecoderLM.sliding_window",
            "HybridDecoderLM.num_key_value_heads",
            "HybridDecoderLM.num_attention_heads_per_layer",
            "HybridDecoderLM.layer_types")]
  finally:
    config.clear_config()
  heads = 8
  group = layer_heads[list(kinds).index("sliding")] // kv_heads
  rng = np.random.default_rng(42)
  q, do = (jnp.asarray(rng.normal(size=(b, t, heads * d)), jnp.float32)
           for _ in range(2))
  k, v = (jnp.asarray(rng.normal(size=(b, t, heads // group * d)),
                      jnp.float32) for _ in range(2))

  def split(x):
    return x.reshape(b, t, -1, d).transpose(0, 2, 1, 3)

  def reference(q, k, v):
    k, v = (jnp.repeat(split(x), group, axis=1) for x in (k, v))
    out = attention.attention(split(q), k, v, causal=True, window=window)
    return out.transpose(0, 2, 1, 3).reshape(b, t, heads * d)

  def kernel(q, k, v):
    return attention.flash_attention(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), heads, causal=True, window=window)

  def arm(fn):
    def run(q, k, v):
      out, vjp = jax.vjp(fn, q, k, v)
      return (out,) + vjp(do.astype(out.dtype))
    return jax.jit(run)

  with jax.default_matmul_precision("highest"):
    want = [np.asarray(x, np.float32) for x in arm(reference)(q, k, v)]
  got = [np.asarray(x, np.float32) for x in arm(kernel)(q, k, v)]
  names = ("out", "dq", "dk", "dv")
  errors = {n: float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
            for n, g, w in zip(names, got, want)}
  _check(all(np.isfinite(e) and e <= WINDOW_TOL for e in errors.values()),
         f"the windowed flash kernels and the band-masked reference "
         f"disagree beyond {WINDOW_TOL} of the largest entry: {errors}")
  text = arm(kernel).lower(q, k, v).compile().as_text()
  mosaic = ("%flash_window_fwd." in text and "%flash_window_bwd." in text
            and "%flash_fwd." not in text and "tpu_custom_call" in text)
  _check(device["platform"] != "tpu" or mosaic,
         "the compiled op holds no flash_window_fwd / flash_window_bwd "
         "custom call, or a causal one")
  return {"phase": "window", "ok": True, "device": device,
          "config": os.path.relpath(LAGUNA_GIN, ROOT),
          "shape": {"batch": b, "length": t, "heads": heads,
                    "kv_heads": heads // group, "head_dim": d,
                    "window": window},
          "relative_error": errors, "tolerance": WINDOW_TOL,
          "mosaic": mosaic, "peak_device_bytes": _peak_device_bytes()}


def phase_barrier(out_dir: str, extra_bindings=(), device=("tpu", 1)) -> dict:
  """Phase 8: is `jax.block_until_ready` a barrier here? The phase-1
  train step on a resident batch, a window of steps closed by
  `block_until_ready`, against the same window closed by a host fetch
  (`utils.backend.state_barrier`), against dispatch alone."""
  device = _device_record(device)
  import jax
  import numpy as np

  from tensor2robot_tpu.utils import backend

  del out_dir
  compiled, record, state, features, labels = _trainer_step(
      TUNED_GIN, extra_bindings)
  for _ in range(2):
    state, metrics = compiled(state, features, labels)
  jax.block_until_ready(state)

  def window(close):
    nonlocal state
    start = time.perf_counter()
    for _ in range(BARRIER_STEPS):
      state, metrics = compiled(state, features, labels)
    dispatched = time.perf_counter()
    close(state)
    closed = time.perf_counter()
    np.asarray(metrics["loss"])  # anything left to wait for shows here
    fetched = time.perf_counter()
    jax.block_until_ready(state)
    return ((dispatched - start) / BARRIER_STEPS,
            (closed - start) / BARRIER_STEPS, fetched - closed)

  readings = {"block_until_ready": [], "host_fetch": []}
  for _ in range(BARRIER_WINDOWS):  # alternate, so drift hits both
    readings["block_until_ready"].append(window(jax.block_until_ready))
    readings["host_fetch"].append(window(backend.state_barrier))
  summary = {}
  for name, rows in readings.items():
    summary[name] = {
        "dispatch_s_per_step": _median([r[0] for r in rows]),
        "closed_s_per_step": _median([r[1] for r in rows]),
        "fetch_after_close_s": _median([r[2] for r in rows])}
  ratio = (summary["block_until_ready"]["closed_s_per_step"]
           / summary["host_fetch"]["closed_s_per_step"])
  _check(np.isfinite(ratio) and ratio > 0, f"bad timings: {summary}")
  return {"phase": "barrier", "ok": True, "device": device,
          "windows": BARRIER_WINDOWS, "steps_per_window": BARRIER_STEPS,
          "train_step_cache": record.get("cache"),
          **summary,
          "block_until_ready_over_host_fetch": ratio,
          # A barrier waits for the device: a window it closes takes as
          # long as one a host fetch closes, and far longer than
          # dispatch alone.
          "block_until_ready_is_a_barrier": bool(ratio > 0.9),
          "peak_device_bytes": _peak_device_bytes()}


# -- four chips ---------------------------------------------------------------


def _spread_report(state, mesh) -> dict:
  """How a TrainState lies across the mesh: every leaf lives on every
  device, and leaves the partition rules shard hold a PART on each."""
  import jax

  n_devices = mesh.devices.size
  leaves = jax.tree_util.tree_leaves(state.params)
  sharded = 0
  for leaf in leaves:
    devices = {s.device for s in leaf.addressable_shards}
    _check(len(leaf.sharding.device_set) == n_devices
           and len(devices) == n_devices,
           f"a {leaf.shape} param lives on {len(devices)} device(s)")
    if not leaf.sharding.is_fully_replicated:
      sharded += 1
      _check(leaf.addressable_shards[0].data.size < leaf.size,
             f"a sharded {leaf.shape} param holds whole copies")
  in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in mesh.devices.flat]
  return {"param_leaves": len(leaves), "sharded_param_leaves": sharded,
          "bytes_in_use": in_use}


MULTICHIP_STEPS = 3


def phase_multichip_dp(out_dir: str, extra_bindings=(),
                       device=("tpu", 4)) -> dict:
  """--multichip (i): the tuned Grasping44 config over a (data, fsdp,
  model) = (2, 2, 1) mesh, global batch 256, against the same seed and
  batch on the first chip alone, in this same process."""
  device = _device_record(device)
  import jax

  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.parallel import train_step as ts

  runs = {}
  for name, mesh in (
      ("four_chips", mesh_lib.create_mesh(mesh_shape=(2, 2, 1))),
      ("one_chip", mesh_lib.create_mesh(mesh_shape=(1, 1, 1),
                                        devices=jax.devices()[:1]))):
    model_dir = os.path.join(out_dir, f"dp_{name}")
    _, clock, since_start = _run_trainer(
        [TUNED_GIN],
        [f"train_eval_model.model_dir = '{model_dir}'",
         f"train_eval_model.max_train_steps = {MULTICHIP_STEPS}",
         "train_eval_model.mode = 'train'",
         "train_eval_model.log_every_n_steps = 1",
         *extra_bindings],
        mesh=mesh, partition_rules=ts.fsdp_rules())
    record = _read_jsonl(os.path.join(model_dir, "runs.jsonl"))[-1]
    _check(record.get("platform") == device["platform"],
           str(record.get("platform")))
    runs[name] = {"losses": [loss for _, loss in clock.losses],
                  "seconds_to_first_step": since_start[0][1],
                  "spread": _spread_report(clock.final_state, mesh)}
    clock.final_state = None  # free the chips for the next arm
  spread = runs["four_chips"]["spread"]
  _check(spread["sharded_param_leaves"] > 0
         and (device["platform"] != "tpu"
              or min(spread["bytes_in_use"]) > (1 << 20)),
         f"state or memory is not spread over four chips: {spread}")
  pairs = list(zip(runs["four_chips"]["losses"], runs["one_chip"]["losses"]))
  _check(len(pairs) == MULTICHIP_STEPS
         and all(_close(a, b, LOSS_RTOL) for a, b in pairs),
         f"four-chip and one-chip losses disagree: {pairs}")
  return {"phase": "multichip_dp", "ok": True, "device": device,
          "mesh": {"data": 2, "fsdp": 2, "model": 1},
          "config": os.path.relpath(TUNED_GIN, ROOT),
          "loss_rtol": LOSS_RTOL, **runs,
          "peak_device_bytes": _peak_device_bytes()}


def phase_multichip_sp(out_dir: str, extra_bindings=(),
                       device=("tpu", 4)) -> dict:
  """--multichip (ii): the sequence family over (data, sp, model) =
  (2, 2, 1) at the long-context widths (hidden 512, 8 heads, T 4096) —
  ring attention, then ulysses with the flash inner kernel — against
  reference attention on the first chip alone."""
  device = _device_record(device)
  import jax

  from tensor2robot_tpu.parallel import mesh as mesh_lib

  sp_axes = ("data", "sp", "model")
  arms = (
      ("ring", ["SequenceRegressionModel.attention_backend = 'ring'"],
       lambda: mesh_lib.create_mesh(mesh_shape=(2, 2, 1),
                                    axis_names=sp_axes)),
      ("ulysses_flash",
       ["SequenceRegressionModel.attention_backend = 'ulysses'",
        "SequenceRegressionModel.ulysses_inner = 'flash'"],
       lambda: mesh_lib.create_mesh(mesh_shape=(2, 2, 1),
                                    axis_names=sp_axes)),
      ("one_chip_reference",
       ["SequenceRegressionModel.attention_backend = 'reference'"],
       lambda: mesh_lib.create_mesh(mesh_shape=(1, 1, 1),
                                    devices=jax.devices()[:1])))
  runs = {}
  for name, bindings, make_mesh in arms:
    model_dir = os.path.join(out_dir, f"sp_{name}")
    mesh = make_mesh()
    _, clock, since_start = _run_trainer(
        [FLASH_GIN],
        [f"train_eval_model.model_dir = '{model_dir}'",
         "train_eval_model.max_train_steps = 2",
         "train_eval_model.log_every_n_steps = 1",
         "SequenceRegressionModel.device_type = 'tpu'",
         *bindings, *extra_bindings],
        mesh=mesh)
    record = _read_jsonl(os.path.join(model_dir, "runs.jsonl"))[-1]
    _check(record.get("platform") == device["platform"]
           and record.get("num_devices") == 4, str(record.get("platform")))
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in mesh.devices.flat]
    runs[name] = {"losses": [loss for _, loss in clock.losses],
                  "seconds_to_first_step": since_start[0][1],
                  "bytes_in_use": in_use}
    clock.final_state = None
  want = runs["one_chip_reference"]["losses"]
  for name in ("ring", "ulysses_flash"):
    _check((device["platform"] != "tpu"
            or min(runs[name]["bytes_in_use"]) > 0)
           and all(_close(a, b, LOSS_RTOL)
                   for a, b in zip(runs[name]["losses"], want)),
           f"{name} disagrees with the one-chip reference: {runs}")
  return {"phase": "multichip_sp", "ok": True, "device": device,
          "mesh": {"data": 2, "sp": 2, "model": 1},
          "config": os.path.relpath(FLASH_GIN, ROOT),
          "loss_rtol": LOSS_RTOL, **runs,
          "peak_device_bytes": _peak_device_bytes()}


PHASES = {"train": phase_train, "resume": phase_resume,
          "serve": phase_serve, "kernels": phase_kernels,
          "delta_rule": phase_delta_rule, "state_space": phase_state_space,
          "grouped_matmul": phase_grouped_matmul,
          "window": phase_window, "barrier": phase_barrier,
          "multichip_dp": phase_multichip_dp,
          "multichip_sp": phase_multichip_sp}


def run_phase(name: str, out_dir: str) -> int:
  """A child's whole life: run one phase on the chip, write
  `<out_dir>/<name>.json`, print it as one line."""
  import traceback

  import jax

  # A broken cache is an error here, not a warning.
  jax.config.update("jax_raise_persistent_cache_errors", True)
  try:
    result = PHASES[name](out_dir)
  except BaseException as e:  # noqa: BLE001 - every failure fails the phase
    traceback.print_exc()
    result = {"phase": name, "ok": False,
              "error": f"{type(e).__name__}: {e}"[:2000]}
  line = json.dumps(result, default=float)
  with open(os.path.join(out_dir, f"{name}.json"), "w") as f:
    f.write(line)
  print(line, flush=True)
  return 0 if result["ok"] else 1


# ---------------------------------------------------------------------------
# Parent side: no jax.
# ---------------------------------------------------------------------------


def _run_child(name: str) -> dict:
  result_path = os.path.join(OUT_DIR, f"{name}.json")
  env = dict(os.environ)
  env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
  env.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
  child = subprocess.Popen(
      [sys.executable, "-c",
       "import sys, chip_smoke; "
       f"sys.exit(chip_smoke.run_phase({name!r}, {OUT_DIR!r}))"],
      cwd=ROOT, env=env)
  try:
    child.wait(timeout=PHASE_TIMEOUT_S)
  except subprocess.TimeoutExpired:
    child.kill()
    child.wait()
    return {"phase": name, "ok": False,
            "error": f"killed after {PHASE_TIMEOUT_S:.0f} s"}
  if not os.path.isfile(result_path):
    return {"phase": name, "ok": False,
            "error": f"the child died with code {child.returncode} and "
                     "left no result"}
  with open(result_path) as f:
    return json.load(f)


def main(argv) -> int:
  if argv not in ([], ["--multichip"]):
    print("usage: python chip_smoke.py [--multichip]", file=sys.stderr)
    return 2
  multichip = bool(argv)
  shutil.rmtree(OUT_DIR, ignore_errors=True)
  os.makedirs(OUT_DIR)
  results = {}
  for name in (MULTICHIP_PHASES if multichip else ONE_CHIP_PHASES):
    needed = NEEDS.get(name)
    if needed and not results[needed]["ok"]:
      results[name] = {"phase": name, "ok": False,
                       "error": f"skipped: phase {needed!r} failed"}
      print(json.dumps(results[name]), flush=True)
      continue
    started = time.time()
    results[name] = _run_child(name)
    print(f"chip_smoke: phase {name} "
          f"{'ok' if results[name]['ok'] else 'FAILED'} in "
          f"{time.time() - started:.0f} s", file=sys.stderr, flush=True)
    if len(results) == 1 and "device" not in results[name]:
      # The first phase found no chip (or died before it looked): the
      # others would only find the same.
      break
  # The tool brings back at most 64 MiB: keep each run's records, drop
  # its checkpoints and export bundles.
  for model_dir in os.listdir(OUT_DIR):
    for heavy in ("checkpoints", "export"):
      shutil.rmtree(os.path.join(OUT_DIR, model_dir, heavy),
                    ignore_errors=True)
  failed = [name for name, r in results.items() if not r["ok"]]
  devices = [r["device"] for r in results.values() if "device" in r]
  expected = 4 if multichip else 1
  if failed or not devices or any(
      d != devices[0] or d["platform"] != "tpu" or d["count"] != expected
      for d in devices):
    print(f"chip_smoke: FAILED {failed or devices}", file=sys.stderr)
    return 1
  print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main(sys.argv[1:]))
