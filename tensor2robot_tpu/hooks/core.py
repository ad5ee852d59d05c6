"""Training hooks: the callback protocol replacing Estimator SessionRunHooks.

Reference surface: `HookBuilder` ABC
(/root/reference/hooks/hook_builder.py:27-43), gin operative-config logger
(gin_config_hook_builder.py:28-55), golden-values recorder
(golden_values_hook_builder.py:37-79), variable stats logger
(variable_logger_hook.py:27-62), and the async checkpoint->export
listeners (checkpoint_hooks.py:51-201, async_export_hook_builder.py:
87-134) including the one-version-lagged export dir used by TD3 target
networks.

Here a Hook is a plain object with lifecycle callbacks driven by the
train loop; builders are gin-configurables producing hook lists.
"""

from __future__ import annotations

import abc
import glob
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Mapping, Optional

import jax
import numpy as np

from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.utils import config

__all__ = ["Hook", "HookBuilder", "call_hooks", "ConfigSaverHook",
           "GoldenValuesHook", "VariableLoggerHook", "ExportHook",
           "DefaultHookBuilder", "AsyncExportHookBuilder", "BestExportHook",
           "StepStatsHook", "SentinelHook", "add_golden_outputs"]


class TrainContext:
  """What hooks see: model, dirs, and accessors into the live loop.

  `step_stats` is the loop's live `obs.stepstats.StepStatsRecorder`,
  `sentinel` the run's `obs.sentinel.Sentinel`, `flight_recorder` its
  `obs.flightrec.FlightRecorder` (each None when disabled)."""

  def __init__(self, model, model_dir: str,
               get_state: Callable[[], Any],
               summary_writer=None, mesh=None, step_stats=None,
               sentinel=None, flight_recorder=None):
    self.model = model
    self.model_dir = model_dir
    self.get_state = get_state
    self.summary_writer = summary_writer
    self.mesh = mesh
    self.step_stats = step_stats
    self.sentinel = sentinel
    self.flight_recorder = flight_recorder


class Hook:
  def begin(self, ctx: TrainContext) -> None:
    pass

  def after_step(self, ctx: TrainContext, step: int,
                 metrics: Mapping[str, Any]) -> None:
    pass

  def after_checkpoint(self, ctx: TrainContext, step: int) -> None:
    pass

  def after_rewind(self, ctx: TrainContext, step: int) -> None:
    """Called after a graftguard divergence REWIND restored a verified
    checkpoint (`step` = the step now resumed from). The coordination
    seam an always-on loop needs: a publisher hook drops pending
    publishes above the rewind target; collection-side consumers learn
    the learner stepped back without the run dying."""

  def after_eval(self, ctx: TrainContext, step: int,
                 metrics: Mapping[str, Any]) -> None:
    pass

  def end(self, ctx: TrainContext) -> None:
    pass


def call_hooks(hooks: List[Hook], method: str, *args) -> None:
  """Calls `method` on every hook in order, each inside a `train/hook`
  span (args `hook` = the class name, `method`): the loop's hooks are
  where a device's wait hides, and one span a hook names which."""
  tracer = trace_lib.get_tracer()
  for hook in hooks:
    with tracer.span("train/hook", cat="train", hook=type(hook).__name__,
                     method=method):
      getattr(hook, method)(*args)


class HookBuilder(abc.ABC):
  """Gin-configurable factory of hooks (reference hook_builder.py:27-43)."""

  @abc.abstractmethod
  def create_hooks(self, model, model_dir: str) -> List[Hook]:
    ...


@config.configurable
class ConfigSaverHook(Hook):
  """Writes the operative config to model_dir at train begin (reference
  GinConfigSaverHook, /root/reference/models/abstract_model.py:772-775)."""

  def __init__(self, filename: str = "operative_config-0.gin"):
    self._filename = filename

  def begin(self, ctx: TrainContext) -> None:
    os.makedirs(ctx.model_dir, exist_ok=True)
    with open(os.path.join(ctx.model_dir, self._filename), "w") as f:
      f.write(config.operative_config_str())


_GOLDEN_REGISTRY: Dict[str, Callable] = {}


def add_golden_outputs(name: str, fn: Callable) -> None:
  """Registers a golden-value producer: fn(state) -> dict of arrays
  (reference collection-based add_golden_tensor,
  /root/reference/hooks/golden_values_hook_builder.py:37-39)."""
  _GOLDEN_REGISTRY[name] = fn


@config.configurable
class GoldenValuesHook(Hook):
  """Saves registered golden values + final predict outputs on a fixed
  batch to `golden_values.npy` at train end; guards the
  data->train->checkpoint pipeline against silent regressions."""

  def __init__(self, batch_fn: Optional[Callable] = None,
               filename: str = "golden_values.npy"):
    self._batch_fn = batch_fn
    self._filename = filename

  def end(self, ctx: TrainContext) -> None:
    from tensor2robot_tpu.parallel import train_step as ts

    values: Dict[str, np.ndarray] = {}
    state = ctx.get_state()
    for name, fn in _GOLDEN_REGISTRY.items():
      out = fn(state)
      for key, value in out.items():
        values[f"{name}/{key}"] = np.asarray(value)
    if self._batch_fn is not None:
      predict = ts.make_predict_fn(ctx.model)
      outputs = predict(state, self._batch_fn())
      for key, value in outputs.items():
        values[f"predict/{key}"] = np.asarray(value)
    path = os.path.join(ctx.model_dir, self._filename)
    os.makedirs(ctx.model_dir, exist_ok=True)
    np.save(path, values, allow_pickle=True)


@config.configurable
class VariableLoggerHook(Hook):
  """Logs parameter counts and per-leaf norms (reference
  variable_logger_hook.py:27-62)."""

  def __init__(self, every_n_steps: int = 100, max_num_variables: int = 50):
    self._every_n_steps = every_n_steps
    self._max = max_num_variables

  def after_step(self, ctx, step, metrics) -> None:
    if step % self._every_n_steps:
      return
    from absl import logging

    state = ctx.get_state()
    leaves = jax.tree_util.tree_leaves_with_path(state.params)
    total = sum(int(np.prod(l.shape)) for _, l in leaves)
    logging.info("step %d: %d params in %d arrays", step, total, len(leaves))
    for path, leaf in leaves[:self._max]:
      logging.info("  %s %s |x|=%.4f", jax.tree_util.keystr(path),
                   tuple(leaf.shape), float(jax.numpy.linalg.norm(leaf)))


@config.configurable
class StepStatsHook(Hook):
  """Emits graftscope step records through the run's `SummaryWriter`.

  The loop-side measurement lives in `obs.stepstats.StepStatsRecorder`
  (attached to `TrainContext.step_stats` by `train_eval_model`); this
  hook is the write path: per-step records into `metrics.jsonl`, a final
  metrics-registry snapshot, and the Chrome trace JSON next to it
  (`trace.graftscope.json` — open in Perfetto). Replaces the reference's
  host_call summary plumbing
  (/root/reference/models/abstract_model.py:873-936)."""

  def __init__(self, trace_filename: str = "trace.graftscope.json"):
    self._trace_filename = trace_filename

  def _flush(self, ctx: TrainContext) -> None:
    if ctx.step_stats is None or ctx.summary_writer is None:
      return
    for step, record in ctx.step_stats.drain():
      ctx.summary_writer.write_scalars(step, record)

  def after_step(self, ctx: TrainContext, step: int, metrics) -> None:
    self._flush(ctx)

  def end(self, ctx: TrainContext) -> None:
    from tensor2robot_tpu.obs import metrics as metrics_lib
    from tensor2robot_tpu.obs import trace as trace_lib

    self._flush(ctx)
    if ctx.summary_writer is None:
      return
    snapshot = metrics_lib.snapshot()
    if snapshot:
      step = int(np.asarray(ctx.get_state().step))
      ctx.summary_writer.write_scalars(step, snapshot)
    tracer = trace_lib.get_tracer()
    if tracer.events():
      log_dir = os.path.dirname(ctx.summary_writer.path)
      tracer.save(os.path.join(log_dir, self._trace_filename))


@config.configurable
class SentinelHook(Hook):
  """Feeds per-step HOST-side scalars to the run's `obs.sentinel` and
  publishes its incident summary at train end.

  Auto-appended by `train_eval_model` beside `StepStatsHook` when step
  telemetry is on. The after_step filter matters: per-step metrics
  from a single-step dispatch are still LIVE device arrays (the loop
  only fetches them at the log cadence) and forcing them here would
  add a device round trip per scalar per step;
  `Sentinel.observe_metrics` therefore inspects only
  values that already live on the host (numbers/numpy — e.g. the K-step
  loop path's batched scalar fetch), and the loop additionally feeds
  the log-cadence scalars once they are fetched anyway."""

  def after_step(self, ctx: TrainContext, step: int, metrics) -> None:
    if ctx.sentinel is not None:
      ctx.sentinel.observe_metrics(step, metrics)

  def end(self, ctx: TrainContext) -> None:
    if ctx.sentinel is None or ctx.summary_writer is None:
      return
    summary = ctx.sentinel.summary()
    if summary["incidents"]:
      step = int(np.asarray(ctx.get_state().step))
      ctx.summary_writer.write_scalars(
          step, {"sentinel/incidents": float(summary["incidents"]),
                 **{f"sentinel/{kind}": float(count)
                    for kind, count in summary["by_kind"].items()}})


@config.configurable
class ExportHook(Hook):
  """Exports a serving bundle after each checkpoint, GCs old exports, and
  optionally maintains a one-version-lagged directory (reference
  CheckpointExportListener + LaggedCheckpointListener,
  /root/reference/hooks/checkpoint_hooks.py:51-201; TD3 target networks
  read the lagged dir). With `async_export=True` the export runs on a
  background thread and `after_checkpoint` NEVER blocks on an in-flight
  export: the newest snapshot goes into a latest-wins pending slot the
  worker drains, so a slow filesystem delays exports but not training
  (the reference's AsyncCheckpointSaverHook listener behavior)."""

  def __init__(self,
               export_generator=None,
               export_dir_name: str = "export",
               num_versions: int = 3,
               lagged_export_dir_name: Optional[str] = None,
               async_export: bool = False):
    import threading

    self._export_generator = export_generator
    self._export_dir_name = export_dir_name
    self._num_versions = num_versions
    self._lagged_dir_name = lagged_export_dir_name
    self._async = async_export
    self._worker = None
    self._lock = threading.Lock()
    self._pending = None
    self._worker_running = False

  def begin(self, ctx: TrainContext) -> None:
    if self._export_generator is not None:
      self._export_generator.set_specification_from_model(ctx.model)

  def after_checkpoint(self, ctx: TrainContext, step: int) -> None:
    if self._export_generator is None:
      return
    if self._async:
      import threading

      state = jax.device_get(ctx.get_state())
      with self._lock:
        # Latest wins: if an export is in flight, replace any queued
        # snapshot instead of blocking the train loop behind a join().
        self._pending = (ctx, step, state)
        if not self._worker_running:
          self._worker_running = True
          # Backstop exemption: the drain worker self-terminates as soon
          # as the latest-wins pending slot empties (there is no stop
          # event for a finalizer to set) and close()/end() join it on
          # every loop exit path.
          self._worker = threading.Thread(
              target=self._drain,
              daemon=True)  # graftlint: disable=thread-stage-missing-backstop
          try:
            self._worker.start()
          except Exception:
            self._worker_running = False  # recoverable at next checkpoint
            raise
      return None
    return self._do_export(ctx, step, ctx.get_state())

  def _drain(self) -> None:
    import threading

    try:
      while True:
        with self._lock:
          item = self._pending
          self._pending = None
          if item is None:
            # Clearing the running flag and observing an empty slot happen
            # under one lock, so a concurrent after_checkpoint either hands
            # this worker its snapshot or starts a fresh worker — never
            # strands a pending export.
            self._worker_running = False
            return
        ctx, step, state = item
        try:
          self._do_export(ctx, step, state)
        except Exception:  # noqa: BLE001 - keep draining newer snapshots
          from absl import logging

          logging.exception("ExportHook: async export at step %d failed",
                            step)
    finally:
      # A BaseException (SystemExit/KeyboardInterrupt in _do_export)
      # escapes the loop above with the running flag still set; clear it
      # so later checkpoints can start a fresh worker instead of
      # enqueueing snapshots nothing will ever drain. Guarded so a
      # clean-exited worker cannot stomp a successor's flag.
      with self._lock:
        if (self._worker is threading.current_thread()
            and self._worker_running):
          self._worker_running = False

  def _do_export(self, ctx: TrainContext, step: int, state) -> Optional[str]:
    base = os.path.join(ctx.model_dir, self._export_dir_name)
    previous = _numeric_subdirs(base)
    path = self._export_generator.export(state, base, global_step=step)
    if self._lagged_dir_name and previous:
      lagged_base = os.path.join(ctx.model_dir, self._lagged_dir_name)
      lagged_target = os.path.join(lagged_base, os.path.basename(previous[-1]))
      if not os.path.isdir(lagged_target):
        os.makedirs(lagged_base, exist_ok=True)
        shutil.copytree(previous[-1], lagged_target)
        for old in _numeric_subdirs(lagged_base)[:-self._num_versions]:
          shutil.rmtree(old, ignore_errors=True)
    for old in _numeric_subdirs(base)[:-self._num_versions]:
      shutil.rmtree(old, ignore_errors=True)
    return path

  def close(self, timeout: Optional[float] = None) -> None:
    """Joins the in-flight async-export worker (it self-terminates once
    the latest-wins pending slot is empty, so the join is bounded by
    one export). The graftlint `thread-stage-missing-close` contract
    for every thread-spawning stage class; `end()` is the train-loop
    call site."""
    if self._worker is not None and self._worker.is_alive():
      self._worker.join(timeout=timeout)

  def end(self, ctx: TrainContext) -> None:
    self.close()


def _numeric_subdirs(base: str) -> List[str]:
  if not os.path.isdir(base):
    return []
  dirs = [os.path.join(base, d) for d in os.listdir(base)
          if d.isdigit() and os.path.isdir(os.path.join(base, d))]
  return sorted(dirs, key=lambda p: int(os.path.basename(p)))


@config.configurable
class DefaultHookBuilder(HookBuilder):
  """Config saver + variable logger (the reference's default hook set)."""

  def create_hooks(self, model, model_dir):
    return [ConfigSaverHook(), VariableLoggerHook()]


@config.configurable
class AsyncExportHookBuilder(HookBuilder):
  """Checkpoint-triggered export with GC (reference
  async_export_hook_builder.py:87-134)."""

  def __init__(self, export_generator=None, num_versions: int = 3,
               lagged: bool = False, async_export: bool = True):
    self._export_generator = export_generator
    self._num_versions = num_versions
    self._lagged = lagged
    self._async_export = async_export

  def create_hooks(self, model, model_dir):
    return [ExportHook(
        export_generator=self._export_generator,
        num_versions=self._num_versions,
        lagged_export_dir_name="lagged_export" if self._lagged else None,
        async_export=self._async_export)]


@config.configurable
class BestExportHook(Hook):
  """Exports only when an eval metric improves (reference BestExporter,
  /root/reference/utils/train_eval.py:295-386 best/latest compare fns).

  Keeps a `best_export/` dir with the single best bundle plus a
  `best_metric.json` record of the winning value.
  """

  def __init__(self,
               export_generator=None,
               metric_key: str = "loss",
               higher_is_better: bool = False,
               export_dir_name: str = "best_export"):
    self._export_generator = export_generator
    self._metric_key = metric_key
    self._higher = higher_is_better
    self._export_dir_name = export_dir_name
    self._best: Optional[float] = None

  def begin(self, ctx: TrainContext) -> None:
    if self._export_generator is not None:
      self._export_generator.set_specification_from_model(ctx.model)
    # Resume comparison state across restarts.
    record = os.path.join(ctx.model_dir, self._export_dir_name,
                          "best_metric.json")
    if os.path.isfile(record):
      import json

      self._best = json.load(open(record)).get("value")

  def after_eval(self, ctx: TrainContext, step: int, metrics) -> None:
    if self._export_generator is None or self._metric_key not in metrics:
      return
    import json

    value = float(np.asarray(metrics[self._metric_key]))
    if not np.isfinite(value):
      return  # a NaN baseline would lock out every future export
    improved = (self._best is None or not np.isfinite(self._best)
                or (value > self._best if self._higher
                    else value < self._best))
    if not improved:
      return
    self._best = value
    base = os.path.join(ctx.model_dir, self._export_dir_name)
    self._export_generator.export(ctx.get_state(), base, global_step=step)
    for old in _numeric_subdirs(base)[:-1]:
      shutil.rmtree(old, ignore_errors=True)
    with open(os.path.join(base, "best_metric.json"), "w") as f:
      json.dump({"metric": self._metric_key, "value": value,
                 "step": step}, f)
