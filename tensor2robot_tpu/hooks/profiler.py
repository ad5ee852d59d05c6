"""Profiling hook: capture a jax.profiler trace of a training-step window.

The reference has no in-repo tracing (SURVEY.md §5: only TF summaries +
TPU host_call). This is the TPU-native upgrade: a windowed
`jax.profiler` trace (XPlane, viewable in TensorBoard / Perfetto) taken
after compilation has settled.

A trace that was asked for and cannot be taken is an error: a failing
`start_trace`/`stop_trace` raises out of the run instead of training on
without the trace the user configured. The trace directory is surfaced
in the end-of-run report: logged at `end()`, recorded as
`gauge/profiler/trace_captured`, and picked up by
`python -m tensor2robot_tpu.bin.graftscope`, which lists profiler dirs
found under the model_dir.

The operator's view of the device (PR 37): when the trace stops, the
hook lays the train step's op table (`obs.xray.op_scopes`: phase, scope
and module path of every instruction, built where the step compiled)
over the trace it just wrote and leaves `device_scopes.json` beside it:
the first device's time by phase (forward, recompute, backward,
optimizer, ema, other), by declared `jax.named_scope` and by Flax
module, with the copies in each (`obs.xray.device_time_by_scope`; the
benchmark's scope metrics call the same function). `graftscope
<model_dir>` prints it. Reading the trace back takes a few seconds for
a large step and happens once, after the trace has stopped; a trace
with no device plane (a CPU run), a run without the table or whose
steps all ran inside the on-device loop (`train_loop_k<k>`, another
executable), or any failure leaves no file and the run untouched.
"""

from __future__ import annotations

import os
from typing import Optional

from tensor2robot_tpu.hooks import core as hooks_lib
from tensor2robot_tpu.obs import metrics as obs_metrics
from tensor2robot_tpu.obs import xray as xray_lib
from tensor2robot_tpu.utils import config

__all__ = ["ProfilerHook", "ProfilerHookBuilder", "DEVICE_SCOPES_FILE"]

DEVICE_SCOPES_FILE = "device_scopes.json"


def write_device_scopes(trace_dir: str) -> Optional[str]:
  """Reduces the trace under `trace_dir` by the train step's op table
  and writes `device_scopes.json` there; returns the file's path, or
  None where there is nothing to reduce."""
  import json

  lines = xray_lib.read_device_lines(trace_dir)
  table = xray_lib.op_scopes("train_step")
  if not lines or not table:
    return None
  reduced = xray_lib.device_time_by_scope(
      lines["ops"], table, lines["modules"])
  if not reduced["steps"]:
    return None
  reduced.update(executable=table.get("executable"),
                 module=table.get("module"))
  path = os.path.join(trace_dir, DEVICE_SCOPES_FILE)
  with open(path, "w") as f:
    json.dump(reduced, f, indent=1, sort_keys=True)
  return path


@config.configurable
class ProfilerHook(hooks_lib.Hook):
  """Traces steps (start_step, start_step + num_steps]: the trace starts
  once the device has finished step `start_step` and stops once it has
  finished the last one, a barrier at each end."""

  def __init__(self, start_step: int = 10, num_steps: int = 5,
               subdir: str = "profile"):
    self._start_step = start_step
    self._end_step = start_step + num_steps
    self._subdir = subdir
    self._active = False
    self._trace_dir: Optional[str] = None

  def _stop_trace(self) -> None:
    import jax

    jax.profiler.stop_trace()
    self._active = False
    try:
      write_device_scopes(self._trace_dir)
    except Exception as e:  # noqa: BLE001 - the trace itself is taken
      from absl import logging

      logging.warning("ProfilerHook: no %s beside the trace (%s: %s)",
                      DEVICE_SCOPES_FILE, type(e).__name__, e)

  @staticmethod
  def _barrier(ctx) -> None:
    """Waits for the device to finish what the loop has dispatched. The
    host runs tens of steps ahead of the chip, so without it the trace
    holds whatever the device happened to run while the host dispatched
    the window's steps (one partial step on the v5e: my chip run, PR 37)
    and not the steps the hook names."""
    import jax

    get_state = getattr(ctx, "get_state", None)
    if callable(get_state):
      jax.block_until_ready(get_state())

  def after_step(self, ctx, step, metrics) -> None:
    import jax

    if step == self._start_step and not self._active:
      log_dir = os.path.join(ctx.model_dir, self._subdir)
      os.makedirs(log_dir, exist_ok=True)
      self._barrier(ctx)
      jax.profiler.start_trace(log_dir)
      self._active = True
      self._trace_dir = log_dir
    elif self._active and step >= self._end_step:
      self._barrier(ctx)
      self._stop_trace()

  def end(self, ctx) -> None:
    if self._active:
      self._barrier(ctx)
      self._stop_trace()
    from absl import logging

    obs_metrics.gauge("profiler/trace_captured").set(
        1.0 if self._trace_dir else 0.0)
    if self._trace_dir:
      logging.info(
          "ProfilerHook: profiler trace in %s (open in TensorBoard or "
          "Perfetto; `python -m tensor2robot_tpu.bin.graftscope %s` "
          "lists it)", self._trace_dir, ctx.model_dir)


@config.configurable
class ProfilerHookBuilder(hooks_lib.HookBuilder):
  def __init__(self, start_step: int = 10, num_steps: int = 5):
    self._start_step = start_step
    self._num_steps = num_steps

  def create_hooks(self, model, model_dir):
    return [ProfilerHook(start_step=self._start_step,
                         num_steps=self._num_steps)]
