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
"""

from __future__ import annotations

import os
from typing import Optional

from tensor2robot_tpu.hooks import core as hooks_lib
from tensor2robot_tpu.obs import metrics as obs_metrics
from tensor2robot_tpu.utils import config

__all__ = ["ProfilerHook", "ProfilerHookBuilder"]


@config.configurable
class ProfilerHook(hooks_lib.Hook):
  """Traces steps [start_step, start_step + num_steps)."""

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

  def after_step(self, ctx, step, metrics) -> None:
    import jax

    if step == self._start_step and not self._active:
      log_dir = os.path.join(ctx.model_dir, self._subdir)
      os.makedirs(log_dir, exist_ok=True)
      jax.profiler.start_trace(log_dir)
      self._active = True
      self._trace_dir = log_dir
    elif self._active and step >= self._end_step:
      self._stop_trace()

  def end(self, ctx) -> None:
    if self._active:
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
