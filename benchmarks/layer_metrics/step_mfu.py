"""Whole step: model FLOPs per step (the configuration's own count, from the
plain reference's shapes) x steps in the traced window / the traced window's
seconds / (chips x the bf16 peak of the device kind), in %. Steps are the
train-step module's events in the trace and the window runs from the first
device op's start to the last one's end, so the host's stalls inside it
count and the profiler's own start and stop do not. Recomputation counts
nothing, and the compiled program is never asked."""

from benchmarks.harness import trace_reduce


def read(run):
  peaks, busy = run.get("peaks"), run.get("busy")
  steps = len(trace_reduce.step_durations(run.get("events")))
  if not peaks or not steps or not busy or not busy["window_s"] > 0:
    return None
  achieved = run["model_flops_per_step"] * steps / busy["window_s"]
  return 100.0 * achieved / peaks["bf16_flops_per_s"]
