"""Trainer loop: traced window minus the union of device-op intervals, per
step of the traced window (the train-step module's events in it), in ms: what
the host's loop leaves the chip waiting for, each step."""

from benchmarks.harness import trace_reduce


def read(run):
  busy = run.get("busy")
  steps = len(trace_reduce.step_durations(run.get("events")))
  if not busy or not steps:
    return None
  return 1e3 * (busy["window_s"] - busy["busy_s"]) / steps
