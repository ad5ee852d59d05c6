"""Step program: median device duration of the train-step module in the
traced window, in ms. The train step is the module with the most summed time
on the trace's module line."""

import statistics

from benchmarks.harness import trace_reduce


def read(run):
  durations = trace_reduce.step_durations(run.get("events"))
  return 1e3 * statistics.median(durations) if durations else None
