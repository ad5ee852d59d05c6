"""Trainer loop: what one stepstats record costs the device, in ms. Per record
in the window, from the end of the program's `train/barrier` span (the device
has just run dry: the barrier waited for it) to the start of the next
`train/dispatch`: the record itself, the hooks, the log, less the benchmark's
own hook. The median over the window's records (the lower of the two middle
ones: a traced second holds few); `record_stall_max_ms` is the largest."""

import statistics

from benchmarks.layer_metrics import program_spans


def read(run):
  stalls = program_spans.record_stalls(program_spans.program_events(run),
                                       run.get("steps", 0))
  return statistics.median_low(ms for _, ms, _ in stalls) if stalls else None
