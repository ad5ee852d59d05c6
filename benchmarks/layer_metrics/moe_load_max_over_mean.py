"""Experts: how uneven the router's load over the experts held is: per
stepstats record of the window the worst layer's `moe_load_max_over_mean`
(the busiest held expert's pairs over the mean), and of those the median."""

import statistics

from benchmarks.layer_metrics import hybrid_ops


def read(run):
  records = hybrid_ops.counter_records(run, "moe_load_max_over_mean")
  return statistics.median(max(r) for r in records) if records else None
