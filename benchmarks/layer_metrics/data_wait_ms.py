"""Host data plane and prefetcher: median of stepstats' `data_wait_ms` (host
time the loop waited for its next batch, per step) over the window's records.
The traced run binds `step_stats_every_n_steps = 10`; each record costs one
barrier, which that run's idle share then contains."""

import statistics


def read(run):
  values = [r["data_wait_ms"] for _, r in run.get("stepstats", [])
            if "data_wait_ms" in r]
  return statistics.median(values) if values else None
