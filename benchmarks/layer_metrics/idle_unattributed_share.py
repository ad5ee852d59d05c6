"""Device: of the traced window's device idle time in gaps of 1 ms and more,
the share (%) that no program span below `train/iteration` covers: how much of
the chip's waiting the tracing still cannot name. 0 where the window has no
such gap. Shorter gaps are launch latency between queued programs, which no
host span can cover.

The two clocks are laid over each other from data: each `bench/after_step`
event of the trace lies inside the program's `train/hook` span of `BenchHook`
for the same step. A trace without host events (the critic's cell: PERF.md
says why) gives nothing to pair, and the metric is left out there: the
profiler's times count from the session's start, which only the xplane's
`Task Environment` plane holds, so the tracer's anchor alone cannot place
them."""

from benchmarks.layer_metrics import program_spans


def read(run):
  events = program_spans.program_events(run)
  trace_events = run.get("events")
  if not events or not trace_events:
    return None
  gaps = program_spans.device_gaps(trace_events)
  share = program_spans.unattributed_share(
      events, gaps, program_spans.offset_from_pairs(events, trace_events))
  if share is not None:
    program_spans.say(f"{len(gaps)} device gaps of 1 ms and more, "
                      f"{sum(b - a for a, b in gaps) / 1e6:.3f} ms idle in "
                      f"them, {share:.2f} % of it under no program span")
  return share
