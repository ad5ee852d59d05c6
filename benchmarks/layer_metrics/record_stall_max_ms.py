"""Trainer loop: the longest record stall of the window, in ms (see
`record_stall_ms`). Beside the median it says "every record" or "the first
one". The stall's self times by span go to standard error, longest first."""

from benchmarks.layer_metrics import program_spans


def read(run):
  events = program_spans.program_events(run)
  stalls = program_spans.record_stalls(events, run.get("steps", 0))
  if not stalls:
    return None
  step, ms, between = max(stalls, key=lambda s: s[1])
  leaves = sorted(program_spans.leaf_self_times(events, between).items(),
                  key=lambda kv: -kv[1])[:6]
  program_spans.say(
      f"records at steps {[s for s, _, _ in stalls]} stalled",
      [round(m, 3) for _, m, _ in stalls], f"ms; the longest, step {step}:",
      ", ".join(f"{name} {value:.3f}" for name, value in leaves))
  return ms
