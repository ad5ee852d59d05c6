"""Compile cache: seconds of step 1's `train/dispatch` span: the train step
traced, then loaded from the cache or compiled (its child `xray/analyze` says
which, in `cache_hit`), then dispatched."""

from benchmarks.layer_metrics import program_spans


def read(run):
  first = [e for e in program_spans.named(
      program_spans.program_events(run), "train/dispatch")
           if e.get("step") == 1]
  return first[0]["dur"] / 1e6 if len(first) == 1 else None
