"""Trainer loop: median duration of the program's `train/dispatch` span over
the window's iterations, in ms: the host's floor under a faster step
program."""

from benchmarks.layer_metrics import program_spans


def read(run):
  return program_spans.median_ms(program_spans.window_spans(
      program_spans.program_events(run), run.get("steps", 0),
      "train/dispatch"))
