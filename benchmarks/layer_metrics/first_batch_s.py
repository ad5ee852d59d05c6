"""Compile cache: seconds of the program's `setup/first_batch` span, the
trainer's first `next` of its stream: a part of `first_step_s`."""

from benchmarks.layer_metrics import program_spans


def read(run):
  return program_spans.setup_s(program_spans.program_events(run),
                               "setup/first_batch")
