"""Compile cache: seconds of the program's `setup/create_state` and
`setup/restore` spans together: the state's init program traced, loaded or
compiled, and run, and a checkpoint read where there is one."""

from benchmarks.layer_metrics import program_spans


def read(run):
  return program_spans.setup_s(program_spans.program_events(run),
                               "setup/create_state", "setup/restore")
