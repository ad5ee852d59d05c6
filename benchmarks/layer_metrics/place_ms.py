"""Host data plane and prefetcher: median duration of the prefetcher's
`data/place` span while the window ran, in ms (171 MB a batch on the critic).
Against `step_device_ms` it says how far the prefetcher is from binding."""

from benchmarks.layer_metrics import program_spans


def read(run):
  return program_spans.median_ms(program_spans.window_spans(
      program_spans.program_events(run), run.get("steps", 0), "data/place"))
