"""Experts: the fullest expert row buffer of the window, in %: the largest
`moe_buffer_fill` (held (token, expert) pairs over the buffer's rows) over
the layers and the window's stepstats records. Over 100 would be a drop."""

from benchmarks.layer_metrics import hybrid_ops


def read(run):
  records = hybrid_ops.counter_records(run, "moe_buffer_fill")
  return 100.0 * max(max(r) for r in records) if records else None
