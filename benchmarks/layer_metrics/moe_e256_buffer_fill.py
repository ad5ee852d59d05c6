"""Experts: the fullest expert row buffer of the window, in %, of the layers
whose router is 256 wide (this cell's four sparse layers): the largest
`moe_buffer_fill` over the layers and the window's stepstats records, read
as `moe_buffer_fill.py` reads it. Over 100 would be a drop, which the
comparison that decides `correct` does not see."""

from benchmarks.layer_metrics import moe_buffer_fill

read = moe_buffer_fill.read
