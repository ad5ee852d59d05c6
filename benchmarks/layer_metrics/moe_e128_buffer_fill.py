"""Experts: the fullest expert row buffer of the window, in %, of the layers
whose router is 128 wide (this cell's four expert layers): the largest
`moe_buffer_fill` over the layers and the window's stepstats records, read
as `moe_buffer_fill.py` reads it. Over 100 would be a drop."""

from benchmarks.layer_metrics import moe_buffer_fill

read = moe_buffer_fill.read
