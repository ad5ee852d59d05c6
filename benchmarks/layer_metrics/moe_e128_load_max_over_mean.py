"""Experts: how uneven the 128-wide router's load over the 8 experts held
is: per stepstats record of the window the worst layer's
`moe_load_max_over_mean`, and of those the median, read as
`moe_load_max_over_mean.py` reads it."""

from benchmarks.layer_metrics import moe_load_max_over_mean

read = moe_load_max_over_mean.read
