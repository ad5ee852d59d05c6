"""Step program: device ms a step in the forward pass run again inside the
backward pass (`jax.checkpoint`: the ops under a `rematted_computation`).
Cells whose step rematerialises nothing read nothing."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.phase_ms(run, "recompute")
