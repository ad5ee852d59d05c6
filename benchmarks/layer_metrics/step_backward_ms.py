"""Step program: device ms a step in the backward pass: the ops under a
`transpose(...)` of autodiff and under no `rematted_computation`. XLA fuses
Adam's update into the fusions that make the weights' gradients and names them
after the gradient, so this holds the optimizer's fused share too (the
`[bench scopes]` table's `mixed backward+optimizer` line says how much)."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.phase_ms(run, "backward")
