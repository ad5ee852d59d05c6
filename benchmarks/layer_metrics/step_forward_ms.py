"""Step program: device ms a step in the forward pass: the ops whose `op_name`
lies under the step's `loss` scope and under no `transpose(` and no
`rematted_computation` (`obs/xray.classify_op_name`)."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.phase_ms(run, "forward")
