"""Experts: device ms a step under the `moe_experts` scope: the grouped
products (`ops/grouped_matmul`) and the activation between them, all
phases."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.scope_ms(run, "moe_experts")
