"""Kernels: device ms a step under the sliding-window attention layers' scope,
`attn_window`: projections, q/k norms, rotary, the windowed flash kernels,
the gate; all phases."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.scope_ms(run, "attn_window")
