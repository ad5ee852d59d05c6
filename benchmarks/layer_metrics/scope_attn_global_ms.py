"""Kernels: device ms a step under the global attention layers' scope,
`attn_global` (the Laguna kinds' causal layers): projections, q/k norms, the
YaRN rotary, the causal flash kernels, the gate; all phases."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.scope_ms(run, "attn_global")
