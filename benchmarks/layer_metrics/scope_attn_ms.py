"""Kernels: device ms a step under the attention layers' scopes, `attn_gated`
(two-slot kinds) or `attn_plain` (one-slot kinds): projections, rotary, the
flash kernels, the gate; all phases."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.scope_ms(run, "attn_gated", "attn_plain")
