"""Experts: device ms a step under the `moe_route` scope
(`layers/moe.ShardedExpertsMoE`: router, top-k, sort, gather into the row
buffer, scatter-add back), all phases; the grouped products are not in it."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.scope_ms(run, "moe_route")
