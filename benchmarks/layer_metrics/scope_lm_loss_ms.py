"""Step program: device ms a step under the `lm_loss` scope
(`models/hybrid_lm`: the head's products and the cross entropy, scored in
chunks), forward and backward."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.scope_ms(run, "lm_loss")
