"""Step program: share (%) of the train step's device time that the program's
names do not cover: ops of phase `other` under no declared scope, and ops
whose name the op table lacks. Phase `other` under a scope (`metrics`) is
covered."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  out = device_scopes.reduced(run)
  if out is None:
    return None
  return 100.0 * (out["unscoped_s"] + out["unknown_s"]) / out["total_s"]
