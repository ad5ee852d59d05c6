"""Compile cache: seconds of step 1's `xray/lower` + `xray/compile` spans
(a cold start) or of its `xray/cache_load` span (a warm one), a run has one
or the other, plus its `xray/op_scopes` span: the op table built from the
executable's text (cold) or read from beside the cache entry (warm)."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.first_step_spans(
      run, "xray/lower", "xray/compile", "xray/cache_load", "xray/op_scopes")
