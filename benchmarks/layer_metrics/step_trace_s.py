"""Compile cache: seconds of step 1's `xray/trace` span: the train step
traced to a jaxpr, paid warm and cold alike."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.first_step_spans(run, "xray/trace")
