"""State space: device ms a step under the `ssm_scan` scope
(`layers/decoder.Mamba2Mixer`: the chunked Mamba-2 scan), forward, recomputed
forward and backward. The same thing `ssd_scan_ms` finds by shapes, here by
the program's name."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.scope_ms(run, "ssm_scan")
