"""Linear attention: device ms a step under the `gdn_scan` scope
(`layers/decoder.GatedDeltaNet`: the chunked gated delta rule with its
inverse), forward, recomputed forward and backward. The same thing
`gdn_scan_ms` finds by shapes, here by the program's name."""

from benchmarks.layer_metrics import device_scopes


def read(run):
  return device_scopes.scope_ms(run, "gdn_scan")
