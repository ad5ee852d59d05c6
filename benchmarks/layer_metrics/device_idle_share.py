"""Device: 1 - union of device-op intervals over the traced window, in %."""


def read(run):
  busy = run.get("busy")
  if not busy or not busy["window_s"] > 0:
    return None
  return 100.0 * (1.0 - busy["busy_s"] / busy["window_s"])
