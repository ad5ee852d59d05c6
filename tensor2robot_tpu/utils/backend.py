"""Backend pinning, completion barriers and device memory accounting.

The one shared implementation of (a) pinning a process to the CPU
backend with an optional virtual multi-device topology (`pin_cpu`,
`assert_cpu_backend`) — used by `__graft_entry__.py`, `scripts/lint.sh`,
`scripts/obs_report.sh` and `device_type='cpu'` configs
(tests/conftest.py keeps an inline pre-import copy of the pin recipe
because it must run before anything else is importable); (b) the
completion barriers (`sync`, `state_barrier`) that close a timed window
with a host fetch; (c) `device_memory_stats`, the client-side memory
accounting behind `obs.stepstats` and `obs.xray`; and (d) the two v5e
peaks `obs.xray`'s roofline reads.

There is no timer here: a step is timed with a barrier, a clock and no
subtraction (`chip_smoke.py`'s step clock, `benchmarks/drivers/trainer.py`).

Reference analogue: /root/reference/utils/train_eval.py:136-151 runs
TPUEstimator tests on CPU; here the same "validate without hardware" need is
met by a virtual host-device topology.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# The project's one real device class (TPU v5e / "v5 lite"): public-spec
# peaks, read by obs/xray.py's roofline. The benchmark keeps its own
# table (benchmarks/harness/peaks.py); tests/test_backend.py holds the
# two equal.
V5E_PEAK_BF16_FLOPS = 197e12
V5E_PEAK_HBM_BW = 819e9


def pin_cpu(n_devices: int = 0) -> None:
  """Pins this process's jax to CPU (optionally with n virtual devices).

  Must run before the backend initializes (first ``jax.devices()`` /
  computation): raises RuntimeError when another backend is already up,
  since the pin could then only pretend.
  """
  os.environ["JAX_PLATFORMS"] = "cpu"
  if n_devices:
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"{_COUNT_FLAG}={n_devices}"
    if _COUNT_FLAG in flags:
      new_flags = re.sub(rf"{_COUNT_FLAG}=\d+", want, flags)
    else:
      new_flags = (flags + " " + want).strip()
    os.environ["XLA_FLAGS"] = new_flags
  import jax
  from jax._src import xla_bridge

  if xla_bridge.backends_are_initialized():
    assert_cpu_backend()
    return
  jax.config.update("jax_platforms", "cpu")


def assert_cpu_backend() -> None:
  """Raises if the live backend is not CPU (i.e. pinning came too late)."""
  import jax

  platform = jax.devices()[0].platform
  if platform != "cpu":
    raise RuntimeError(
        f"backend is '{platform}', not CPU — it was initialized before "
        "pin_cpu() ran; refusing to run CPU work over real hardware")


def sync(x):
  """Forces device completion of ``x`` by fetching it to host (numpy).

  A host fetch of a value that depends on the full computation is a
  barrier on every backend, and it surfaces on-device errors.
  ``jax.block_until_ready``, which moves no bytes, closes a timed window
  just as well on the v5e (``chip_smoke.py``'s barrier phase; PERF.md,
  PR 22).
  """
  import numpy as np

  return np.asarray(x)


def state_barrier(state):
  """Completion barrier for a TrainState: host-fetches the smallest
  param leaf (cheapest transfer; params depend on the full
  forward+backward+update, unlike the loss, which does not depend on the
  final step's optimizer/EMA update)."""
  import jax

  return sync(min(jax.tree_util.tree_leaves(state.params),
                  key=lambda a: a.size))


def device_memory_stats() -> dict:
  """Client-side live-buffer and allocator accounting.

  Reads ONLY client-held metadata: ``jax.live_arrays`` handles and the
  device's allocator counters (``memory_stats``) — no device
  computation is dispatched and nothing is fetched. Keys:
  ``live_arrays`` / ``live_bytes`` always; ``device_bytes_in_use`` /
  ``device_peak_bytes_in_use`` / ``device_bytes_limit`` when the
  backend's allocator reports them (the CPU backend reports none).
  The ONE shared implementation behind ``obs.stepstats``'s per-window
  gauges and ``obs.xray``'s run-record memory block.
  """
  import jax

  arrays = [a for a in jax.live_arrays() if not a.is_deleted()]
  out = {
      "live_arrays": float(len(arrays)),
      "live_bytes": float(sum(getattr(a, "nbytes", 0) for a in arrays)),
  }
  stats = jax.devices()[0].memory_stats()
  for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
    if stats and key in stats:
      out[f"device_{key}"] = float(stats[key])
  return out
