"""Backend pinning, completion barriers and step timers.

These helpers are the one shared implementation of (a) pinning a process
to the CPU backend with an optional virtual multi-device topology — used
by `__graft_entry__.py`, the CPU bench modes and `device_type='cpu'`
configs (tests/conftest.py keeps an inline pre-import copy of the pin
recipe because it must run before anything else is importable) — and
(b) the completion barriers and step timers the measurement scripts
share.

Reference analogue: /root/reference/utils/train_eval.py:136-151 runs
TPUEstimator tests on CPU; here the same "validate without hardware" need is
met by a virtual host-device topology.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

# The project's one real device class (TPU v5e / "v5 lite"): public-spec
# peaks shared by bench.py and the tuning/AOT-analysis scripts so MFU
# and roofline numbers cannot silently disagree.
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


def require_tpu():
  """Returns jax's first device, raising unless it is a TPU: the
  measurement scripts call this first, so a process that found no chip
  fails instead of timing a CPU under a device metric's name."""
  import jax

  device = jax.devices()[0]
  if device.platform != "tpu":
    raise RuntimeError(
        f"this measurement needs a TPU; jax runs on '{device.platform}' "
        f"({device.device_kind})")
  return device


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


def time_op(fn, *args, iters: int = 30):
  """Per-iter wall time of a (jitted) op with the host-fetch barrier
  cost cancelled — the ONE shared micro-op timer for the flash
  validate/tune scripts, so the measurement methodology cannot drift
  between scripts whose numbers are compared against each other.

  The closing barrier is a host fetch (see ``sync``), which costs real
  time. Time (1 iter + fetch) and (iters + fetch) and difference them
  so the fetch and any fixed dispatch overhead cancel. The 1-iter leg
  is the median of 3 — it is ~pure fetch cost for sub-ms kernels and
  one noisy fetch makes the difference negative. A clamped-to-zero
  result means noise swamped the kernel: report it as below the
  measurement floor, don't divide by it.
  """
  import time as _time

  if iters < 2:
    raise ValueError("iters must be >= 2 (the fetch-cancel difference "
                     "needs two run lengths)")
  out = fn(*args)  # warmup / compile
  sync(out)

  def run(n):
    t0 = _time.perf_counter()
    o = None
    for _ in range(n):
      o = fn(*args)
    sync(o)
    return _time.perf_counter() - t0

  t1 = sorted(run(1) for _ in range(3))[1]
  tn = run(iters)
  return max(tn - t1, 0.0) / (iters - 1)


def time_train_steps(step, state, features, labels, iters,
                     warmup: int = 3):
  """Times ``step(state, features, labels)`` with the shared barrier
  discipline (warmup → barrier → timed loop → barrier); returns
  ``(seconds_per_step, final_state)``. The one shared implementation for
  bench/tuning/baseline scripts, so a future change to the barrier
  recipe lands everywhere at once."""
  h1, h2, state = time_train_steps_halves(step, state, features, labels,
                                          iters, warmup=warmup)
  # Mean over ALL timed steps, both halves barrier-subtracted (pure
  # step time; see time_train_steps_halves).
  n1 = iters - iters // 2
  return (h1 * n1 + h2 * (iters - n1)) / iters, state


def time_train_steps_halves(step, state, features, labels, iters,
                            warmup: int = 3, out_flags: dict | None = None):
  """``time_train_steps`` with the timed loop split into two
  barrier-separated halves; returns ``(sec_per_step_first_half,
  sec_per_step_second_half, final_state)``. When a half's window is
  barrier-dominated (see ``_pure`` below) and ``out_flags`` is given,
  ``out_flags["barrier_dominated"] = True`` is set so callers (bench
  probe records, autotune's ranking) know the number is a clamped
  estimate rather than a measurement, and ``obs.sentinel``'s step-time
  spike detector ignores such records.

  Why: one-time effects INSIDE the timed window (first-touch
  allocation, defrag, warming caches) inflate a plain mean. The second
  half is the steady-state number (what a days-long training run sees);
  a large half-to-half gap is itself the diagnostic. The barrier fetch
  cost is estimated (by a back-to-back second fetch on the
  already-drained device) and subtracted from BOTH halves, so each is
  pure step time — a barrier amortized over a short half (e.g. 2 steps
  in a 5-iter profile window) would otherwise dominate it."""
  import time

  for _ in range(warmup):
    state, _ = step(state, features, labels)
  state_barrier(state)
  n1 = iters - iters // 2
  n2 = iters - n1
  start = time.perf_counter()
  for _ in range(n1):
    state, _ = step(state, features, labels)
  state_barrier(state)
  mid = time.perf_counter()
  # The clock can only stop AFTER a barrier (dispatch is async), so a
  # closing barrier's host-fetch cost is inside each half's window.
  # Estimate it with a back-to-back second barrier (the device is
  # already drained, so this times the pure fetch) and subtract it from
  # BOTH halves — pure step time. If noise makes the estimate larger
  # than a (tiny) window, fall back to the un-subtracted value rather
  # than report a zero step time (downstream divides by it).
  state_barrier(state)
  barrier_cost = time.perf_counter() - mid

  def _pure(window, n):
    # Clamp the barrier-dominated fallback: when the estimated barrier
    # cost swallows (nearly) all of the window, a naive residual would
    # be near-zero (or negative) and report an absurdly small step time
    # — autotune keeps the MAX examples/sec, so one such probe would
    # become the headline. Returning the FULL window over-corrects the
    # other way: it re-includes the whole
    # barrier and reads ~barrier/n high. Clamp to max(residual,
    # 0.2*window) — a bounded estimate that can still sit on EITHER
    # side of the truth when the barrier estimate itself is noisy,
    # which is exactly why the record is flagged ``barrier_dominated``:
    # consumers (bench autotune's ranking, sentinel's spike detector)
    # must treat it as untrusted, not merely conservative (ADVICE.md
    # round 5).
    residual = window - barrier_cost
    if residual < 0.2 * window:
      if out_flags is not None:
        out_flags["barrier_dominated"] = True
      return max(residual, 0.2 * window) / n
    return residual / n

  sec_h1 = _pure(mid - start, n1)
  if n2 == 0:
    return sec_h1, sec_h1, state
  mid2 = time.perf_counter()
  for _ in range(n2):
    state, _ = step(state, features, labels)
  state_barrier(state)
  end = time.perf_counter()
  return sec_h1, _pure(end - mid2, n2), state
