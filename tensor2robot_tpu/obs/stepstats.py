"""Per-train-step telemetry: data-wait vs device time, compile events.

The reference's TPUEstimator hid the step economics inside
`iterations_per_loop` host calls (/root/reference/models/
abstract_model.py:662-834); our explicit loop can measure them — but ONLY
behind a barrier, since jax returns before the device finishes. The
barrier here is a host fetch through `utils.backend.state_barrier` (the
smallest param leaf depends on the full fwd+bwd+update), whose fetched
leaf the non-finite check reuses; `jax.block_until_ready` closes a
window just as well on the v5e (0.9997 of the host-fetch window, my chip
run, PR 22).

Accounting per measured window (`every_n_steps` dispatches, default 1):

* `data_wait_ms`  — host time staging batches (`data_wait()` windows).
  Under the overlapped host loader (`data/overlap.py` stages feeding a
  `DevicePrefetcher`), the loop's `data_wait()` wraps only the DEQUEUE
  of an already-placed batch, so parse/preprocess/place work running in
  worker threads concurrently with device compute inflates NEITHER
  `data_wait_ms` NOR `device_wait_ms` (pinned by the synthetic
  overlapped-producer test in tests/test_overlap.py): a near-zero
  `data_wait_ms` with healthy throughput means the pipeline keeps up,
  and a growing one means the consumer outran it — read the
  `data/overlap_*` stage timings to see which stage binds;
* `device_wait_ms` — un-overlapped device wait: dispatch-call time plus
  the closing barrier fetch (a wait of the host's, not a device time:
  the device's own step time comes from a profiler trace). Host staging
  that overlaps device compute is deliberately NOT charged to the device
  — the split answers "what is the loop's wall clock spent waiting on";
* `host_ms`       — the remainder (hooks, metric fetch, logging);
* `step_ms`       — full window wall time / steps;
* `examples_per_sec`, `compile` (a dispatch of the window compiled or
  loaded an executable: counted, not inferred from its duration),
  `live_arrays` / `live_bytes` gauges.

The barrier costs a real host fetch per measured window and serializes
the dispatch/prefetch overlap: use `every_n_steps=1` only for CPU/debug
runs and a coarser cadence for accelerator training so it amortizes
(the windowed averages stay exact) — `train_eval_model`'s default picks
per-step vs log-cadence by backend. Importing this module never
touches jax (backend access is lazy, from inside a live loop); the
train-loop integration lives in `train_eval.py` +
`hooks.core.StepStatsHook`.

Spans (`obs.trace`, children of the loop's `train/iteration`):
`train/data_wait`, `train/dispatch` (`train/compile_dispatch` marks the
dispatches that compiled or loaded an executable; its `xray/analyze`
child says which), `train/barrier`, and `train/record` with its children
`train/record/gauges` and one `train/record/observer` per observer:
what the host does between a barrier's end and the next dispatch is
what the device waits for.
"""

from __future__ import annotations

import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import trace as trace_lib

__all__ = ["StepStatsRecorder"]

# A window whose post-barrier residual is below this fraction of the
# window is "barrier dominated": its step_ms is an upper bound, not a
# measurement — flagged in the record so obs.sentinel's spike detector
# skips it.
BARRIER_DOMINATED_RESIDUAL = 0.2

# Backend compiles of this process, counted by a `jax.monitoring`
# listener that the first recorder to `start()` installs (jax has no way
# to take one listener out again, so there is one for the process). A
# dispatch that blocks on a full device queue is slow and compiles
# nothing: the marker comes from this count, never from a duration.
_BACKEND_COMPILES = [0]
_LISTENING = [False]


def _count_backend_compiles() -> None:
  """Installs the listener once. Where jax is absent or refuses, no
  compile is ever counted and only cache hits mark a dispatch."""
  if _LISTENING[0]:
    return
  _LISTENING[0] = True
  try:
    import jax.monitoring

    def listener(event: str, _seconds: float, **_) -> None:
      if event.endswith("backend_compile_duration"):
        _BACKEND_COMPILES[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
  except Exception:  # noqa: BLE001 - telemetry never breaks the loop
    pass


class _WaitTimer:
  """Accumulates one staging window into the recorder (+ trace span)."""

  __slots__ = ("_rec", "_start_ns", "_span")

  def __init__(self, rec: "StepStatsRecorder"):
    self._rec = rec
    self._start_ns = 0
    self._span = None

  def __enter__(self) -> "_WaitTimer":
    self._span = self._rec._tracer.open("train/data_wait", cat="train")
    self._start_ns = time.perf_counter_ns()
    return self

  def __exit__(self, exc_type, exc, tb) -> None:
    self._rec._data_wait_ns += time.perf_counter_ns() - self._start_ns
    self._span.close()


class _NullTimer:
  __slots__ = ()

  def __enter__(self):
    return self

  def __exit__(self, exc_type, exc, tb):
    return None


_NULL_TIMER = _NullTimer()


def _default_barrier(state):
  from tensor2robot_tpu.utils import backend

  # Return the fetched leaf: it is ALREADY on the host (the barrier is
  # a host fetch by definition), so the non-finite divergence check
  # piggybacks on it for free — no extra fetch.
  return backend.state_barrier(state)


class StepStatsRecorder:
  """Train-loop step accountant; all clock reads live in this module.

  Protocol (see `train_eval.py`):

    rec.start()                       # after data/state bring-up
    with rec.data_wait(): batch = next(...)
    rec.before_dispatch(); state, m = step(...); rec.after_dispatch()
    with rec.data_wait(): next_batch = next(...)   # overlapped staging
    rec.end_step(step, state, num_steps=k)         # barrier at cadence
    for step, record in rec.drain(): writer.write_scalars(step, record)

  A disabled recorder (`every_n_steps=0`) keeps the call sites
  unconditional and no-ops at one attribute check per call.
  """

  def __init__(self,
               batch_size: int,
               every_n_steps: int = 1,
               barrier: Optional[Callable[[Any], None]] = None,
               registry: Optional[metrics_lib.Registry] = None,
               tracer: Optional[trace_lib.Tracer] = None,
               device_gauges: bool = True,
               counter_prefixes: Tuple[str, ...] = ()):
    self._enabled = every_n_steps > 0
    # Step metrics a model names as counters (`step_counter_prefixes`)
    # ride the record: read after the barrier, so they cost no wait.
    self._counter_prefixes = tuple(counter_prefixes)
    self._batch_size = int(batch_size)
    self._every_n = max(int(every_n_steps), 1)
    self._barrier = barrier or _default_barrier
    self._registry = registry or metrics_lib.get_registry()
    self._tracer = tracer or trace_lib.get_tracer()
    self._device_gauges = device_gauges
    self._records: List[Tuple[int, Dict[str, float]]] = []
    self._window_start_ns = 0
    self._data_wait_ns = 0
    self._dispatch_ns = 0
    self._barrier_ns = 0
    self._steps_in_window = 0
    self._dispatches_in_window = 0
    self._last_record_step: Optional[int] = None
    self._t_dispatch_ns = 0
    self._compiles_before = (0, 0.0)  # (backend compiles, cache hits)
    self._dispatch_span = trace_lib.Span(None, "", "", None)
    self._compile_in_window = 0
    self._observers: List[Callable[[int, Dict[str, float]], Any]] = []
    self._last_barrier_nonfinite: Optional[float] = None

  @property
  def enabled(self) -> bool:
    return self._enabled

  def add_observer(self,
                   observer: Callable[[int, Dict[str, float]], Any]
                   ) -> None:
    """Registers `observer(step, record)`, called synchronously for
    every emitted window record (drain() is untouched — observers are
    the online path, e.g. `obs.sentinel` / the flight recorder). An
    observer that raises is warned about and dropped — telemetry must
    never take down a train loop."""
    self._observers.append(observer)

  def start(self) -> None:
    """Marks the start of the first measurement window."""
    if self._enabled:
      _count_backend_compiles()
      self._window_start_ns = time.perf_counter_ns()

  def data_wait(self):
    """Context manager charging its window to `data_wait_ms`."""
    return _WaitTimer(self) if self._enabled else _NULL_TIMER

  def before_dispatch(self) -> None:
    if self._enabled:
      self._dispatch_span = self._tracer.open("train/dispatch", cat="train")
      self._compiles_before = self._compile_counts()
      self._t_dispatch_ns = time.perf_counter_ns()

  def _compile_counts(self) -> Tuple[int, float]:
    """(backend compiles of the process, executables `obs.excache`
    loaded): what a dispatch can do instead of only launching."""
    return (_BACKEND_COMPILES[0],
            self._registry.counter("cache/hits").value)

  def after_dispatch(self) -> None:
    """Call immediately after the (async) step dispatch returns."""
    if not self._enabled:
      return
    dur_ns = time.perf_counter_ns() - self._t_dispatch_ns
    self._dispatch_span.close()
    self._dispatch_ns += dur_ns
    self._dispatches_in_window += 1
    if self._compile_counts() != self._compiles_before:
      # The dispatch compiled (a first call, a re-trace at new shapes)
      # or took its executable from the cache (xray's `cache_hit`).
      self._compile_in_window += 1
      self._registry.counter("stepstats/compile_events").inc()
      self._tracer.add_complete("train/compile_dispatch",
                                self._t_dispatch_ns, dur_ns, cat="train")

  def end_step(self, step: int, state: Any, num_steps: int = 1,
               metrics: Optional[Dict[str, Any]] = None) -> None:
    """Closes the step; at the cadence, barriers and emits a record.
    `metrics` are the dispatch's step metrics (stacked for a K-step loop):
    those under the recorder's counter prefixes join the record, as the
    last step gave them."""
    if not self._enabled:
      return
    self._steps_in_window += num_steps
    if self._steps_in_window < self._every_n:
      return
    with self._tracer.span("train/barrier", cat="train"):
      barrier_start_ns = time.perf_counter_ns()
      fetched = self._barrier(state)
      now_ns = time.perf_counter_ns()
    self._barrier_ns += now_ns - barrier_start_ns
    with self._tracer.span("train/record", cat="train"):
      self._observe_barrier(fetched)
      self._emit(step, now_ns, self._read_counters(metrics))

  def _read_counters(self, metrics: Optional[Dict[str, Any]]
                     ) -> Dict[str, float]:
    if not metrics or not self._counter_prefixes:
      return {}
    import numpy as np

    return {key: float(np.asarray(value).reshape(-1)[-1])
            for key, value in metrics.items()
            if key.startswith(self._counter_prefixes)}

  def _observe_barrier(self, fetched: Any) -> None:
    """Piggybacks on the barrier's host fetch: non-finite divergence
    check on the fetched param leaf (no extra fetch)."""
    self._last_barrier_nonfinite = None
    if fetched is not None:
      try:
        import numpy as np

        self._last_barrier_nonfinite = float(
            not bool(np.all(np.isfinite(np.asarray(fetched)))))
      except Exception:  # noqa: BLE001 - non-float leaves etc.
        self._last_barrier_nonfinite = None

  def _emit(self, step: int, now_ns: int,
            counters: Dict[str, float]) -> None:
    n = self._steps_in_window
    window_s = max((now_ns - self._window_start_ns) / 1e9, 1e-9)
    data_wait_ms = self._data_wait_ns / 1e6 / n
    device_wait_ms = (self._dispatch_ns + self._barrier_ns) / 1e6 / n
    step_ms = window_s * 1e3 / n
    record: Dict[str, float] = {
        "step_ms": step_ms,
        "device_wait_ms": device_wait_ms,
        "data_wait_ms": data_wait_ms,
        "host_ms": max(step_ms - device_wait_ms - data_wait_ms, 0.0),
        "dispatch_ms": self._dispatch_ns / 1e6 / n,
        "examples_per_sec": n * self._batch_size / window_s,
        "compile": float(self._compile_in_window > 0),
        "steps_in_window": float(n),
        # BARRIER_DOMINATED_RESIDUAL: a window the barrier fetch
        # swallowed is an upper bound — the sentinel spike detector
        # must skip it.
        "barrier_dominated": float(
            window_s * 1e9 - self._barrier_ns
            < BARRIER_DOMINATED_RESIDUAL * window_s * 1e9),
    }
    if self._last_barrier_nonfinite is not None:
      record["nonfinite_params"] = self._last_barrier_nonfinite
    record.update(counters)
    with self._tracer.span("train/record/gauges", cat="train"):
      record.update(self._read_device_gauges())
    self._records.append((int(step), record))
    for observer in list(self._observers):
      try:
        with self._tracer.span(
            "train/record/observer", cat="train",
            observer=getattr(observer, "__qualname__", None)
            or type(observer).__name__):
          observer(int(step), record)
      except Exception as e:  # noqa: BLE001 - drop a broken observer
        self._observers.remove(observer)
        print(f"stepstats: observer {observer!r} failed and was "
              f"detached ({type(e).__name__}: {e})", file=sys.stderr)
    reg = self._registry
    reg.histogram("stepstats/step_ms").record(step_ms)
    reg.histogram("stepstats/device_wait_ms").record(device_wait_ms)
    reg.histogram("stepstats/data_wait_ms").record(data_wait_ms)
    reg.histogram("stepstats/examples_per_sec").record(
        record["examples_per_sec"])
    first_step = int(step) - n + 1
    self._tracer.add_complete(
        "train/step_window", self._window_start_ns,
        now_ns - self._window_start_ns, cat="train",
        args={"first_step": first_step, "last_step": int(step), "steps": n})
    self._window_start_ns = now_ns
    self._data_wait_ns = self._dispatch_ns = self._barrier_ns = 0
    self._steps_in_window = self._dispatches_in_window = 0
    self._compile_in_window = 0
    self._last_record_step = int(step)

  def _read_device_gauges(self) -> Dict[str, float]:
    """Live-array count/bytes (+ allocator bytes when the backend
    reports them). Latches off on first failure — telemetry must never
    take down a train loop."""
    if not self._device_gauges:
      return {}
    try:
      from tensor2robot_tpu.utils import backend

      out = backend.device_memory_stats()
      self._registry.gauge("device/live_arrays").set(out["live_arrays"])
      self._registry.gauge("device/live_bytes").set(out["live_bytes"])
      return out
    except Exception:  # noqa: BLE001 - gauges are best-effort
      self._device_gauges = False
      return {}

  def drain(self) -> List[Tuple[int, Dict[str, float]]]:
    """Pops every completed (step, record) pair, oldest first."""
    records, self._records = self._records, []
    return records
