"""graftscope-xray: compile, cost and memory introspection below dispatch.

The reference has nothing under the dispatch boundary — TPUEstimator
hides compilation and HBM inside the session
(/root/reference/models/abstract_model.py:662-834) and every OOM or
compile stall surfaces as an opaque session error. Here the jit/pjit
entry points can be X-rayed: `analyze_jit` AOT-traces/lowers/compiles a
jitted callable with per-phase timing and reads the compiled
executable's own XLA cost analysis (FLOPs, bytes accessed) and memory
analysis (argument/output/temp bytes), plus jaxpr equation counts and
declared-donation byte accounting from `Traced.args_info`. From those it
derives arithmetic intensity, an analytic v5e roofline, and (given a
measured step time) MFU — the accounting that diagnosed the round-5
b80–b128 valley by hand (PERFORMANCE.md: 451 ms/step measured vs a
~28 ms roofline priced from the very same cost-analysis numbers).

`memory_accounting` prices a TrainState + batch in bytes, globally and
PER SHARD (via each leaf's `sharding.shard_shape`; replicated leaves
cost full bytes per device), and `hbm_watermark_estimate` combines it
with the executable's temp bytes into the per-run HBM watermark that
rounds 2–5 OOMed without (b512/b320/b384 all died blind).

Analysis results land in three places at once: the process-wide metrics
registry (`xray/<name>/…` gauges), a module-level record collector
(drained into `obs.runlog` run records), and the caller's hands.

Backend-free at import like the rest of `obs/` — jax is imported only
inside the analysis functions, which are called from live loops where
the backend is already up (tests/test_observability.py proves the
import under a poisoned JAX_PLATFORMS). Telemetry must never take down
a train loop: `XrayedFunction` falls back to the plain jitted callable
on ANY analysis or compiled-call failure.

graftcache (PR 7): `analyze_jit`/`XrayedFunction` take a `cache=` seam
(`obs.excache`) that persists the AOT executables they produce and
short-circuits lower+compile with a deserialize on later processes —
trainer restarts and serving cold starts warm-start in
milliseconds. All cache failure modes degrade to the fresh compile.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.utils import backend as backend_lib

__all__ = ["analyze_jit", "XrayedFunction", "memory_accounting",
           "hbm_watermark_estimate", "analytic_mfu", "pytree_bytes",
           "pytree_shard_bytes", "records", "clear_records"]

_RECORDS: List[Dict[str, Any]] = []
_LOCK = threading.Lock()


def records() -> List[Dict[str, Any]]:
  """Compile records collected since the last `clear_records()`."""
  with _LOCK:
    return list(_RECORDS)


def clear_records() -> None:
  """Drops collected records (run start, alongside trace/metrics reset)."""
  with _LOCK:
    _RECORDS.clear()


def _collect(record: Dict[str, Any]) -> None:
  with _LOCK:
    _RECORDS.append(record)


# ---------------------------------------------------------------------------
# Byte accounting over pytrees.
# ---------------------------------------------------------------------------


def _leaf_nbytes(leaf) -> int:
  """Logical bytes of one array-like leaf (0 for non-arrays)."""
  nbytes = getattr(leaf, "nbytes", None)
  if nbytes is not None:
    return int(nbytes)
  shape = getattr(leaf, "shape", None)
  dtype = getattr(leaf, "dtype", None)
  if shape is None or dtype is None:
    return 0
  import numpy as np

  size = 1
  for dim in shape:
    size *= int(dim)
  return size * np.dtype(dtype).itemsize


def _leaf_shard_nbytes(leaf) -> int:
  """Per-device bytes of one leaf: the shard slice when the leaf carries
  a sharding, the full array otherwise (replicated arrays DO occupy full
  bytes on every device — that is the honest per-shard cost)."""
  sharding = getattr(leaf, "sharding", None)
  shape = getattr(leaf, "shape", None)
  if sharding is not None and shape is not None:
    try:
      import numpy as np

      shard_shape = sharding.shard_shape(tuple(shape))
      size = 1
      for dim in shard_shape:
        size *= int(dim)
      return size * np.dtype(leaf.dtype).itemsize
    except Exception:  # noqa: BLE001 - fall back to the global bytes
      pass
  return _leaf_nbytes(leaf)


def pytree_bytes(tree) -> int:
  """Total logical bytes over every array leaf of `tree`."""
  import jax

  return sum(_leaf_nbytes(x) for x in jax.tree_util.tree_leaves(tree))


def pytree_shard_bytes(tree) -> int:
  """Per-device bytes over every leaf (see `_leaf_shard_nbytes`)."""
  import jax

  return sum(_leaf_shard_nbytes(x) for x in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# Compile telemetry.
# ---------------------------------------------------------------------------


def _count_eqns(jaxpr) -> int:
  """Total equation count, nested jaxprs (pjit/scan/custom_vjp bodies)
  included — a cheap structural size proxy that moves when a model edit
  re-traces into something materially different."""
  jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
  total = 0
  for eqn in getattr(jaxpr, "eqns", ()):
    total += 1
    for value in eqn.params.values():
      values = value if isinstance(value, (list, tuple)) else (value,)
      for item in values:
        if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
          total += _count_eqns(item)
  return total


def _donation_bytes(traced, args) -> Tuple[float, float]:
  """(donated, undonated) argument bytes from the Traced's args_info
  (the declared donation set — what the caller hands over, whether or
  not XLA finds a reusable buffer for each)."""
  import jax

  infos = jax.tree_util.tree_leaves(
      traced.args_info, is_leaf=lambda n: hasattr(n, "donated"))
  if infos and all(hasattr(i, "donated") for i in infos):
    donated = sum(_leaf_nbytes(i) for i in infos if i.donated)
    total = sum(_leaf_nbytes(i) for i in infos)
    return float(donated), float(total - donated)
  total = sum(pytree_bytes(a) for a in args)
  return 0.0, float(total)


def analytic_mfu(flops: float, step_sec: float,
                 peak_flops: float = backend_lib.V5E_PEAK_BF16_FLOPS
                 ) -> float:
  """Model FLOP utilization: executable FLOPs over (time x device peak)."""
  return flops / max(step_sec, 1e-12) / peak_flops


def analyze_jit(name: str, fn, *args,
                registry: Optional[metrics_lib.Registry] = None,
                collect: bool = True,
                cache=None) -> Tuple[Any, Dict[str, Any]]:
  """AOT trace->lower->compile of a jitted `fn` at `args`, instrumented.

  Returns `(compiled, record)` where `compiled` is the executable
  (callable with the same signature and shardings/donation as `fn`) and
  `record` is a JSON-safe dict: per-phase times (`trace_s`, `lower_s`,
  `compile_s`), `jaxpr_eqns`, declared `donated_bytes` /
  `undonated_bytes`, XLA `flops` / `bytes_accessed` (None where the
  backend reports none), memory analysis (`temp_bytes`, `output_bytes`,
  `argument_bytes`, `generated_code_bytes`), and the derived
  `arithmetic_intensity` (FLOPs/byte) + `roofline_ms`.

  `roofline_ms` always prices against the project's one real device
  class (v5e public peaks, `utils.backend`), whatever backend compiled
  the executable — it answers "what SHOULD this step cost on the chip",
  which is exactly the number the round-5 valley violated 16x.

  `cache` (an `obs.excache.ExecutableCache` or a directory path)
  short-circuits lower+compile with a persisted executable when the
  content-addressed key (jaxpr fingerprint, abstract shapes/dtypes/
  shardings, donation layout, static args, device topology, backend
  version) hits: the record then carries the COLD process's cost/memory
  analysis plus a `cache` block (`{hit, key, load_ms, bytes}`) and
  `lower_s == compile_s == 0`. A load failure of any kind — corrupt
  blob, version skew, key trouble — falls back to the fresh compile
  below (cache trouble must never take down the run, the same contract
  as every other telemetry path here); a miss stores the fresh
  executable for the next process.

  Raises on (compile) failure — callers that must not die use
  `XrayedFunction` (or wrap in try/except) and keep the plain jitted fn.
  """
  from tensor2robot_tpu.obs import excache as excache_lib

  reg = registry or metrics_lib.get_registry()
  cache = excache_lib.as_cache(cache)
  t0 = time.perf_counter()
  traced = fn.trace(*args)
  t1 = time.perf_counter()

  cache_key = None
  if cache is not None:
    try:
      cache_key = excache_lib.cache_key(
          name, **excache_lib.key_components_from_traced(traced, args))
    except Exception as e:  # noqa: BLE001 - key trouble = no caching
      reg.counter("cache/key_failures").inc()
      print(f"graftcache: key computation for {name!r} failed "
            f"({type(e).__name__}: {e}); compiling fresh",
            file=sys.stderr)
    if cache_key is not None:
      entry = cache.load(cache_key)
      if entry is not None:
        donated, undonated = _donation_bytes(traced, args)
        record = dict(entry["record"])
        record.update({
            "name": name,
            "trace_s": t1 - t0,
            "lower_s": 0.0,
            "compile_s": 0.0,
            "jaxpr_eqns": _count_eqns(traced.jaxpr),
            "donated_bytes": donated,
            "undonated_bytes": undonated,
            "cache": {"hit": True, "key": cache_key,
                      "load_ms": entry["load_ms"],
                      "bytes": entry["bytes"]},
        })
        record.setdefault("flops", None)
        record.setdefault("bytes_accessed", None)
        reg.counter("xray/analyses").inc()
        reg.gauge(f"xray/{name}/cache_load_ms").set(entry["load_ms"])
        if collect:
          _collect(record)
        return entry["compiled"], record

  lowered = traced.lower()
  t2 = time.perf_counter()
  if cache is not None and cache_key is not None:
    # An AOT-tier miss about to be stored compiles WITHOUT the XLA
    # persistent cache: an executable served out of that cache does not
    # survive the serialize round-trip, so the entry could never
    # (re)fill — see excache.xla_cache_bypassed.
    with excache_lib.xla_cache_bypassed():
      compiled = lowered.compile()
  else:
    compiled = lowered.compile()
  t3 = time.perf_counter()

  donated, undonated = _donation_bytes(traced, args)
  record: Dict[str, Any] = {
      "name": name,
      "trace_s": t1 - t0,
      "lower_s": t2 - t1,
      "compile_s": t3 - t2,
      "jaxpr_eqns": _count_eqns(traced.jaxpr),
      "donated_bytes": donated,
      "undonated_bytes": undonated,
  }
  flops = bytes_accessed = None
  try:
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
    if "flops" in cost:
      flops = float(cost["flops"])
    if "bytes accessed" in cost:
      bytes_accessed = float(cost["bytes accessed"])
  except Exception:  # noqa: BLE001 - cost analysis is backend-optional
    pass
  record["flops"] = flops
  record["bytes_accessed"] = bytes_accessed
  # flops == 0.0 is a valid answer (copy/gather-dominated executables):
  # the memory-bound roofline bytes/BW is exactly the health-check
  # number then, so only a missing/zero bytes figure disables it.
  if flops is not None and bytes_accessed:
    record["arithmetic_intensity"] = flops / bytes_accessed
    record["roofline_ms"] = 1e3 * max(
        flops / backend_lib.V5E_PEAK_BF16_FLOPS,
        bytes_accessed / backend_lib.V5E_PEAK_HBM_BW)
    record["peak_flops"] = backend_lib.V5E_PEAK_BF16_FLOPS
    record["peak_hbm_bw"] = backend_lib.V5E_PEAK_HBM_BW
  try:
    mem = compiled.memory_analysis()
    if mem is not None:
      record["temp_bytes"] = float(mem.temp_size_in_bytes)
      record["output_bytes"] = float(mem.output_size_in_bytes)
      record["argument_bytes"] = float(mem.argument_size_in_bytes)
      record["generated_code_bytes"] = float(
          mem.generated_code_size_in_bytes)
  except Exception:  # noqa: BLE001 - memory analysis is backend-optional
    pass

  if cache is not None and cache_key is not None:
    # Persist for the NEXT process (best-effort, counted); the stored
    # sidecar carries this record so a warm start keeps full compile
    # telemetry without paying the compile.
    stored = cache.store(cache_key, compiled, record=record, name=name)
    record["cache"] = {"hit": False, "key": cache_key, "stored": stored}

  reg.counter("xray/analyses").inc()
  reg.gauge(f"xray/{name}/compile_s").set(record["compile_s"])
  reg.gauge(f"xray/{name}/jaxpr_eqns").set(float(record["jaxpr_eqns"]))
  reg.gauge(f"xray/{name}/donated_bytes").set(donated)
  if flops is not None:
    reg.gauge(f"xray/{name}/flops").set(flops)
  if bytes_accessed is not None:
    reg.gauge(f"xray/{name}/bytes_accessed").set(bytes_accessed)
  if collect:
    _collect(record)
  return compiled, record


class XrayedFunction:
  """Lazily X-rays a jitted fn on its first call; never breaks the call.

  The first invocation runs `analyze_jit` at the live arguments and
  keeps the AOT executable for every later call (the same compile the
  plain jit would have paid on first dispatch — no double work, the
  plain path never compiles). Any failure — no AOT support, a backend
  without cost analysis, a later call at different shapes that the
  frozen executable rejects — permanently degrades to the plain jitted
  fn with a counter bump (`xray/analyze_failures` /
  `xray/compiled_call_fallbacks`), because telemetry must never take
  down a train loop or a serving path.
  """

  def __init__(self, name: str, fn,
               registry: Optional[metrics_lib.Registry] = None,
               cache=None):
    self._name = name
    self._fn = fn
    self._registry = registry or metrics_lib.get_registry()
    # graftcache seam: a persisted executable turns the first call's
    # compile into a deserialize (trainer restarts warm-start); all cache failure modes already degrade inside analyze_jit.
    self._cache = cache
    self._compiled = None
    self._record: Optional[Dict[str, Any]] = None
    self._failed = False
    self._lock = threading.Lock()

  @property
  def record(self) -> Optional[Dict[str, Any]]:
    return self._record

  def _analyze(self, args) -> None:
    with self._lock:
      if self._compiled is not None or self._failed:
        return
      try:
        start_ns = time.perf_counter_ns()
        self._compiled, self._record = analyze_jit(
            self._name, self._fn, *args, registry=self._registry,
            cache=self._cache)
        # The compile-or-cache-load of the first call, as a child of the
        # caller's span (the trainer's first `train/dispatch`); the
        # record holds its trace, lower and compile seconds.
        trace_lib.get_tracer().add_complete(
            "xray/analyze", start_ns, time.perf_counter_ns() - start_ns,
            cat="xray", args={
                "executable": self._name,
                "cache_hit": bool(
                    (self._record.get("cache") or {}).get("hit"))})
      except Exception as e:  # noqa: BLE001 - degrade, never break the call
        self._failed = True
        self._registry.counter("xray/analyze_failures").inc()
        from absl import logging

        logging.warning("graftscope-xray: analysis of %r unavailable "
                        "(%s: %s); running the plain jitted fn",
                        self._name, type(e).__name__, e)

  def __call__(self, *args):
    if self._compiled is None and not self._failed:
      self._analyze(args)
    compiled = self._compiled
    if compiled is None:
      return self._fn(*args)
    try:
      return compiled(*args)
    except Exception:  # noqa: BLE001 - e.g. new shapes vs frozen executable
      with self._lock:
        self._compiled = None
        self._failed = True
      # Retry on the plain jit ONLY while the inputs are intact — i.e.
      # the failure was a pre-execution rejection (shape/dtype mismatch
      # against the frozen executable). An execution-phase error on a
      # donating fn (e.g. jax_debug_nans) has already consumed its
      # donated buffers; retrying would mask the real error behind an
      # "Array has been deleted", so re-raise the original instead.
      import jax

      if any(getattr(leaf, "is_deleted", lambda: False)()
             for leaf in jax.tree_util.tree_leaves(args)):
        raise
      self._registry.counter("xray/compiled_call_fallbacks").inc()
      # The plain jit re-traces at the new shapes; a genuine math/user
      # error re-raises from here unchanged.
      return self._fn(*args)


# ---------------------------------------------------------------------------
# Memory accounting.
# ---------------------------------------------------------------------------


def memory_accounting(state=None, batch=None,
                      num_data_shards: Optional[int] = None
                      ) -> Dict[str, float]:
  """Prices a TrainState (+ optional batch) in bytes, global and
  per-shard.

  `state` is duck-typed on the TrainState fields (`params`,
  `opt_state`, `ema_params`, `mutable_state`); any may be absent.
  Per-shard bytes come from each leaf's committed sharding
  (`sharding.shard_shape`); replicated leaves cost full bytes per
  device. A HOST batch (numpy, no shardings) is divided by
  `num_data_shards` when given — the data-parallel placement estimate
  for batches that are not on device yet.
  """
  out: Dict[str, float] = {}
  state_total = state_shard = 0
  for field, key in (("params", "params"), ("opt_state", "opt_state"),
                     ("ema_params", "ema"), ("mutable_state", "mutable")):
    tree = getattr(state, field, None)
    if tree is None:
      continue
    total = pytree_bytes(tree)
    shard = pytree_shard_bytes(tree)
    out[f"{key}_bytes"] = float(total)
    out[f"{key}_bytes_per_shard"] = float(shard)
    state_total += total
    state_shard += shard
  if state is not None:
    out["state_bytes"] = float(state_total)
    out["state_bytes_per_shard"] = float(state_shard)
  if batch is not None:
    total = pytree_bytes(batch)
    shard = pytree_shard_bytes(batch)
    if shard == total and num_data_shards and num_data_shards > 1:
      shard = -(-total // num_data_shards)  # host batch: ceil split
    out["batch_bytes"] = float(total)
    out["batch_bytes_per_shard"] = float(shard)
  return out


def hbm_watermark_estimate(memory: Dict[str, float],
                           compile_records=()) -> float:
  """Per-device HBM watermark estimate in bytes.

  resident state + resident batch + the executable's scratch: XLA's
  `temp_bytes` when a compile record reports it, else the param bytes
  again (the gradient/update buffers a train step materializes — the
  floor for any backward pass). An ESTIMATE, not an allocator readout:
  its job is to say "b512 will not fit in 16 GB" BEFORE the probe OOMs
  blind, the way rounds 2–5 did.
  """
  temp = max((float(r.get("temp_bytes") or 0.0) for r in compile_records),
             default=0.0)
  scratch = max(temp, memory.get("params_bytes_per_shard", 0.0))
  return (memory.get("state_bytes_per_shard", 0.0)
          + memory.get("batch_bytes_per_shard", 0.0) + scratch)
