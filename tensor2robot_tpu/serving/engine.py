"""graftserve engine: shape-bucketed executable cache over a predictor.

The reference's serving runtime stops at one-request-per-session-call
SavedModel serving
(/root/reference/predictors/exported_savedmodel_predictor.py:53-359);
it has no executable reuse story at all — TF sessions re-specialize
per feed shape behind the scenes.

The recompile problem this solves: a jitted predict fn compiles per
input SHAPE, and serving traffic arrives at every batch size — each
fresh compile costs seconds (a 472 px Grasping44 bucket: ~4 s on the
v5e, my chip run, PR 22) while clients wait, and the
in-process predictor's xray wrapper freezes at its FIRST live shape,
permanently degrading every other size to plain-jit dispatch (one
compile per new size, forever). Production inference engines fix this
with compile-once/serve-many executable reuse (PAPERS.md: portable O(1)
autoregressive caching; the Gemma-on-TPU serving writeup): pad requests
up a small bucket ladder so a handful of executables, compiled ONCE at
startup, cover every request size.

`BucketedEngine` implements that cache:

* a bucket ladder (default: doubling 1/2/4/.../max_batch_size) — each
  bucket AOT-compiled eagerly at `warmup()` through the graftscope-xray
  path (`obs.xray.analyze_jit`), so compile time, jaxpr size, roofline
  and per-bucket cost analysis land in the metrics registry and the
  run's `runs.jsonl` record like every other executable in this repo;
* a graftcache seam (`cache=` — an `obs.excache.ExecutableCache` or a
  directory path): warmup loads the whole bucket ladder from the
  persistent executable cache, so a serving COLD START in a fresh
  process pays N deserializes instead of N compiles. `compile_count` counts FRESH compiles
  only — a fully warm start reports `compile_count == 0` with
  `cache_loads == len(buckets)` (tests/test_excache.py pins it across
  processes), and a stale/corrupt entry silently costs one fresh
  compile (the excache fallback contract);
* `predict(features)` pads the batch up to the smallest covering bucket
  (pad rows repeat row 0 — always in-distribution, never NaN fodder),
  dispatches the CACHED executable, host-fetches, and masks the pad
  rows out of every returned output;
* a pinned zero-recompile guarantee: after warmup every spec-conforming
  request hits a cached executable (`serve/engine/compiles` stays at
  `len(buckets)` — tests/test_graftserve.py pins it across a randomized
  request-size sweep). Requests larger than the top bucket are chunked
  into top-bucket dispatches;
* serving never breaks on cache trouble: a Compiled call rejected at
  dispatch (e.g. off-spec dtypes) falls back to the plain jitted fn
  (counted: `serve/engine/exec_fallbacks`), mirroring
  `obs.xray.XrayedFunction`.

Backend-free at import like `obs/`: jax is imported only inside methods,
which run where the backend is already up (tier-1 poisoned-platform
trap covers this module).
"""

from __future__ import annotations

import threading
import time
from typing import (Any, Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

import numpy as np

from tensor2robot_tpu.obs import graftrace
from tensor2robot_tpu.obs import metrics as obs_metrics
from tensor2robot_tpu.obs import trace as obs_trace
from tensor2robot_tpu.utils import config

__all__ = ["BucketedEngine", "bucket_ladder", "traffic_bucket_ladder",
           "ladder_padding_stats", "observed_request_rows"]


def bucket_ladder(max_batch_size: int) -> List[int]:
  """The default doubling ladder 1, 2, 4, ... with max always included
  (a non-power-of-two max becomes the top rung: 12 -> [1, 2, 4, 8, 12])."""
  if max_batch_size < 1:
    raise ValueError(f"max_batch_size must be >= 1, got {max_batch_size}")
  ladder = []
  b = 1
  while b < max_batch_size:
    ladder.append(b)
    b *= 2
  ladder.append(max_batch_size)
  return ladder


def observed_request_rows(histogram_name: str = "serve/request_rows"
                          ) -> List[int]:
  """Observed per-request row counts from the serving telemetry stream
  (`MicroBatcher.predict` records every request's rows into the
  `serve/request_rows` histogram; the reservoir is an unbiased sample
  of the full traffic). The input side of `traffic_bucket_ladder` —
  ROADMAP item 1's "derive the ladder from observed traffic"."""
  return [int(v) for v in obs_metrics.histogram(histogram_name).values()]


def traffic_bucket_ladder(sizes: Sequence[int],
                          max_batch_size: int,
                          min_share: float = 0.05,
                          split_waste: float = 0.25,
                          max_buckets: int = 8) -> List[int]:
  """Bucket ladder derived from OBSERVED request sizes (ROADMAP item 1).

  The fixed doubling ladder spends one compiled executable per power of
  two regardless of where the traffic actually lands; real fleets see
  skewed size mixes (a robot fleet ticking at batch 1, a CEM sweep at
  24), so the compile budget should sit where the rows are. Starting
  from the fixed ladder (`bucket_ladder` — the fallback and the A/B
  baseline, kept verbatim when traffic is uniform):

  1. MERGE: repeatedly drop the non-top rung carrying the smallest
     traffic share below `min_share` — a rarely-hit rung costs a whole
     compile (seconds) to save padding on almost no
     traffic; its requests pad up to the next rung.
  2. SPLIT: repeatedly insert the traffic-median size of the rung whose
     mean padded-row fraction exceeds `split_waste` (while under
     `max_buckets`) — a hot rung wasting >25 % of its dispatched rows
     on padding earns a tighter rung at the size the traffic actually
     has.

  Merges run to fixpoint before splits (the two passes cannot cycle),
  every boundary decision is deterministic in `sizes`, and the top rung
  is always `max_batch_size` (oversize requests chunk through it, so
  they count as `max_batch_size` here). Uniform traffic over
  [1, max_batch_size] leaves the fixed ladder unchanged — the A/B
  baseline property tests/test_fleet.py pins. Empty `sizes` returns the
  fixed ladder (the fallback)."""
  if max_batch_size < 1:
    raise ValueError(f"max_batch_size must be >= 1, got {max_batch_size}")
  sizes = [min(int(s), max_batch_size) for s in sizes if int(s) >= 1]
  base = bucket_ladder(max_batch_size)
  if not sizes:
    return base
  ladder = list(base)

  def _assign(ladder_now: List[int]):
    by_rung: Dict[int, List[int]] = {b: [] for b in ladder_now}
    for size in sizes:
      for b in ladder_now:
        if b >= size:
          by_rung[b].append(size)
          break
    return by_rung

  # Merge pass (to fixpoint): drop under-trafficked rungs, never the top.
  while len(ladder) > 1:
    by_rung = _assign(ladder)
    total = float(len(sizes))
    droppable = [(len(by_rung[b]) / total, b) for b in ladder[:-1]
                 if len(by_rung[b]) / total < min_share]
    if not droppable:
      break
    ladder.remove(min(droppable)[1])

  # Split pass (to fixpoint): tighten rungs wasting rows on padding.
  while len(ladder) < max_buckets:
    by_rung = _assign(ladder)
    worst = None
    for b in ladder:
      rows = by_rung[b]
      if not rows:
        continue
      waste = sum((b - s) / b for s in rows) / len(rows)
      if waste > split_waste and (worst is None or waste > worst[0]):
        worst = (waste, b, rows)
    if worst is None:
      break
    rows = sorted(worst[2])
    median = rows[len(rows) // 2]
    if median in ladder or median == worst[1]:
      break
    ladder = sorted(ladder + [median])
  return ladder


def ladder_padding_stats(sizes: Sequence[int],
                         ladder: Sequence[int]) -> Dict[str, float]:
  """Padding economics of `ladder` over observed `sizes`: the
  fixed-vs-derived numbers.
  `padded_row_frac` is the fraction of dispatched rows that are padding;
  `dispatch_rows_per_row` the dispatched/requested row blow-up."""
  ladder = sorted(set(int(b) for b in ladder))
  if not ladder:
    raise ValueError("ladder must be non-empty")
  top = ladder[-1]
  sizes = [int(s) for s in sizes if int(s) >= 1]
  if not sizes:
    return {"requested_rows": 0.0, "dispatched_rows": 0.0,
            "padded_row_frac": 0.0, "dispatch_rows_per_row": 1.0,
            "buckets": float(len(ladder))}
  requested = 0
  dispatched = 0
  for size in sizes:
    requested += size
    full, rest = divmod(size, top)
    dispatched += full * top
    if rest:
      dispatched += next(b for b in ladder if b >= rest)
  return {
      "requested_rows": float(requested),
      "dispatched_rows": float(dispatched),
      "padded_row_frac": (dispatched - requested) / dispatched
      if dispatched else 0.0,
      "dispatch_rows_per_row": dispatched / requested if requested else 1.0,
      "buckets": float(len(ladder)),
  }


def _pad_rows(array: np.ndarray, bucket: int) -> np.ndarray:
  """Pads the leading dim up to `bucket` by repeating row 0 (always a
  valid, in-distribution row — zero padding can feed NaN-producing ops
  like normalizations on degenerate inputs)."""
  rows = array.shape[0]
  if rows == bucket:
    return array
  pad = np.broadcast_to(array[:1], (bucket - rows,) + array.shape[1:])
  return np.concatenate([array, pad], axis=0)


@config.configurable
class BucketedEngine:
  """Shape-bucketed executable cache in front of a predictor.

  Wraps any `_JaxPredictorBase` (via its `serving_bundle()` seam).
  Duck-types the predictor contract, so callers — policies, env loops,
  a `MicroBatcher` — use it exactly like the predictor it fronts.
  """

  def __init__(self, predictor=None,
               max_batch_size: int = 8,
               buckets: Optional[Sequence[int]] = None,
               name: str = "serve/engine",
               cache=None,
               cache_namespace: Optional[str] = None):
    if predictor is None:
      raise ValueError("predictor is required.")
    self._predictor = predictor
    if buckets is not None:
      buckets = sorted(set(int(b) for b in buckets))
      if not buckets or buckets[0] < 1:
        raise ValueError(f"buckets must be positive ints, got {buckets}")
      max_batch_size = buckets[-1]
    else:
      buckets = bucket_ladder(max_batch_size)
    self._buckets = buckets
    self._max_batch_size = max_batch_size
    self._name = name
    # graftcache (obs.excache): persistent executable cache for the
    # bucket ladder. Deferred coercion — a str path must not import
    # excache machinery at construction in backend-free contexts.
    # `cache_namespace` names the analyze_jit records (and so the cache
    # KEY prefix) independently of the telemetry `name`: N fleet
    # replicas with per-replica names share one namespace, so a single
    # forged entry set warms every replica (graftforge; keys still
    # diverge per replica when state placement differs — the sharding
    # component — but identically-placed replicas deduplicate).
    self._cache = cache
    self._cache_namespace = cache_namespace or name
    self._compiled: Dict[int, Callable] = {}
    self._records: Dict[int, Dict[str, Any]] = {}
    self._compile_count = 0
    self._cache_loads = 0
    self._warmup_ms: Optional[float] = None
    self._warmup_load_ms = 0.0
    self._warmup_compile_ms = 0.0
    self._warmup_provenance: List[Dict[str, Any]] = []
    self._bundle = None
    self._lock = threading.Lock()

  # -- warmup ---------------------------------------------------------------

  @property
  def buckets(self) -> List[int]:
    return list(self._buckets)

  @property
  def compile_count(self) -> int:
    """FRESH compiles paid by this process (cache loads excluded) —
    without a cache this equals `len(buckets)` after warmup (the pinned
    zero-recompile guarantee); a fully warm cached start reports 0."""
    return self._compile_count

  @property
  def cache_loads(self) -> int:
    """Buckets served from the persistent executable cache at warmup."""
    return self._cache_loads

  @property
  def warmup_ms(self) -> Optional[float]:
    """Wall-clock of the last warmup that did work (None before warmup).
    THE serving cold-start headline: graftscope diff gates it."""
    return self._warmup_ms

  @property
  def warmup_load_ms(self) -> float:
    """Warmup wall spent DESERIALIZING cached executables (graftcache
    hits). `warmup_ms == warmup_load_ms + warmup_compile_ms` up to
    arena/bundle bookkeeping — the split that makes a forge regression
    attributable: a forged start is all load, a cold start all compile,
    and a creeping compile share means entries stopped hitting."""
    return self._warmup_load_ms

  @property
  def warmup_compile_ms(self) -> float:
    """Warmup wall spent on FRESH trace+lower+compile (cache misses and
    AOT-less degrades)."""
    return self._warmup_compile_ms

  @property
  def warmup_provenance(self) -> List[Dict[str, Any]]:
    """Per-rung warmup provenance: `{rung, source, ms, key}` where
    `source` is 'cache' (deserialized), 'compile' (fresh), or
    'fallback' (AOT-less plain-jit degrade). Stamped into the serving
    run records so per-rung forge regressions are attributable."""
    return [dict(p) for p in self._warmup_provenance]

  @property
  def compile_records(self) -> List[Dict[str, Any]]:
    """Per-bucket xray records (compile time, flops, roofline, ...)."""
    return [dict(self._records[b]) for b in self._buckets
            if b in self._records]

  def warmup(self) -> "BucketedEngine":
    """Eagerly AOT-compiles every bucket through graftscope-xray.

    Synthesizes a wire-layout batch per bucket from the predictor's
    feature spec, runs it through the SAME host preprocess the live path
    uses (so the compiled pytree structure/dtypes match real traffic
    exactly), and caches the compiled executable. Idempotent; called
    again after a predictor `restore()` it is a no-op (shapes are stable
    across restores — only param values change, and the engine reads
    state through the bundle's getter at every dispatch).
    """
    from tensor2robot_tpu import specs as specs_lib
    from tensor2robot_tpu.obs import excache as excache_lib
    from tensor2robot_tpu.obs import xray as obs_xray

    with self._lock:
      cache = excache_lib.as_cache(self._cache)
      bundle = self._bundle = self._predictor.serving_bundle()
      warmup_start = time.perf_counter()
      did_work = False
      for bucket in self._buckets:
        if bucket in self._compiled:
          continue
        did_work = True
        self._warm_bucket_locked(bucket, bundle, cache, specs_lib,
                                 obs_xray)
      if did_work:
        self._warmup_ms = (time.perf_counter() - warmup_start) * 1e3
        obs_metrics.gauge("serve/engine/warmup_ms").set(self._warmup_ms)
        obs_metrics.gauge("serve/engine/warmup_load_ms").set(
            self._warmup_load_ms)
        obs_metrics.gauge("serve/engine/warmup_compile_ms").set(
            self._warmup_compile_ms)
    return self

  def _warm_bucket_locked(self, bucket: int, bundle, cache,
                          specs_lib, obs_xray) -> None:
    """Compiles (or cache-loads) ONE rung, with per-rung provenance —
    which rungs were deserializes vs fresh compiles is what makes a
    forge/cache regression attributable (`warmup_provenance`)."""
    wire = specs_lib.make_random_numpy(bundle.feature_spec,
                                       batch_size=bucket, seed=0)
    features = bundle.preprocess(wire)
    start = time.perf_counter()
    rec_name = f"{self._cache_namespace}/bucket{bucket}"
    source = "compile"
    try:
      compiled, record = obs_xray.analyze_jit(
          rec_name, bundle.jit_predict,
          bundle.get_state(), features, cache=cache)
    except Exception as e:  # noqa: BLE001 - AOT-less backends
      # No AOT support: dispatch the plain jit once at this shape —
      # jax's own per-shape cache then serves later calls without
      # recompiling, preserving the zero-recompile guarantee with
      # degraded (no cost-analysis) telemetry.
      bundle.jit_predict(bundle.get_state(), features)
      compiled = None
      source = "fallback"
      record = {"name": rec_name,
                "compile_s": time.perf_counter() - start,
                "error": f"{type(e).__name__}: {e}"}
    elapsed_ms = (time.perf_counter() - start) * 1e3
    self._compiled[bucket] = compiled
    self._records[bucket] = record
    cache_block = record.get("cache") or {}
    if cache_block.get("hit"):
      # Served from graftcache: a deserialize, not a compile — the
      # cold-start economics this cache exists for.
      source = "cache"
      self._cache_loads += 1
      self._warmup_load_ms += elapsed_ms
      obs_metrics.counter("serve/engine/cache_loads").inc()
    else:
      self._compile_count += 1
      self._warmup_compile_ms += elapsed_ms
      obs_metrics.counter("serve/engine/compiles").inc()
    self._warmup_provenance.append(
        {"rung": bucket, "source": source, "ms": elapsed_ms,
         "key": cache_block.get("key")})
    obs_metrics.gauge(
        f"serve/engine/bucket{bucket}/compile_s").set(
            float(record.get("compile_s") or 0.0))

  def reladder(self, buckets: Sequence[int]) -> "BucketedEngine":
    """Atomically moves the engine onto a new bucket ladder, warming
    any NEW rungs (compile or graftcache load) BEFORE the swap — the
    rollout pre-forge seam: a traffic-derived ladder change
    (`traffic_bucket_ladder`) must never put a cold rung in front of
    live traffic (one fresh rung = one compile a client would wait
    out). Rungs no longer on the ladder keep their cached
    executables (an oversize request chunks through the top rung, so
    dropped executables are simply unused; a reladder back is free).
    """
    from tensor2robot_tpu import specs as specs_lib
    from tensor2robot_tpu.obs import excache as excache_lib
    from tensor2robot_tpu.obs import xray as obs_xray

    buckets = sorted(set(int(b) for b in buckets))
    if not buckets or buckets[0] < 1:
      raise ValueError(f"buckets must be positive ints, got {buckets}")
    with self._lock:
      if self._bundle is None:
        self._bundle = self._predictor.serving_bundle()
      cache = excache_lib.as_cache(self._cache)
      for bucket in buckets:
        if bucket not in self._compiled:
          self._warm_bucket_locked(bucket, self._bundle, cache,
                                   specs_lib, obs_xray)
      # Every rung warm: the swap itself is one assignment under the
      # lock — concurrent predicts see either ladder, both fully warm.
      self._buckets = buckets
      self._max_batch_size = buckets[-1]
      obs_metrics.counter("serve/engine/reladders").inc()
    return self

  def rung_traces(self) -> List[Tuple[int, Any, Tuple]]:
    """`[(rung, traced, args), ...]` for every ladder rung — trace-only,
    never a lower or compile. The one arg-synthesis seam `warmup()`,
    `rung_cache_keys()` (graftforge --verify) and `graftscope audit`
    (jaxpr_audit) all reason over: the traced program IS the program a
    live warmup would compile, so whatever the audit reads off its
    jaxpr (baked constants, donation flags, loop bodies) is what
    deployment pays. Tracing is cheap and side-effect-free (donation is
    declared, not consumed, at trace time)."""
    from tensor2robot_tpu import specs as specs_lib

    with self._lock:
      if self._bundle is None:
        self._bundle = self._predictor.serving_bundle()
      bundle = self._bundle
      state = bundle.get_state()
      traces: List[Tuple[int, Any, Tuple]] = []
      for bucket in self._buckets:
        wire = specs_lib.make_random_numpy(bundle.feature_spec,
                                           batch_size=bucket, seed=0)
        features = bundle.preprocess(wire)
        args = (state, features)
        traces.append((bucket, bundle.jit_predict.trace(*args), args))
      return traces

  def rung_cache_keys(self) -> Dict[int, str]:
    """The graftcache key of every rung WITHOUT compiling (trace-only).

    The graftforge `--verify` seam: keys come from the SAME bundle /
    wire-synthesis / trace path `warmup()` compiles through
    (`rung_traces`), so a key this returns is byte-identical to the one
    a live warmup would look up — the engine owns its arg synthesis in
    one place and the forge CLI can check an existing cache against it
    without paying a single lower+compile."""
    from tensor2robot_tpu.obs import excache as excache_lib

    return {
        bucket: excache_lib.cache_key(
            f"{self._cache_namespace}/bucket{bucket}",
            **excache_lib.key_components_from_traced(traced, args))
        for bucket, traced, args in self.rung_traces()}

  def _bucket_for(self, rows: int) -> int:
    for bucket in self._buckets:
      if bucket >= rows:
        return bucket
    raise AssertionError(f"no bucket covers {rows} rows")  # chunked before

  # -- serving --------------------------------------------------------------

  def predict(self, features: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    """Bucket-padded predict; outputs match unbatched predict row-for-row.

    Oversize requests are served in top-bucket chunks and re-assembled —
    callers never see the ladder.
    """
    if not self._compiled:
      self.warmup()
    features = {k: np.asarray(v) for k, v in dict(features).items()}
    rows = next(iter(features.values())).shape[0]
    if rows < 1:
      raise ValueError("request must have at least one row (got 0)")
    start = time.perf_counter()
    with obs_trace.span("serve/engine/predict", cat="serve", rows=rows):
      if rows <= self._max_batch_size:
        result = self._predict_chunk(features, rows)
      else:
        chunks = []
        chunk_rows = []
        for offset in range(0, rows, self._max_batch_size):
          chunk = {k: v[offset:offset + self._max_batch_size]
                   for k, v in features.items()}
          chunk_rows.append(next(iter(chunk.values())).shape[0])
          chunks.append(self._predict_chunk(chunk, chunk_rows[-1]))
        result = {}
        for k in chunks[0]:
          first = np.asarray(chunks[0][k])
          # Batched outputs (leading dim == that chunk's rows) re-join
          # across chunks; non-batched ones (scalars / fixed-size
          # diagnostics) are identical per chunk — keep the first.
          if first.ndim and first.shape[0] == chunk_rows[0]:
            result[k] = np.concatenate([c[k] for c in chunks], axis=0)
          else:
            result[k] = first
    obs_metrics.histogram("serve/engine/predict_ms").record(
        (time.perf_counter() - start) * 1e3)
    obs_metrics.counter("serve/engine/rows").inc(rows)
    return result

  def _predict_chunk(self, features: Dict[str, np.ndarray],
                     rows: int) -> Dict[str, np.ndarray]:
    bundle = self._bundle
    bucket = self._bucket_for(rows)
    # Preprocess the REAL rows only, then pad the model-layout features
    # up to the bucket — host preprocessing is per-row work on the
    # serving hot path, and preprocessing pad rows would multiply it by
    # bucket/rows. Shapes still match the warmup-compiled executable
    # (warmup preprocesses a full bucket, and preprocess is per-row:
    # the split-exactness tests pin outputs against unbatched predict).
    # Only leaves whose leading dim is the batch get padded — the same
    # shape[0] test the pad-mask below and `batcher._split_outputs` use.
    model_features = bundle.preprocess(features)
    if bucket != rows:
      import jax

      # `pad` is an informational sub-stage of the batcher's dispatch
      # window (graftrace.INFO_STAGES) — reported in the breakdown but
      # excluded from the reconciliation sum, which would otherwise
      # double-count it inside `dispatch`.
      pad_ns = time.perf_counter_ns()
      obs_metrics.counter("serve/engine/padded_rows").inc(bucket - rows)
      model_features = jax.tree_util.tree_map(
          lambda a: _pad_rows(np.asarray(a), bucket)
          if getattr(a, "ndim", 0) and np.asarray(a).shape[0] == rows
          else a, model_features)
      graftrace.record_stage(
          "pad", (time.perf_counter_ns() - pad_ns) / 1e6,
          ctx=graftrace.current(), start_ns=pad_ns)
    state = bundle.get_state()
    compiled = self._compiled.get(bucket)
    device_ns = time.perf_counter_ns()
    try:
      if compiled is not None:
        outputs = compiled(state, model_features)
      else:
        outputs = bundle.jit_predict(state, model_features)
    except Exception:  # noqa: BLE001 - never break serving on the cache
      # Pre-execution rejection by the frozen executable (off-spec
      # dtype/layout traffic): degrade THIS call to the plain jit —
      # correctness first, the recompile it may cost is counted.
      obs_metrics.counter("serve/engine/exec_fallbacks").inc()
      outputs = bundle.jit_predict(state, model_features)
    # The np.asarray fetch is the barrier (and surfaces device
    # errors); pad rows are masked out AFTER the
    # fetch so the device sees only full-bucket shapes. Only outputs
    # whose leading dim IS the padded batch get sliced — a non-batched
    # output (a scalar or fixed-size diagnostic) passes through intact,
    # the same shape[0] test `batcher._split_outputs` applies.
    out = {}
    for k, v in dict(outputs).items():
      v = np.asarray(v)
      if v.ndim and v.shape[0] == bucket:
        v = v[:rows]
      out[k] = v
    # `device` = executable call + host fetch (the real barrier): the
    # other dispatch-internal sub-stage, same exclusion rule as `pad`.
    device_ms = (time.perf_counter_ns() - device_ns) / 1e6
    graftrace.record_stage(
        "device", device_ms, ctx=graftrace.current(), start_ns=device_ns)
    # Cumulative device-occupancy counter: the engine-level busy signal
    # the graftwatch ledger's per-group numbers cross-check against
    # (stage histograms are reservoir-sampled; this is exact).
    obs_metrics.counter("serve/engine/device_busy_ms").inc(device_ms)
    return out

  # -- predictor duck-type passthroughs -------------------------------------

  def get_feature_specification(self):
    return self._predictor.get_feature_specification()

  def restore(self) -> bool:
    ok = self._predictor.restore()
    if ok and self._bundle is not None:
      # Re-bind the bundle so a model swapped in by restore() (not just
      # new params) is picked up; cached executables stay valid because
      # shapes/dtypes are pinned by the spec.
      self._bundle = self._predictor.serving_bundle()
    return ok

  @property
  def global_step(self) -> int:
    return self._predictor.global_step

  @property
  def model_version(self) -> int:
    return self.global_step

  def assert_is_loaded(self) -> None:
    self._predictor.assert_is_loaded()

  def close(self) -> None:
    self._predictor.close()
