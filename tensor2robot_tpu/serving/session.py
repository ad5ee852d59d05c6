"""graftserve sessions: device-resident decode caches for O(1) ticks.

The reference's serving story — one SavedModel predict per session
call (/root/reference/predictors/exported_savedmodel_predictor.py:
53-359), recurrent state threaded HOST-side by the policy
(/root/reference/policies/policies.py:188-218 LSTMCEMPolicy) — and
graftserve up to PR 5 are STATELESS: every predict re-runs the model
end to end, so a sequential policy (the causal-attention trunk in
`models/sequence_model.py`, the LSTM carry of `LSTMRegressionModel`,
SNAIL/TEC episodic conditioning)
pays the full O(T) prefix on every control tick — at T=32 a robot fleet
does ~32x the necessary per-tick FLOPs (ROADMAP item 3). Production
autoregressive serving fixes this with continuous batching over
per-session decode caches (PAPERS.md: "Compiler-First State Space
Duality and Portable O(1) Autoregressive Caching for Inference",
arXiv:2603.09555; the Gemma-on-TPU batched serving economics): session
state lives ON DEVICE between requests and one decode-step executable
advances N sessions one tick per dispatch.

`SessionEngine` is that runtime:

* a device-resident session-state ARENA: one pytree whose leaves are
  [max_sessions + 1, ...] stacks of per-session decode state (KV cache
  rows / LSTM carries / tick index) built from the model's
  `init_session_state` seam. Slot 0 is the reserved NULL slot — pad
  lanes of a partial dispatch gather and scatter through it, so masked
  writes can never clobber a live session (every live slot appears at
  most once per dispatch; null-slot duplicates all carry the same
  masked-out value);
* a bucketed decode executable ladder (1/2/4/.../max_tick_batch, same
  shape discipline as `BucketedEngine`): `decode_dispatch` gathers the
  batch's slots from the arena, runs the model's pure `decode_step_fn`
  one tick, and scatters the surviving state back — compiled ONCE per
  bucket at `warmup()` through `obs.xray.analyze_jit` with the
  graftcache seam, plus ONE slot-reset executable for open(). Zero recompiles after warmup across any open/step/close/evict
  churn — `compile_count` is pinned by tests;
* session lifecycle: `open()` admits (or EVICTS the least-recently
  ticked idle session under slot pressure — `admission='evict_lru'`;
  `admission='shed'` refuses instead), `step(sid, obs)` advances one
  tick, `close(sid)` frees the slot but only after any in-flight
  dispatch that includes the session completes (the join
  discipline: arena state mid-dispatch is an in-flight device op);
* `restore()` hot-swap interplay: params flow through the decode
  bundle's state getter at EVERY dispatch, so a checkpoint hot-swap
  lands mid-episode without touching session state — open sessions keep
  their (old-params) caches and later ticks use the new params, exactly
  the continuous-deployment semantics `BucketedEngine.restore()` has;
* session state NEVER visits the host: outputs are fetched per tick,
  state stays device-resident (the graftlint `session-state-leak` rule
  mechanizes this at decode call sites).

`SessionBatcher` is the continuous-batching front: concurrent per-robot
`step()` calls coalesce into one decode dispatch (MicroBatcher's worker
/ condvar / joining close discipline), with SESSION AFFINITY — a
session appears at most once per dispatch, so two queued ticks of one
episode keep their order.

graftscope telemetry (runs.jsonl via the standard registry snapshot):
  serve/session/active           open sessions (gauge)
  serve/session/slot_occupancy   open / max_sessions (gauge)
  serve/session/tick_ms          per-dispatch wall (host fetch incl.)
  serve/session/cache_bytes      arena bytes resident on device (gauge)
  serve/session/{opens,closes,evictions,shed,ticks,dispatches,
                 padded_lanes,exec_fallbacks}  counters

Backend-free at import like the rest of `serving/` (jax only inside
methods; tests/test_session.py runs the bookkeeping under a poisoned
JAX_PLATFORMS).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import (Any, Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

import numpy as np

from tensor2robot_tpu.obs import graftrace
from tensor2robot_tpu.obs import metrics as obs_metrics
from tensor2robot_tpu.obs import trace as obs_trace
from tensor2robot_tpu.serving import engine as engine_lib
from tensor2robot_tpu.utils import config

__all__ = ["SessionEngine", "SessionBatcher", "SessionError",
           "SessionShedError", "SessionEvictedError",
           "UnknownSessionError", "SessionClosedError",
           "SessionHorizonError"]


class SessionError(RuntimeError):
  """Base of the session-lifecycle error family."""

  def __init__(self, message: str, session_id: Optional[int] = None):
    super().__init__(message)
    self.session_id = session_id


class SessionShedError(SessionError):
  """Admission refused: no free slot and nothing evictable."""


class SessionEvictedError(SessionError):
  """The session's slot was reclaimed under pressure; its next step
  fails with this so the robot re-opens instead of silently continuing
  on another episode's cache."""


class UnknownSessionError(SessionError):
  """step/close on a session id this engine never opened (or already
  closed and forgot)."""


class SessionClosedError(SessionError):
  """step on a session after close()."""


class SessionHorizonError(SessionError):
  """The episode outran the model's decode horizon (KV-cache capacity).
  A tick past it would be an out-of-bounds scatter XLA silently DROPS —
  the cache write vanishes while the attention mask stays all-true, so
  outputs go quietly wrong; this error is the loud alternative."""


def _mask_like(mask, leaf):
  """Broadcasts a [N] lane mask over a [N, ...] state leaf."""
  return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))


def resolve_decode_kernel(requested: Optional[bool],
                          has_arena_fn: bool,
                          backend_is_tpu=None) -> Tuple[bool, str]:
  """The graftkern auto-gate (ISSUE 20), as a pure function: (active,
  reason). `requested` is the engine's `use_decode_kernel` tri-state —
  None auto-selects (on iff the model exposes the fused-arena seam AND
  the process backend is a TPU), True/False force.
  `backend_is_tpu` is a zero-arg thunk so the decision stays
  backend-free on every forced/declined path (the poisoned-platform
  trap pins that): it is invoked ONLY when `requested is None` and
  every other precondition already holds. Auto declines off-TPU
  because there the kernel tier runs the Pallas interpreter — a
  parity/smoke vehicle, not a win; `use_decode_kernel=True` still
  forces it (that is how the CPU tier-1 tests run the real kernel
  body)."""
  if requested is False:
    return False, "disabled (use_decode_kernel=False)"
  if not has_arena_fn:
    return False, ("model-unsupported: the decode bundle has no "
                   "decode_arena_fn (no KV arena layout to stream)")
  if requested is None and not (backend_is_tpu is not None
                                and backend_is_tpu()):
    return False, ("auto-off: non-TPU backend (interpreter-mode kernels "
                   "are a smoke tier, not a win; use_decode_kernel=True "
                   "forces them)")
  return True, "on"


# Terminal session ids (closed / evicted) remembered for precise error
# messages. BOUNDED: a continuous-batching server runs for the
# deployment lifetime, and an unbounded set would accrete one entry per
# episode forever. A forgotten ancient id degrades gracefully to
# UnknownSessionError — the same terminal outcome, less specific text.
_TERMINAL_IDS_CAP = 4096


@config.configurable
class SessionEngine:
  """Stateful session serving over a predictor's decode bundle (module
  docstring). Duck-types the predictor lifecycle surface (`restore` /
  `warmup` / `global_step` / `close`) so policies can hold one."""

  def __init__(self, predictor=None,
               max_sessions: int = 64,
               max_tick_batch: int = 8,
               buckets: Optional[Sequence[int]] = None,
               admission: str = "evict_lru",
               name: str = "serve/session",
               cache=None,
               cache_namespace: Optional[str] = None,
               use_decode_kernel: Optional[bool] = None):
    if predictor is None:
      raise ValueError("predictor is required.")
    if max_sessions < 1:
      raise ValueError(f"max_sessions must be >= 1, got {max_sessions}")
    if admission not in ("evict_lru", "shed"):
      raise ValueError(f"admission must be 'evict_lru' or 'shed', "
                       f"got {admission!r}")
    self._predictor = predictor
    self._max_sessions = max_sessions
    if buckets is not None:
      buckets = sorted(set(int(b) for b in buckets))
      if not buckets or buckets[0] < 1:
        raise ValueError(f"buckets must be positive ints, got {buckets}")
      max_tick_batch = buckets[-1]
    else:
      buckets = engine_lib.bucket_ladder(max_tick_batch)
    if max_tick_batch > max_sessions:
      raise ValueError(
          f"max_tick_batch {max_tick_batch} exceeds max_sessions "
          f"{max_sessions}: a dispatch can never gather that many "
          "distinct live slots")
    self._buckets = buckets
    self._max_tick_batch = max_tick_batch
    self._admission = admission
    self._name = name
    # graftcache namespace: names the analyze_jit records (and the cache
    # KEY prefix) independently of the telemetry `name`, so replicas
    # with per-replica names share one forged entry set (BucketedEngine
    # has the same seam; graftforge relies on it).
    self._cache = cache
    self._cache_namespace = cache_namespace or name
    # graftkern decode-kernel tier (ISSUE 20): tri-state request,
    # resolved ONCE at bundle bind (`resolve_decode_kernel`) and sticky
    # for the engine's lifetime — the bucket ladder is compiled for one
    # dispatch body, a mid-flight flip would recompile it. The
    # native-stager discipline from PR 6 applies: an explicit True the
    # toolchain cannot honor warns once and falls back to the jitted
    # path; auto (None) degrades silently with a counter. Auto turns on
    # only on a TPU backend — off-TPU the kernel runs the Pallas
    # interpreter (parity vehicle, not a win) and must be forced.
    self._use_decode_kernel = use_decode_kernel
    self._decode_kernel_active: Optional[bool] = None
    self._decode_kernel_reason: Optional[str] = None
    # Host bookkeeping (self._lock): slot table + LRU + in-flight set.
    self._lock = threading.Lock()
    self._idle = threading.Condition(self._lock)
    self._slots: Dict[int, int] = {}  # session_id -> arena slot
    self._free: List[int] = list(range(1, max_sessions + 1))  # 0 = null
    self._last_tick: Dict[int, float] = {}
    self._tick_count: Dict[int, int] = {}
    self._in_flight: set = set()
    self._evicted: set = set()
    self._evicted_order: "collections.deque[int]" = collections.deque()
    self._closed_ids: set = set()
    self._closed_order: "collections.deque[int]" = collections.deque()
    self._next_id = itertools.count(1)
    # Device state (self._arena_lock): the arena pytree is DONATED into
    # every decode/reset dispatch and rebound from the result, so every
    # arena touch must serialize — a second dispatch racing the first
    # would hand XLA an already-consumed buffer.
    self._arena_lock = threading.Lock()
    self._arena = None
    self._init_row = None
    self._bundle = None
    self._max_ticks: Optional[int] = None
    self._compiled: Dict[int, Any] = {}
    self._reset_compiled = None
    self._reset_jit = None
    self._dispatch_jits: Dict[int, Any] = {}
    self._records: Dict[str, Dict[str, Any]] = {}
    self._compile_count = 0
    self._cache_loads = 0
    self._warmup_ms: Optional[float] = None
    self._warmup_load_ms = 0.0
    self._warmup_compile_ms = 0.0
    self._warmup_provenance: List[Dict[str, Any]] = []

  # -- warmup ---------------------------------------------------------------

  @property
  def buckets(self) -> List[int]:
    return list(self._buckets)

  @property
  def max_sessions(self) -> int:
    return self._max_sessions

  @property
  def compile_count(self) -> int:
    """FRESH compiles paid by this process: len(buckets) + 1 (the slot
    reset executable) after an uncached warmup, 0 on a fully warm
    graftcache start — and PINNED there across session churn (the
    zero-recompile acceptance, tests/test_session.py)."""
    return self._compile_count

  @property
  def cache_loads(self) -> int:
    return self._cache_loads

  @property
  def warmup_ms(self) -> Optional[float]:
    return self._warmup_ms

  @property
  def warmup_load_ms(self) -> float:
    """Warmup wall spent deserializing graftcache hits (the
    BucketedEngine split contract — see engine.py)."""
    return self._warmup_load_ms

  @property
  def warmup_compile_ms(self) -> float:
    """Warmup wall spent on fresh trace+lower+compile."""
    return self._warmup_compile_ms

  @property
  def warmup_provenance(self) -> List[Dict[str, Any]]:
    """Per-rung provenance `{rung, source, ms, key}` (rung = decode
    bucket int or 'reset'); source in cache/compile/fallback."""
    return [dict(p) for p in self._warmup_provenance]

  @property
  def compile_records(self) -> List[Dict[str, Any]]:
    return [dict(r) for r in self._records.values()]

  @property
  def active_sessions(self) -> int:
    with self._lock:
      return len(self._slots)

  @property
  def cache_bytes(self) -> int:
    """Device bytes held by the session arena (shape/dtype metadata
    only — never fetches state values to host)."""
    from tensor2robot_tpu.obs import xray as obs_xray

    return int(obs_xray.pytree_bytes(self._arena))

  @property
  def decode_kernel_active(self) -> Optional[bool]:
    """True/False once the graftkern gate is resolved (at bundle bind);
    None on a cold engine that has not bound its bundle yet."""
    return self._decode_kernel_active

  @property
  def decode_kernel_reason(self) -> Optional[str]:
    """Why the gate resolved the way it did ('on' when active)."""
    return self._decode_kernel_reason

  def decode_kernel_mode(self) -> Tuple[bool, str]:
    """Binds the decode bundle and resolves (and pins) the graftkern
    gate WITHOUT building any device state — backend-free when the
    predictor's bundle is (the poisoned-platform trap runs this)."""
    with self._arena_lock:
      if self._bundle is None:
        self._bundle = self._predictor.decode_bundle()
        self._max_ticks = getattr(self._bundle, "max_ticks", None)
      self._resolve_decode_kernel_locked()
      return bool(self._decode_kernel_active), self._decode_kernel_reason

  def _resolve_decode_kernel_locked(self) -> None:
    """Resolves `use_decode_kernel` against the bound bundle (caller
    holds _arena_lock). Sticky: later restores/warmups keep the first
    resolution — the compiled bucket ladder embodies it."""
    if self._decode_kernel_active is not None:
      return
    def _backend_is_tpu():
      # Thunked: only the fully-eligible auto path ever touches the
      # backend (forced/declined resolutions stay backend-free, which
      # the poisoned-platform trap pins).
      import jax

      return jax.default_backend() == "tpu"

    active, reason = resolve_decode_kernel(
        self._use_decode_kernel,
        getattr(self._bundle, "decode_arena_fn", None) is not None,
        backend_is_tpu=_backend_is_tpu)
    self._decode_kernel_active = active
    self._decode_kernel_reason = reason
    obs_metrics.gauge("serve/session/decode_kernel").set(float(active))
    if not active and self._use_decode_kernel is not False:
      # The auto-gate (or a forced request) declined the kernel tier:
      # count every degrade; WARN only for the explicit request (the
      # use_native_stager discipline — auto stays silent).
      obs_metrics.counter("serve/session/decode_kernel_off").inc()
      if self._use_decode_kernel is True:
        from absl import logging

        logging.warning(
            "%s: use_decode_kernel=True cannot be honored (%s); "
            "falling back to the jitted decode path.", self._name, reason)

  def _make_dispatch(self, bundle):
    """The bucketed decode executable body. Kernel tier OFF: masked
    gather -> one decode tick -> masked scatter (pad lanes ride the
    null slot (0) with mask=False, so their writes land masked-out old
    values on a slot no session owns). Kernel tier ON: the bundle's
    fused-arena step (`decode_arena_fn`) consumes the arena directly —
    the gather/scatter of the KV leaves happens INSIDE the Pallas
    launch (slot-steered block maps + in-place append), with the same
    (state, arena, slots, features, mask) -> (new_arena, outputs)
    signature, so both tiers share one warmup/caching/fallback path
    and graftforge forges identical keys for whichever is active."""
    import jax
    import jax.numpy as jnp

    if self._decode_kernel_active:
      arena_fn = bundle.decode_arena_fn

      def decode_dispatch(state, arena, slots, features, mask):
        return arena_fn(state, arena, slots, features, mask)

      return jax.jit(decode_dispatch, donate_argnums=(1,))

    decode_fn = bundle.decode_fn

    def decode_dispatch(state, arena, slots, features, mask):
      gathered = jax.tree_util.tree_map(lambda a: a[slots], arena)
      new_state, outputs = decode_fn(state, gathered, features)
      new_arena = jax.tree_util.tree_map(
          lambda a, new, old: a.at[slots].set(
              jnp.where(_mask_like(mask, new), new, old)),
          arena, new_state, gathered)
      return new_arena, outputs

    return jax.jit(decode_dispatch, donate_argnums=(1,))

  def _make_reset(self):
    """One-slot re-init executable (open() reuses freed slots): writes
    the bundle's init row at a scalar slot index. Compiled once at
    warmup — slot churn must never compile."""
    import jax

    def reset_slot(arena, slot, init_row):
      return jax.tree_util.tree_map(
          lambda a, row: a.at[slot].set(row[0]), arena, init_row)

    return jax.jit(reset_slot, donate_argnums=(0,))

  def warmup(self) -> "SessionEngine":
    """Builds the arena and AOT-compiles the decode bucket ladder + the
    slot-reset executable through graftscope-xray (graftcache-seamed).
    Idempotent; a later `restore()` does NOT require re-warming (params
    flow through the bundle's state getter at dispatch time)."""
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu import specs as specs_lib
    from tensor2robot_tpu.obs import excache as excache_lib
    from tensor2robot_tpu.obs import xray as obs_xray

    with self._arena_lock:
      if self._bundle is None:
        self._bundle = self._predictor.decode_bundle()
        self._max_ticks = getattr(self._bundle, "max_ticks", None)
      self._resolve_decode_kernel_locked()
      bundle = self._bundle
      if self._arena is not None and self._compiled:
        return self
      cache = excache_lib.as_cache(self._cache)
      warmup_start = time.perf_counter()
      host_arena = bundle.init_session_state(self._max_sessions + 1)
      self._arena = jax.tree_util.tree_map(jnp.asarray, host_arena)
      self._init_row = jax.tree_util.tree_map(
          jnp.asarray, bundle.init_session_state(1))
      obs_metrics.gauge("serve/session/cache_bytes").set(
          float(self.cache_bytes))
      state = bundle.get_state()
      for bucket in self._buckets:
        if bucket in self._compiled:
          continue
        fn = self._dispatch_jits.setdefault(
            bucket, self._make_dispatch(bundle))
        wire = specs_lib.make_random_numpy(bundle.observation_spec,
                                           batch_size=bucket, seed=0)
        features = {k: np.asarray(v) for k, v in dict(wire).items()}
        slots = np.zeros((bucket,), np.int32)  # null slot: warmup-safe
        mask = np.zeros((bucket,), bool)
        rec_name = f"{self._cache_namespace}/decode{bucket}"
        self._compile_one(rec_name, bucket, fn, cache,
                          (state, self._arena, slots, features, mask),
                          obs_xray)
      if self._reset_compiled is None and self._reset_jit is None:
        self._reset_jit = self._make_reset()
        rec_name = f"{self._cache_namespace}/reset_slot"
        self._compile_one(rec_name, "reset", self._reset_jit, cache,
                          (self._arena, np.int32(0), self._init_row),
                          obs_xray)
      self._warmup_ms = (time.perf_counter() - warmup_start) * 1e3
      obs_metrics.gauge("serve/session/warmup_ms").set(self._warmup_ms)
      obs_metrics.gauge("serve/session/warmup_load_ms").set(
          self._warmup_load_ms)
      obs_metrics.gauge("serve/session/warmup_compile_ms").set(
          self._warmup_compile_ms)
    return self

  def _compile_one(self, rec_name: str, key, fn, cache, args,
                   obs_xray) -> None:
    """analyze_jit one executable with the engine's counting + honest
    AOT-less degrade (the BucketedEngine warmup contract). NOTE: the
    warmup args include the live arena, which the jitted fns DONATE —
    analyze_jit only traces/lowers/compiles (never executes), so the
    arena buffer survives; the no-AOT fallback dispatches for real and
    must rebind the donated-in arena from the result."""
    start = time.perf_counter()
    source = "compile"
    try:
      compiled, record = obs_xray.analyze_jit(rec_name, fn, *args,
                                              cache=cache)
    except Exception as e:  # noqa: BLE001 - AOT-less backends
      out = fn(*args)
      # Donated args consumed by the real dispatch: rebind the arena.
      if key == "reset":
        self._arena = out
      else:
        self._arena = out[0]
      compiled = None
      source = "fallback"
      record = {"name": rec_name,
                "compile_s": time.perf_counter() - start,
                "error": f"{type(e).__name__}: {e}"}
    elapsed_ms = (time.perf_counter() - start) * 1e3
    if key == "reset":
      self._reset_compiled = compiled
    else:
      self._compiled[key] = compiled
    self._records[rec_name] = record
    cache_block = record.get("cache") or {}
    if cache_block.get("hit"):
      source = "cache"
      self._cache_loads += 1
      self._warmup_load_ms += elapsed_ms
      obs_metrics.counter("serve/session/cache_loads").inc()
    else:
      self._compile_count += 1
      self._warmup_compile_ms += elapsed_ms
      obs_metrics.counter("serve/session/compiles").inc()
    self._warmup_provenance.append(
        {"rung": key, "source": source, "ms": elapsed_ms,
         "key": cache_block.get("key")})

  def rung_traces(self) -> List[Tuple[Any, Any, Tuple]]:
    """`[(rung, traced, args), ...]` for every decode rung plus the
    `"reset"` slot-reset — trace-only, never a lower or compile (the
    BucketedEngine.rung_traces contract; shared by `rung_cache_keys`
    and `graftscope audit`). Binds the decode bundle exactly as warmup
    would (the dispatch jits in `_dispatch_jits` close over its
    decode_fn, and a later warmup reuses them — they must share ONE
    bundle) but builds only a LOCAL throwaway arena for the trace
    avals, so probing a cold engine allocates no resident device
    state."""
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu import specs as specs_lib

    with self._arena_lock:
      if self._bundle is None:
        self._bundle = self._predictor.decode_bundle()
        self._max_ticks = getattr(self._bundle, "max_ticks", None)
      self._resolve_decode_kernel_locked()
      bundle = self._bundle
      arena = self._arena
      init_row = self._init_row
      if arena is None:
        arena = jax.tree_util.tree_map(
            jnp.asarray, bundle.init_session_state(self._max_sessions + 1))
        init_row = jax.tree_util.tree_map(
            jnp.asarray, bundle.init_session_state(1))
      state = bundle.get_state()
      traces: List[Tuple[Any, Any, Tuple]] = []
      for bucket in self._buckets:
        fn = self._dispatch_jits.setdefault(
            bucket, self._make_dispatch(bundle))
        wire = specs_lib.make_random_numpy(bundle.observation_spec,
                                           batch_size=bucket, seed=0)
        features = {k: np.asarray(v) for k, v in dict(wire).items()}
        slots = np.zeros((bucket,), np.int32)
        mask = np.zeros((bucket,), bool)
        args = (state, arena, slots, features, mask)
        traces.append((bucket, fn.trace(*args), args))
      reset_fn = self._reset_jit or self._make_reset()
      args = (arena, np.int32(0), init_row)
      traces.append(("reset", reset_fn.trace(*args), args))
      return traces

  def rung_cache_keys(self) -> Dict[Any, str]:
    """The graftcache key of every decode rung + the slot reset WITHOUT
    compiling (trace-only via `rung_traces`; the graftforge --verify
    seam — the BucketedEngine.rung_cache_keys contract)."""
    from tensor2robot_tpu.obs import excache as excache_lib

    return {
        rung: excache_lib.cache_key(
            f"{self._cache_namespace}/"
            f"{'reset_slot' if rung == 'reset' else f'decode{rung}'}",
            **excache_lib.key_components_from_traced(traced, args))
        for rung, traced, args in self.rung_traces()}

  # -- lifecycle ------------------------------------------------------------

  def open(self) -> int:
    """Admits a new session; returns its id. Under slot pressure either
    evicts the least-recently-ticked idle session (`evict_lru`) or
    refuses (`shed`) — an in-flight session is never evicted."""
    if self._arena is None:
      self.warmup()
    with self._lock:
      if not self._free:
        victim = (self._pick_victim_locked()
                  if self._admission == "evict_lru" else None)
        if victim is None:
          obs_metrics.counter("serve/session/shed").inc()
          raise SessionShedError(
              f"all {self._max_sessions} slots are held"
              + (" and nothing is evictable" if self._admission
                 == "evict_lru" else " (admission='shed')")
              + "; shedding the open()")
        self._evict_locked(victim)
      slot = self._free.pop()
      sid = next(self._next_id)
      self._slots[sid] = slot
      self._last_tick[sid] = time.monotonic()
      self._tick_count[sid] = 0
      # In-flight until the slot reset lands: a concurrent open() under
      # pressure must not evict this brand-new (idle-looking) session
      # and reuse its slot — a stale reset would then clobber the new
      # owner's live state.
      self._in_flight.add(sid)
      obs_metrics.counter("serve/session/opens").inc()
      self._occupancy_locked()
    try:
      with self._arena_lock:
        self._reset_slot(slot)
    except BaseException:
      # A failed reset must not strand a ghost session: the caller
      # never receives the sid, so nothing would ever close it — under
      # admission='shed' max_sessions such ghosts would shed every
      # later open() forever, and the slot still holds the evicted
      # predecessor's stale state.
      with self._lock:
        if self._slots.get(sid) == slot:
          self._slots.pop(sid)
          self._free.append(slot)
          self._last_tick.pop(sid, None)
          self._tick_count.pop(sid, None)
          self._occupancy_locked()
      raise
    finally:
      with self._idle:
        self._in_flight.discard(sid)
        self._idle.notify_all()
    return sid

  def _pick_victim_locked(self) -> Optional[int]:
    candidates = [sid for sid in self._slots if sid not in self._in_flight]
    if not candidates:
      return None
    return min(candidates, key=lambda sid: self._last_tick[sid])

  @staticmethod
  def _remember_terminal(ids: set, order: "collections.deque[int]",
                         sid: int) -> None:
    ids.add(sid)
    order.append(sid)
    while len(order) > _TERMINAL_IDS_CAP:
      ids.discard(order.popleft())

  def _evict_locked(self, sid: int) -> None:
    slot = self._slots.pop(sid)
    self._free.append(slot)
    self._remember_terminal(self._evicted, self._evicted_order, sid)
    self._last_tick.pop(sid, None)
    self._tick_count.pop(sid, None)
    obs_metrics.counter("serve/session/evictions").inc()

  def _occupancy_locked(self) -> None:
    obs_metrics.gauge("serve/session/active").set(float(len(self._slots)))
    obs_metrics.gauge("serve/session/slot_occupancy").set(
        len(self._slots) / self._max_sessions)

  def _reset_slot(self, slot: int) -> None:
    """Re-initializes one arena slot (caller holds _arena_lock)."""
    args = (self._arena, np.int32(slot), self._init_row)
    if self._reset_compiled is not None:
      try:
        self._arena = self._reset_compiled(*args)
        return
      except Exception:  # noqa: BLE001 - degrade, never break serving
        if self._arena_deleted():
          raise
        obs_metrics.counter("serve/session/exec_fallbacks").inc()
    self._arena = self._reset_jit(*args)

  def _arena_deleted(self) -> bool:
    """True when a failed dispatch already consumed the donated arena —
    retrying would mask the real error behind 'Array has been deleted'
    (the XrayedFunction donation discipline)."""
    import jax

    return any(getattr(leaf, "is_deleted", lambda: False)()
               for leaf in jax.tree_util.tree_leaves(self._arena))

  def close_session(self, session_id: int) -> None:
    """Frees the session's slot — AFTER any dispatch that includes it
    completes (in-flight arena state is an in-flight device op: it is
    waited out, never abandoned)."""
    with self._idle:
      while session_id in self._in_flight:
        self._idle.wait(timeout=0.1)
      if session_id in self._evicted:
        self._evicted.discard(session_id)
        return
      if session_id in self._closed_ids:
        return
      if session_id not in self._slots:
        raise UnknownSessionError(f"unknown session {session_id}",
                                  session_id)
      slot = self._slots.pop(session_id)
      self._free.append(slot)
      self._remember_terminal(self._closed_ids, self._closed_order,
                              session_id)
      self._last_tick.pop(session_id, None)
      self._tick_count.pop(session_id, None)
      obs_metrics.counter("serve/session/closes").inc()
      self._occupancy_locked()

  def session_ticks(self, session_id: int) -> int:
    with self._lock:
      if session_id not in self._tick_count:
        raise UnknownSessionError(f"unknown session {session_id}",
                                  session_id)
      return self._tick_count[session_id]

  # -- decode ---------------------------------------------------------------

  def _check_sid_locked(self, sid: int) -> None:
    if sid in self._evicted:
      raise SessionEvictedError(
          f"session {sid} was evicted under slot pressure; re-open and "
          "replay or restart the episode", sid)
    if sid in self._closed_ids:
      raise SessionClosedError(f"session {sid} is closed", sid)
    if sid not in self._slots:
      raise UnknownSessionError(f"unknown session {sid}", sid)

  def step(self, session_id: int, features: Mapping[str, Any]
           ) -> Dict[str, np.ndarray]:
    """Advances ONE session one tick; returns its per-tick outputs."""
    return self.step_many([(session_id, features)])[0]

  def step_many(self, items: Sequence[Tuple[int, Mapping[str, Any]]]
                ) -> List[Dict[str, np.ndarray]]:
    """Advances several DISTINCT sessions one tick in one dispatch.

    Items must name distinct sessions (the batcher's affinity rule —
    one episode's queued ticks must serialize) and at most
    `max_tick_batch` of them. Raises the per-session lifecycle errors
    before any device work; a mid-dispatch failure re-raises to every
    caller with the arena intact (pre-execution rejections fall back to
    the plain jit, counted).
    """
    if not items:
      return []
    if len(items) > self._max_tick_batch:
      raise ValueError(f"{len(items)} session steps exceed "
                       f"max_tick_batch {self._max_tick_batch}")
    sids = [sid for sid, _ in items]
    if len(set(sids)) != len(sids):
      raise ValueError("step_many items must name distinct sessions "
                       "(queued ticks of one session serialize)")
    if self._arena is None:
      self.warmup()
    start = time.perf_counter()
    with self._lock:
      for sid in sids:
        self._check_sid_locked(sid)
        if (self._max_ticks is not None
            and self._tick_count[sid] >= self._max_ticks):
          raise SessionHorizonError(
              f"session {sid} has run {self._tick_count[sid]} ticks — "
              f"the model's decode horizon (KV capacity) is "
              f"{self._max_ticks}; close and re-open the episode", sid)
        if sid in self._in_flight:
          # One dispatch per session at a time — a second concurrent
          # tick would race the first's arena scatter AND let
          # close_session free the slot while this dispatch still
          # includes it (the in-flight set is membership, not a
          # count). The SessionBatcher's affinity rule means it never
          # trips this; direct engine users must serialize per sid.
          raise SessionError(
              f"session {sid} already has a step in flight; an "
              "episode's ticks must serialize (use SessionBatcher for "
              "concurrent callers)", sid)
      slots = [self._slots[sid] for sid in sids]
      self._in_flight.update(sids)
    ticked = False
    try:
      n = len(items)
      bucket = self._bucket_for(n)
      if bucket != n:
        obs_metrics.counter("serve/session/padded_lanes").inc(bucket - n)
      slot_arr = np.zeros((bucket,), np.int32)
      slot_arr[:n] = slots
      mask = np.zeros((bucket,), bool)
      mask[:n] = True
      features = self._stack_features([f for _, f in items], bucket)
      bundle = self._bundle
      state = bundle.get_state()
      with self._arena_lock, \
          obs_trace.span("serve/session/dispatch", cat="serve",
                         sessions=n, bucket=bucket):
        # Same arg classes warmup compiled with (numpy hosts for
        # slots/mask/features): the frozen executables see one layout.
        args = (state, self._arena, slot_arr, features, mask)
        compiled = self._compiled.get(bucket)
        if compiled is not None:
          try:
            self._arena, outputs = compiled(*args)
          except Exception:  # noqa: BLE001 - never break serving on cache
            if self._arena_deleted():
              raise
            obs_metrics.counter("serve/session/exec_fallbacks").inc()
            fn = self._dispatch_jits.setdefault(
                bucket, self._make_dispatch(bundle))
            self._arena, outputs = fn(*args)
        else:
          fn = self._dispatch_jits.setdefault(
              bucket, self._make_dispatch(bundle))
          self._arena, outputs = fn(*args)
        # The arena rebind IS the tick: from here the sessions' device
        # state (KV rows, index leaves) has advanced, so the host
        # bookkeeping must advance with it even if the fetch below
        # fails — device errors surface at fetch time, and counting a fetch-failed tick as "not
        # ticked" would desync tick_count from the arena index: a
        # retry would double-append the observation and the horizon
        # guard would under-count straight into the silently-dropped
        # out-of-bounds scatter it exists to prevent. A fetch failure
        # costs that tick's OUTPUTS, never the state's coherence.
        ticked = True
        # Host-fetch OUTPUTS only (the np.asarray IS the barrier);
        # session state stays device-resident — fetching it
        # here is exactly what the session-state-leak lint rule flags.
        fetched = {k: np.asarray(v) for k, v in dict(outputs).items()}
      results: List[Dict[str, np.ndarray]] = []
      for i in range(n):
        results.append({
            k: v[i] if getattr(v, "ndim", 0) and v.shape[0] == bucket
            else v for k, v in fetched.items()})
      return results
    finally:
      now = time.monotonic()
      with self._idle:
        for sid in sids:
          self._in_flight.discard(sid)
          if ticked and sid in self._tick_count:
            self._last_tick[sid] = now
            self._tick_count[sid] += 1
        self._idle.notify_all()
      if ticked:
        obs_metrics.histogram("serve/session/tick_ms").record(
            (time.perf_counter() - start) * 1e3)
        obs_metrics.counter("serve/session/ticks").inc(len(items))
        obs_metrics.counter("serve/session/dispatches").inc()

  def _bucket_for(self, rows: int) -> int:
    for bucket in self._buckets:
      if bucket >= rows:
        return bucket
    raise AssertionError(f"no bucket covers {rows} rows")  # guarded above

  def _stack_features(self, feature_dicts: List[Mapping[str, Any]],
                      bucket: int) -> Dict[str, np.ndarray]:
    """[B=bucket] feature stack; pad lanes repeat row 0 (in-distribution
    values — their outputs are dropped and their state writes masked)."""
    keys = list(dict(feature_dicts[0]))
    out = {}
    for key in keys:
      rows = [np.asarray(dict(f)[key]) for f in feature_dicts]
      stack = np.stack(rows, axis=0)
      if bucket != len(rows):
        pad = np.broadcast_to(stack[:1],
                              (bucket - len(rows),) + stack.shape[1:])
        stack = np.concatenate([stack, pad], axis=0)
      out[key] = stack
    return out

  # -- predictor duck-type passthroughs -------------------------------------

  def restore(self) -> bool:
    """Hot-swaps params under live sessions: the decode bundle is
    re-bound so a swapped-in model object is picked up, but the ARENA is
    untouched — open sessions keep their decode state and the next tick
    simply runs under the new params (continuous deployment, the
    `BucketedEngine.restore()` semantics)."""
    ok = self._predictor.restore()
    if ok and self._bundle is not None:
      with self._arena_lock:
        self._bundle = self._predictor.decode_bundle()
        self._max_ticks = getattr(self._bundle, "max_ticks", None)
    return ok

  @property
  def global_step(self) -> int:
    return self._predictor.global_step

  @property
  def model_version(self) -> int:
    return self.global_step

  def close(self) -> None:
    self._predictor.close()


class SessionBatcher:
  """Continuous-batching front of a `SessionEngine`: concurrent
  per-robot `step(session_id, obs)` calls coalesce into `step_many`
  dispatches, with session AFFINITY — a session appears at most once
  per dispatch, so one episode's queued ticks keep their order while
  other episodes fill the batch around them.

  Lifecycle calls (`open`/`close_session`/`restore`) pass through to
  the engine; `close()` JOINS the worker with the MicroBatcher's
  discipline (a dispatch-phase worker is waited out
  unconditionally) and fails still-queued ticks with `ShutdownError`.
  """

  def __init__(self, engine: Optional[SessionEngine] = None,
               max_delay_ms: float = 2.0,
               max_queue: int = 256,
               usage: Optional[Callable[[float, int], None]] = None):
    from tensor2robot_tpu.serving import batcher as batcher_lib

    if engine is None:
      raise ValueError("engine is required.")
    self._engine = engine
    self._max_delay_s = max_delay_ms / 1e3
    self._max_queue = max_queue
    # Device-time ledger hook (same `(busy_s, requests)` contract as
    # `MicroBatcher`): one call per step_many dispatch window.
    self._usage = usage
    self._shutdown_error = batcher_lib.ShutdownError
    self._shed_error = batcher_lib.ShedError
    self._pending: "collections.deque" = collections.deque()
    self._lock = threading.Lock()
    self._have_work = threading.Condition(self._lock)
    self._closed = False
    self._phase = ["idle"]
    self._worker = threading.Thread(target=self._run, daemon=True,
                                    name="graftserve-session-batcher")
    self._worker.start()

  # -- client side ----------------------------------------------------------

  def open(self) -> int:
    return self._engine.open()

  def close_session(self, session_id: int) -> None:
    self._engine.close_session(session_id)

  def step(self, session_id: int, features: Mapping[str, Any]
           ) -> Dict[str, np.ndarray]:
    request = _TickRequest(session_id, dict(features),
                           ctx=graftrace.request_context())
    with self._have_work:
      if self._closed:
        raise self._shutdown_error("session batcher is closed")
      if len(self._pending) >= self._max_queue:
        obs_metrics.counter("serve/session/shed_queue_full").inc()
        raise self._shed_error(
            f"session tick queue full ({self._max_queue} pending)")
      was_empty = not self._pending
      self._pending.append(request)
      if was_empty:
        self._have_work.notify()
    request.event.wait()
    if request.error is not None:
      raise request.error
    return request.result

  # -- worker side ----------------------------------------------------------

  def _gather(self) -> Optional[List["_TickRequest"]]:
    """Next affinity-respecting batch: up to the engine's
    max_tick_batch DISTINCT sessions, flushed `max_delay_s` after the
    oldest pending tick. A second tick of a session already in the
    batch stays queued for the next dispatch."""
    with self._have_work:
      while not self._pending:
        if self._closed:
          return None
        self._phase[0] = "idle"
        self._have_work.wait(timeout=0.1)
      if self._closed:
        return None
      self._phase[0] = "gather"
      flush_at = self._pending[0].enqueued_s + self._max_delay_s
      limit = self._engine._max_tick_batch
      while (len(self._pending) < limit and not self._closed):
        remaining = flush_at - time.monotonic()
        if remaining <= 0:
          break
        self._have_work.wait(timeout=remaining)
      if self._closed:
        return None
      batch: List[_TickRequest] = []
      seen: set = set()
      kept: List[_TickRequest] = []
      while self._pending and len(batch) < limit:
        request = self._pending.popleft()
        if request.session_id in seen:
          kept.append(request)  # affinity: serialize same-session ticks
          continue
        seen.add(request.session_id)
        request.pop_ns = time.perf_counter_ns()
        batch.append(request)
      for request in reversed(kept):
        self._pending.appendleft(request)
      return batch

  def _serve_batch(self, batch: List["_TickRequest"]) -> None:
    self._phase[0] = "dispatch"
    try:
      items = [(r.session_id, r.features) for r in batch]
      dispatch_ns = time.perf_counter_ns()
      batch_ctx = graftrace.mint()
      try:
        with graftrace.activate(batch_ctx):
          with obs_trace.span(
              "serve/session/batch", cat="serve", ticks=len(batch),
              links=[r.ctx.span_id for r in batch if r.ctx is not None]):
            results = self._engine.step_many(items)
      except SessionError as e:
        # A lifecycle error names ONE session: fail that tick, retry
        # the rest once as a batch (they were validated together, but a
        # racing evict/close can invalidate any of them).
        bad = [r for r in batch if r.session_id == e.session_id]
        rest = [r for r in batch if r.session_id != e.session_id]
        if not bad:
          raise
        for request in bad:
          request.complete(error=e)
        if rest:
          self._serve_batch(rest)
        return
      end_ns = time.perf_counter_ns()
      graftrace.record_stage_many(
          "queue_wait",
          [(r.pop_ns - r.enq_ns) / 1e6 for r in batch if r.pop_ns])
      graftrace.record_stage_many(
          "dispatch", [(end_ns - dispatch_ns) / 1e6] * len(batch))
      if self._usage is not None:
        self._usage((end_ns - dispatch_ns) / 1e9, len(batch))
      if obs_trace.get_tracer().enabled:
        for r in batch:
          if r.ctx is None:
            continue
          if r.pop_ns:
            obs_trace.add_complete(
                "serve/stage/queue_wait", r.enq_ns, r.pop_ns - r.enq_ns,
                cat="serve", args=r.ctx.args())
          obs_trace.add_complete(
              "serve/stage/dispatch", dispatch_ns, end_ns - dispatch_ns,
              cat="serve", args=r.ctx.args())
      for request, result in zip(batch, results):
        request.complete(result=result)
    finally:
      self._phase[0] = "gather"

  def _run(self) -> None:
    try:
      while True:
        batch = self._gather()
        if batch is None:
          return
        if not batch:
          continue
        try:
          self._serve_batch(batch)
        except BaseException as e:  # noqa: BLE001 - fan out to callers
          for request in batch:
            if not request.event.is_set():
              request.complete(error=e)
    finally:
      self._phase[0] = "done"
      with self._have_work:
        self._closed = True
        pending = list(self._pending)
        self._pending.clear()
      for request in pending:
        request.complete(
            error=self._shutdown_error("session batcher worker exited"))
      graftrace.flush()

  # -- lifecycle ------------------------------------------------------------

  def restore(self) -> bool:
    return self._engine.restore()

  def warmup(self) -> None:
    self._engine.warmup()

  @property
  def global_step(self) -> int:
    return self._engine.global_step

  def close(self, timeout: float = 60.0) -> None:
    """Stops and JOINS the worker (the MicroBatcher close contract: a
    mid-dispatch worker is an in-flight device op — waited out
    unconditionally; any other phase observes the close flag within
    0.1 s)."""
    with self._have_work:
      if self._closed and not self._worker.is_alive():
        return
      self._closed = True
      self._have_work.notify_all()
    deadline = None
    while True:
      self._worker.join(timeout=1.0)
      if not self._worker.is_alive():
        return
      if self._phase[0] == "dispatch":
        deadline = None
        continue
      if deadline is None:
        deadline = time.monotonic() + timeout
      elif time.monotonic() >= deadline:
        break
    from absl import logging

    logging.error(
        "SessionBatcher.close(): worker still alive after %.0fs in "
        "phase %r; abandoning the daemon thread.", timeout,
        self._phase[0])

  def __enter__(self) -> "SessionBatcher":
    return self

  def __exit__(self, exc_type, exc_value, traceback) -> bool:
    self.close()
    return False


class _TickRequest:
  """One queued session tick: features, result slot, completion event."""

  __slots__ = ("session_id", "features", "enqueued_s", "event", "result",
               "error", "ctx", "enq_ns", "pop_ns")

  def __init__(self, session_id: int, features: Dict[str, Any],
               ctx=None):
    self.session_id = session_id
    self.features = features
    self.enqueued_s = time.monotonic()
    self.event = threading.Event()
    self.result: Optional[Dict[str, np.ndarray]] = None
    self.error: Optional[BaseException] = None
    self.ctx = ctx
    self.enq_ns = time.perf_counter_ns()
    self.pop_ns = 0

  def complete(self, result=None, error=None) -> None:
    self.result = result
    self.error = error
    self.event.set()
