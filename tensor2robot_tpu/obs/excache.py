"""graftcache: persistent on-disk executable/AOT cache (compile once,
serve many — across PROCESSES).

Compile time is the measured tax everywhere in this system: the round-5
compile valley (PERFORMANCE.md), `BucketedEngine.warmup()` compiling
every bucket on every serving cold start, and every trainer restart re-paying
the train-step compile it already paid yesterday. The reference never solved this either — TF
sessions re-specialize per feed shape behind an opaque boundary
(/root/reference/predictors/exported_savedmodel_predictor.py:53-359);
its closest artifact is the SavedModel exported once and loaded by many
robots. graftcache is that artifact for compiled XLA executables
(PAPERS.md: "Automatic Full Compilation ... to Cloud TPUs" and
"Compiler-First ... Portable O(1) Autoregressive Caching" both argue the
compile-once/serve-many shape; this module makes it persistent).

Two tiers:

* **Serialized AOT executables** — `jax.experimental.serialize_executable`
  round-trips of the very executables `obs.xray.analyze_jit` already
  produces. Content-addressed on disk under a key that fingerprints
  EVERYTHING that could invalidate an executable: the jaxpr (which bakes
  in static_argnums values), abstract arg shapes/dtypes + pytree
  structure + input shardings, the declared donation layout, the device
  topology, and the jax/jaxlib/backend version. A warm process pays one
  deserialize instead of one compile (the Grasping44 b256 train step
  on the v5e: 0.7 s against 20 s, my chip run, PR 22).
* **The XLA compilation cache** (`jax_compilation_cache_dir`) as the
  backstop for plain-jit paths that never route through `analyze_jit`
  (`enable_xla_cache`): those still re-trace, but XLA's own persistent
  cache absorbs the backend compile.

Layout: one `<key>.json` metadata sidecar (strict JSON: name, key
components, byte sizes, sha256 of the blob, the cold process's xray
record) + one `<key>.bin` pickle blob (serialized executable + in/out
tree defs) per entry, and beside them, where xray built one, the
executable's op table as `<key>.ops` (JSON; `load_op_scopes`). The
sidecar is everything the backend-free readers (`graftscope cache`
list/verify/evict, `entries`, `verify`) need — only `load`/`store`
touch jax.

Contracts, same as the rest of `obs/`:

* telemetry/caching must never take down the run — a stale, corrupt, or
  version-skewed entry falls back to a fresh compile with a
  `cache/corrupt_entries` counter bump (the entry is quarantined), and
  `store` failures are counted, never raised;
* backend-free at import AND at key computation: `cache_key` is pure
  stdlib over pre-computed component strings (tests/test_excache.py
  proves import + key-compute under a poisoned JAX_PLATFORMS); jax is
  imported only inside `load`/`store`/fingerprint helpers, which run
  where the backend is already up;
* every hit/miss/load lands in the metrics registry
  (`cache/{hits,misses,load_ms,bytes,...}`) and from there in the
  runs.jsonl record, so `graftscope diff` gates cold-start time like any
  other headline metric.

graftlint enforces the key discipline statically: a `cache_key(...)`
call site that omits the mesh/dtype/backend-version components is a
finding (`analysis/cache_check.py`), so a future caller cannot silently
build an under-keyed cache.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import re
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from tensor2robot_tpu.obs import metrics as metrics_lib

__all__ = ["CACHE_VERSION", "cache_key", "key_components_from_traced",
           "jaxpr_fingerprint", "pallas_fingerprint", "mesh_fingerprint",
           "backend_fingerprint", "cache_root", "xla_cache_dir",
           "ExecutableCache", "as_cache",
           "enable_xla_cache", "xla_cache_bypassed", "cache_stats"]

# Bumped whenever the entry format (blob layout, meta schema, key
# recipe) changes — part of every key, so an old-format entry can never
# be deserialized by a new reader; it just misses and gets recompiled.
# v2: the key grew the `pallas` component (ISSUE 20 — kernel-revision
# invalidation for Pallas/Mosaic lowerings).
# v3: the sidecar records the executable's device ids (`device_ids`),
# which `load` hands to `deserialize_and_load(execution_devices=...)`.
# v4: the train step and loop were renamed (`t2r_train_step`,
# `t2r_train_loop_k<k>`). The key does not hold a function's name, so
# an executable stored under the old name would come back as
# `jit_step_fn` in a profiler trace: every older entry is retired once.
# v5: an entry carries the executable's op table beside it (`<key>.ops`,
# written by `store` only). An older entry has none and misses once, so
# no reader ever pairs a table with an executable it was not read from.
CACHE_VERSION = 5

# Where both cache tiers live (ISSUE 22 §4). `JAX_COMPILATION_CACHE_DIR`
# places them from outside: jax's own persistent cache is then that
# directory and no code here points it anywhere else. Unset, both go to
# ONE fixed path inside the checkout (gitignored) — for trainer and
# servers alike: the path is part of jax's cache key, so a directory
# that moves (a temporary model_dir, a pid, a timestamp) never hits.
_PLACED_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".graftcache")


def cache_root() -> str:
  """The serialized-AOT tier's directory: `<placed>/graftcache` when
  `JAX_COMPILATION_CACHE_DIR` is set, else `<checkout>/.graftcache`.
  Backend-free (env + path arithmetic only)."""
  placed = os.environ.get(_PLACED_ENV)
  return (os.path.join(placed, "graftcache") if placed
          else _CHECKOUT_CACHE_DIR)


def xla_cache_dir() -> str:
  """The XLA persistent compilation cache's directory: exactly
  `JAX_COMPILATION_CACHE_DIR` when set, else `<checkout>/.graftcache/xla`."""
  return (os.environ.get(_PLACED_ENV)
          or os.path.join(_CHECKOUT_CACHE_DIR, "xla"))


_META_SUFFIX = ".json"
_BLOB_SUFFIX = ".bin"
# The executable's op table (`obs.xray.build_op_table`), JSON, beside the
# entry; `store` writes it, `load_op_scopes` reads it.
_OPS_SUFFIX = ".ops"
_KEY_RE = re.compile(r"^[A-Za-z0-9_.-]+$")


# ---------------------------------------------------------------------------
# Key computation (pure — no jax, no backend).
# ---------------------------------------------------------------------------


def _slug(name: str) -> str:
  """Filesystem-safe readable prefix for a key (`serve/engine/bucket4`
  -> `serve-engine-bucket4`)."""
  return re.sub(r"[^A-Za-z0-9_.]+", "-", str(name)).strip("-") or "fn"


def cache_key(name: str, *,
              jaxpr_fingerprint: str,
              avals: str,
              mesh: str,
              backend_version: str,
              donation: str,
              static_args: str,
              pallas: str) -> str:
  """THE canonical graftcache key. Every keyword is mandatory on purpose.

  A cached executable is only valid for exactly the computation, input
  layout, device topology, and compiler that produced it, so the key
  fingerprints all of them:

  * `jaxpr_fingerprint` — the traced computation (static_argnums values
    are baked into the jaxpr, but see `static_args` below);
  * `avals` — abstract arg shapes/dtypes + pytree structure + committed
    input shardings (a dtype or layout change MUST miss);
  * `mesh` — device topology (`mesh_fingerprint`): count, platform,
    device kinds. An executable compiled for 8 virtual CPU devices must
    never load into a 1-device process;
  * `backend_version` — jax/jaxlib/backend versions
    (`backend_fingerprint`): serialized executables do not survive
    compiler upgrades (round-5 measured fact: the terminal's older
    libtpu refused image-AOT-compiled executables);
  * `donation` — the declared donated-argument layout: donation changes
    buffer aliasing in the compiled artifact, not just the jaxpr;
  * `static_args` — repr of the non-array (static/config) arguments, a
    belt-and-braces over the jaxpr baking (a static value that steers
    compile options without appearing in the jaxpr still invalidates);
  * `pallas` — the Pallas/Mosaic lowering component
    (`pallas_fingerprint`): kernel-body hash + kernel count + the jax
    (== pallas) version for every `pallas_call` in the computation, or
    `"none"`. The kernel BODY rides inside the jaxpr fingerprint too,
    but grid/BlockSpec/alias/compiler-params metadata lives in eqn
    params whose rendering the jaxpr hash covers only incidentally —
    this component pins kernel revisions explicitly, so editing a
    kernel (or upgrading the pallas toolchain that compiles it)
    invalidates cached executables even when the surrounding jaxpr
    text is unchanged.

  Pure stdlib over pre-computed strings: key computation must work
  with no backend (poisoned-platform test). Callers
  with a live `Traced` use `key_components_from_traced`.

  graftlint (`cache-key-missing-component`) statically flags any call
  site that omits a component — do not "simplify" one away.
  """
  payload = json.dumps({
      "v": CACHE_VERSION,
      "jaxpr": str(jaxpr_fingerprint),
      "avals": str(avals),
      "mesh": str(mesh),
      "backend": str(backend_version),
      "donation": str(donation),
      "static": str(static_args),
      "pallas": str(pallas),
  }, sort_keys=True)
  digest = hashlib.sha256(payload.encode("utf-8")).hexdigest()[:32]
  return f"{_slug(name)}-{digest}"


def mesh_fingerprint(devices: Optional[Sequence[Any]] = None) -> str:
  """Device-topology component: count, platform, sorted device kinds.

  Imports jax lazily (callers run where the backend is already up);
  pass `devices` explicitly to stay backend-free.
  """
  if devices is None:
    import jax

    devices = jax.devices()
  devices = list(devices)
  kinds = sorted({str(getattr(d, "device_kind", "?")) for d in devices})
  platforms = sorted({str(getattr(d, "platform", "?")) for d in devices})
  return (f"n{len(devices)}:" + ",".join(platforms) + ":"
          + ",".join(kinds))


def backend_fingerprint() -> str:
  """Compiler-version component: jax + jaxlib + backend platform_version."""
  import jax

  parts = [f"jax={getattr(jax, '__version__', '?')}"]
  try:
    import jaxlib

    parts.append(f"jaxlib={getattr(jaxlib, '__version__', '?')}")
  except Exception:  # noqa: BLE001 - jaxlib version is best-effort
    pass
  try:
    client = jax.devices()[0].client
    parts.append(f"pjrt={getattr(client, 'platform_version', '?')}")
  except Exception:  # noqa: BLE001 - platform_version is best-effort
    pass
  return ";".join(parts)


def _leaf_is_array(leaf) -> bool:
  return hasattr(leaf, "shape") and hasattr(leaf, "dtype")


# Process-local object addresses inside repr()s — the jaxpr string
# embeds e.g. `jvp_jaxpr_thunk=<function _memoize.<locals>.memoized at
# 0x7eb802cac5e0>` for custom_jvp params (measured: the ONLY jaxpr
# difference between two processes tracing the identical model). Thunk
# identity is not semantic; the equations are. Stripped before hashing
# or no key would ever match across processes.
_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def jaxpr_fingerprint(jaxpr) -> str:
  """sha256 of the jaxpr's address-normalized string form."""
  return hashlib.sha256(
      _ADDR_RE.sub("0x", str(jaxpr)).encode("utf-8")).hexdigest()


def _param_jaxprs(val):
  """Yields the jaxprs nested inside one eqn param value (ClosedJaxpr,
  bare Jaxpr, or tuples/lists of either — the shapes cond/scan/pjit
  and pallas_call actually use)."""
  vals = val if isinstance(val, (tuple, list)) else (val,)
  for v in vals:
    inner = getattr(v, "jaxpr", v)  # ClosedJaxpr -> Jaxpr
    if hasattr(inner, "eqns"):
      yield inner


def pallas_fingerprint(jaxpr) -> str:
  """The Pallas/Mosaic lowering component of a cache key.

  Walks the (closed) jaxpr recursively — through cond/scan/pjit/remat
  sub-jaxprs — collecting every `pallas_call` equation, and hashes
  their address-normalized string forms (the kernel BODY jaxpr plus
  the grid/BlockSpec/alias/compiler-params metadata all render into
  the eqn text). Returns `"none"` for kernel-free computations — the
  overwhelmingly common key stays byte-stable and visibly
  kernel-free — else `jax=<version>;n=<count>;<sha256[:32]>`: a kernel
  revision OR a pallas toolchain bump (pallas ships inside jax, so the
  jax version IS the pallas version) invalidates cached executables.
  Pure jaxpr-walking — never touches a backend (poisoned-platform
  safe)."""
  found: List[str] = []

  def walk(jx):
    for eqn in getattr(jx, "eqns", ()):
      if eqn.primitive.name == "pallas_call":
        found.append(_ADDR_RE.sub("0x", str(eqn)))
      for param_val in eqn.params.values():
        for sub in _param_jaxprs(param_val):
          walk(sub)

  walk(getattr(jaxpr, "jaxpr", jaxpr))
  if not found:
    return "none"
  import jax

  digest = hashlib.sha256("||".join(found).encode("utf-8")).hexdigest()
  return (f"jax={getattr(jax, '__version__', '?')};n={len(found)};"
          f"{digest[:32]}")


def key_components_from_traced(traced, args) -> Dict[str, str]:
  """The `cache_key` components for one `fn.trace(*args)` result.

  `avals` folds in the abstract shapes/dtypes, the args_info pytree
  structure, AND the committed input shardings read off the live args
  (two identically-shaped batches sharded differently compile different
  executables). `static_args` reprs every argument with no array leaves
  — conservative (a dynamic scalar config arg adds key sensitivity, an
  extra miss at worst, never a mismatched executable).
  """
  import jax

  infos = jax.tree_util.tree_leaves(
      traced.args_info, is_leaf=lambda n: hasattr(n, "donated"))
  avals = [str(getattr(i, "aval", i)) for i in infos]
  structure = str(jax.tree_util.tree_structure(
      traced.args_info, is_leaf=lambda n: hasattr(n, "donated")))
  shardings = []
  for arg in args:
    for leaf in jax.tree_util.tree_leaves(arg):
      sharding = getattr(leaf, "sharding", None)
      if sharding is not None:
        shardings.append(str(sharding))
  static = [repr(a) for a in args
            if not any(_leaf_is_array(leaf)
                       for leaf in jax.tree_util.tree_leaves(a))]
  return {
      "jaxpr_fingerprint": jaxpr_fingerprint(traced.jaxpr),
      "avals": structure + "|" + ";".join(avals)
               + "|" + ";".join(shardings),
      "mesh": mesh_fingerprint(),
      "backend_version": backend_fingerprint(),
      "donation": ",".join("D" if getattr(i, "donated", False) else "-"
                           for i in infos),
      "static_args": ";".join(static),
      "pallas": pallas_fingerprint(traced.jaxpr),
  }


# ---------------------------------------------------------------------------
# The on-disk cache.
# ---------------------------------------------------------------------------


def _execution_devices(compiled) -> List[Any]:
  """The devices `compiled` was built for, in assignment order (every
  sharding of one executable shares one device assignment)."""
  import jax

  sharding = jax.tree_util.tree_leaves(
      (compiled.input_shardings, compiled.output_shardings))[0]
  return list(sharding._device_assignment)  # pylint: disable=protected-access


class ExecutableCache:
  """Content-addressed executable store under one directory.

  `load`/`store` never raise (fallback-to-fresh-compile is the caller's
  contract; failures are counted); `entries`/`verify`/`evict` are
  backend-free (metadata sidecars only).
  """

  def __init__(self, cache_dir: str,
               registry: Optional[metrics_lib.Registry] = None):
    self._dir = str(cache_dir)
    self._registry = registry
    self._lock = threading.Lock()

  @property
  def directory(self) -> str:
    return self._dir

  @property
  def _reg(self) -> metrics_lib.Registry:
    # Late-bound: the process-wide registry may be reset/swapped between
    # construction and use (train_eval resets it per run).
    return self._registry or metrics_lib.get_registry()

  def _paths(self, key: str) -> Tuple[str, str]:
    if not _KEY_RE.match(key or ""):
      raise ValueError(f"invalid cache key {key!r}")
    return (os.path.join(self._dir, key + _META_SUFFIX),
            os.path.join(self._dir, key + _BLOB_SUFFIX))

  # -- write side -----------------------------------------------------------

  def store(self, key: str, compiled, record: Optional[Dict[str, Any]] = None,
            name: Optional[str] = None,
            op_scopes: Optional[Dict[str, Any]] = None) -> bool:
    """Serializes + persists one executable; False (counted) on failure.
    `op_scopes` (xray's op table) is written beside it, before the
    sidecar, so an entry that is there is there whole.

    The serialized payload is VALIDATED by an in-process deserialize
    before anything touches disk: an executable that itself came out of
    the XLA persistent compilation cache does not round-trip (re-measured
    on jax 0.9.0, CPU: "Function ..._fusion not found"), and persisting
    it would cost every later process a quarantine + recompile.
    `analyze_jit` compiles AOT-tier misses under `xla_cache_bypassed` so
    this should not occur on the standard path; the validation stays for
    direct `store` callers. Rejections are counted
    (`cache/store_rejected`), never raised.

    The blob is written `.tmp` + `os.replace` and the metadata sidecar
    AFTER the blob, so a reader can never observe a sidecar whose blob
    is missing/torn — at worst an orphan blob, which `verify` reports
    and `evict` collects.
    """
    try:
      from jax.experimental import serialize_executable

      meta_path, blob_path = self._paths(key)
      payload = serialize_executable.serialize(compiled)
      devices = _execution_devices(compiled)
      try:
        serialize_executable.deserialize_and_load(
            *payload, execution_devices=devices)
      except Exception as e:  # noqa: BLE001 - unloadable = do not persist
        self._reg.counter("cache/store_rejected").inc()
        print(f"graftcache: NOT persisting {key!r} — its serialized "
              f"form does not load back ({type(e).__name__}: {e})",
              file=sys.stderr)
        return False
      blob = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
      meta = {
          "cache_version": CACHE_VERSION,
          "key": key,
          "name": str(name or (record or {}).get("name") or key),
          "created_unix": time.time(),
          "blob_bytes": len(blob),
          "blob_sha256": hashlib.sha256(blob).hexdigest(),
          "backend_version": backend_fingerprint(),
          "device_ids": [int(d.id) for d in devices],
      }
      if record:
        # The cold process's xray record (compile_s, flops, roofline,
        # memory analysis): a warm start keeps full compile telemetry
        # without paying the compile. Cache bookkeeping is stripped —
        # hit/miss is a property of THIS process, not of the entry.
        stored = {k: v for k, v in record.items() if k != "cache"}
        meta["record"] = stored
      with self._lock:
        os.makedirs(self._dir, exist_ok=True)
        # Temp names are unique PER WRITER (pid+thread): two processes
        # cold-starting the same key against a shared dir must not
        # scribble into one shared ".tmp" (the in-process lock cannot
        # cover cross-process writers); each rename publishes a
        # complete file, last writer wins.
        suffix = f".tmp.{os.getpid()}.{threading.get_ident()}"
        tmp = blob_path + suffix
        with open(tmp, "wb") as f:
          f.write(blob)
        os.replace(tmp, blob_path)
        if op_scopes is not None:
          ops_path = os.path.join(self._dir, key + _OPS_SUFFIX)
          tmp = ops_path + suffix
          with open(tmp, "w") as f:
            json.dump(op_scopes, f, separators=(",", ":"))
          os.replace(tmp, ops_path)
        tmp = meta_path + suffix
        with open(tmp, "w") as f:
          json.dump(meta, f, sort_keys=True)
        os.replace(tmp, meta_path)
      self._reg.counter("cache/stores").inc()
      self._reg.counter("cache/bytes_stored").inc(len(blob))
      return True
    except Exception as e:  # noqa: BLE001 - caching must never break a run
      self._reg.counter("cache/store_failures").inc()
      print(f"graftcache: store of {key!r} failed "
            f"({type(e).__name__}: {e})", file=sys.stderr)
      return False

  # -- read side ------------------------------------------------------------

  def load_op_scopes(self, key: str) -> Optional[Dict[str, Any]]:
    """The op table `store` wrote beside entry `key`, or None: none was
    stored (the table could not be built) or it does not read back."""
    try:
      with open(os.path.join(self._dir, key + _OPS_SUFFIX)) as f:
        table = json.load(f)
      return table if "ops" in table and "paths" in table else None
    except (OSError, ValueError, TypeError):
      return None

  def load(self, key: str) -> Optional[Dict[str, Any]]:
    """Deserializes one entry: {"compiled", "record", "load_ms", "bytes"}
    or None (miss / corrupt / version-skewed — counted, never raised).

    Any load failure past "file absent" quarantines the entry (both
    files unlinked) and bumps `cache/corrupt_entries`: a stale or
    corrupt entry must cost ONE fresh compile, not one per process
    forever — and must never serve a mismatched executable (the key
    already fingerprints everything semantic; the checksum catches
    torn/bit-rotted blobs).
    """
    try:
      meta_path, blob_path = self._paths(key)
    except ValueError:
      self._reg.counter("cache/misses").inc()
      return None
    if not os.path.isfile(meta_path) or not os.path.isfile(blob_path):
      self._reg.counter("cache/misses").inc()
      return None
    start = time.perf_counter()

    def read_verified():
      with open(meta_path) as f:
        meta = json.load(f)
      if int(meta.get("cache_version", -1)) != CACHE_VERSION:
        raise ValueError(
            f"cache_version {meta.get('cache_version')} != {CACHE_VERSION}")
      with open(blob_path, "rb") as f:
        blob = f.read()
      if len(blob) != int(meta.get("blob_bytes", -1)):
        raise ValueError(f"blob is {len(blob)} bytes, sidecar says "
                         f"{meta.get('blob_bytes')}")
      digest = hashlib.sha256(blob).hexdigest()
      if digest != meta.get("blob_sha256"):
        raise ValueError("blob sha256 mismatch")
      return meta, blob

    try:
      try:
        meta, blob = read_verified()
      except Exception:  # noqa: BLE001 - maybe a concurrent re-store
        # Cross-process store/load race: another process's store
        # replaces the blob a moment before its sidecar (store's write
        # order), so a reader can pair an old sidecar with a new blob.
        # One short-delay retry reads the settled pair; only a SECOND
        # failure is genuine corruption worth quarantining — a race
        # must never destroy the valid entry a peer just wrote.
        time.sleep(0.05)
        meta, blob = read_verified()
      import jax
      from jax.experimental import serialize_executable

      # jax 0.9.0 loads over EVERY local device unless told which ones
      # the executable was compiled for (a 1-device executable on an
      # 8-device process otherwise dies at dispatch with "expected 8
      # shards").
      by_id = {d.id: d for d in jax.devices()}
      payload, in_tree, out_tree = pickle.loads(blob)
      compiled = serialize_executable.deserialize_and_load(
          payload, in_tree, out_tree,
          execution_devices=[by_id[i] for i in meta["device_ids"]])
    except Exception as e:  # noqa: BLE001 - corrupt entry -> fresh compile
      self._quarantine(key, e)
      return None
    load_ms = (time.perf_counter() - start) * 1e3
    self._reg.counter("cache/hits").inc()
    self._reg.counter("cache/bytes").inc(len(blob))
    self._reg.histogram("cache/load_ms").record(load_ms)
    return {"compiled": compiled,
            "record": dict(meta.get("record") or {}),
            "load_ms": load_ms, "bytes": len(blob)}

  def _quarantine(self, key: str, error: Exception) -> None:
    self._reg.counter("cache/corrupt_entries").inc()
    print(f"graftcache: entry {key!r} unusable "
          f"({type(error).__name__}: {error}); quarantined — "
          "falling back to a fresh compile", file=sys.stderr)
    try:
      meta_path, blob_path = self._paths(key)
      for path in (meta_path, blob_path,
                   os.path.join(self._dir, key + _OPS_SUFFIX)):
        try:
          os.unlink(path)
        except OSError:
          pass
    except ValueError:
      pass

  # -- backend-free maintenance (graftscope cache CLI) ----------------------

  def entries(self) -> List[Dict[str, Any]]:
    """Metadata of every entry (sidecars only — no jax, no unpickle).

    Orphan blobs (store died between blob and sidecar) are listed with
    `"orphan": True` so `evict` can collect them.
    """
    out: List[Dict[str, Any]] = []
    if not os.path.isdir(self._dir):
      return out
    seen_blobs = set()
    for fname in sorted(os.listdir(self._dir)):
      path = os.path.join(self._dir, fname)
      if fname.endswith(_META_SUFFIX):
        key = fname[:-len(_META_SUFFIX)]
        entry: Dict[str, Any] = {"key": key}
        try:
          with open(path) as f:
            entry.update({k: v for k, v in json.load(f).items()
                          if k != "record"})
        except (OSError, ValueError) as e:
          entry["corrupt_sidecar"] = f"{type(e).__name__}: {e}"
        blob = os.path.join(self._dir, key + _BLOB_SUFFIX)
        entry["blob_present"] = os.path.isfile(blob)
        seen_blobs.add(key)
        out.append(entry)
    for fname in sorted(os.listdir(self._dir)):
      if fname.endswith(_BLOB_SUFFIX):
        key = fname[:-len(_BLOB_SUFFIX)]
        if key not in seen_blobs:
          out.append({"key": key, "orphan": True,
                      "blob_bytes": os.path.getsize(
                          os.path.join(self._dir, fname))})
    return out

  def verify(self) -> Tuple[List[str], List[str]]:
    """(ok keys, bad keys) by checksum — backend-free, read-only."""
    ok: List[str] = []
    bad: List[str] = []
    for entry in self.entries():
      key = entry["key"]
      if entry.get("orphan") or entry.get("corrupt_sidecar") \
          or not entry.get("blob_present"):
        bad.append(key)
        continue
      blob_path = os.path.join(self._dir, key + _BLOB_SUFFIX)
      try:
        with open(blob_path, "rb") as f:
          blob = f.read()
        if (len(blob) != int(entry.get("blob_bytes", -1))
            or hashlib.sha256(blob).hexdigest()
            != entry.get("blob_sha256")):
          raise ValueError("checksum mismatch")
        ok.append(key)
      except (OSError, ValueError):
        bad.append(key)
    return ok, bad

  def evict(self, key: Optional[str] = None,
            older_than_secs: Optional[float] = None,
            name_prefix: Optional[str] = None) -> int:
    """Removes entries; returns how many were removed.

    No selector = everything INCLUDING an XLA compilation-cache tier
    under `<dir>/xla` (the in-checkout layout, where the two tiers are
    one unit; partial evicts leave the XLA tier alone — AOT-miss
    compiles bypass it anyway, see `xla_cache_bypassed`, so evicted
    entries refill cleanly). `key`
    evicts one entry; `older_than_secs` evicts entries created longer
    ago than that (sidecar-less orphans always match an age sweep);
    `name_prefix` evicts entries whose recorded name starts with it
    (how one caller resets ONLY its own namespace instead of nuking
    every other caller's entries in a shared cache dir).
    """
    selective = (key is not None or older_than_secs is not None
                 or name_prefix is not None)
    if not selective:
      import shutil

      shutil.rmtree(os.path.join(self._dir, "xla"), ignore_errors=True)
    removed = 0
    now = time.time()
    for entry in self.entries():
      if key is not None and entry["key"] != key:
        continue
      if name_prefix is not None and not str(
          entry.get("name") or "").startswith(name_prefix):
        continue
      if older_than_secs is not None and not entry.get("orphan"):
        created = float(entry.get("created_unix") or 0.0)
        if now - created < older_than_secs:
          continue
      for suffix in (_META_SUFFIX, _BLOB_SUFFIX, _OPS_SUFFIX):
        try:
          os.unlink(os.path.join(self._dir, entry["key"] + suffix))
        except OSError:
          continue
      removed += 1
    if removed:
      self._reg.counter("cache/evictions").inc(removed)
    return removed


def as_cache(cache) -> Optional[ExecutableCache]:
  """Coerces a cache argument: ExecutableCache passes through, a
  directory path wraps, None/'' disables."""
  if cache is None or cache == "":
    return None
  if isinstance(cache, ExecutableCache):
    return cache
  return ExecutableCache(str(cache))


# ---------------------------------------------------------------------------
# Tier 2: the XLA compilation cache backstop.
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def xla_cache_bypassed():
  """Switches the XLA persistent compilation cache OFF around one
  compile, then back on — the directory is never touched.

  `analyze_jit` wraps the compile of every AOT-tier MISS in this: an
  executable served out of the XLA persistent cache does not survive a
  serialize round-trip (store() would reject it), so a miss that
  compiled through a warm XLA cache could never refill its AOT entry.
  jax decides once per process whether the cache is in use, so the flag
  only takes effect together with `reset_cache()` (which drops that
  decision and the open cache handle, not the files).
  """
  import jax
  from jax.experimental.compilation_cache import compilation_cache

  if not (jax.config.jax_compilation_cache_dir
          and jax.config.jax_enable_compilation_cache):
    yield
    return
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    yield
  finally:
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def enable_xla_cache() -> str:
  """Arms jax's persistent compilation cache at `xla_cache_dir()` — the
  backstop for plain-jit paths that never route through `analyze_jit`
  (they still re-trace, but the backend compile is absorbed by XLA's own
  cache) — and returns that directory. With `JAX_COMPILATION_CACHE_DIR`
  set jax already points there and this changes nothing about the
  directory. Min-compile-time gate dropped to 0 so smoke-scale
  executables cache too (the default skips anything under 1 s)."""
  import jax

  xla_dir = xla_cache_dir()
  if jax.config.jax_compilation_cache_dir != xla_dir:
    from jax.experimental.compilation_cache import compilation_cache

    os.makedirs(xla_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", xla_dir)
    # jax opens its cache once per process; without the reset a cache
    # already opened elsewhere would keep that directory.
    compilation_cache.reset_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
  return xla_dir


def cache_stats(registry: Optional[metrics_lib.Registry] = None
                ) -> Dict[str, float]:
  """The `cache/*` registry slice as a flat dict — the block run records
  embed (ISSUE 7: every hit/miss/load lands in runs.jsonl). Counters
  are pre-created so the schema is stable even on a zero-traffic run."""
  reg = registry or metrics_lib.get_registry()
  for name in ("cache/hits", "cache/misses", "cache/corrupt_entries",
               "cache/stores", "cache/store_failures",
               "cache/store_rejected"):
    reg.counter(name)
  return reg.snapshot(prefix="cache/")
