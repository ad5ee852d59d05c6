"""Tests for graftcache (`obs/excache.py`): the persistent
executable/AOT cache, its xray/engine integration, the
`graftscope cache` CLI, and the `cache-key-missing-component` lint rule.

Contracts (ISSUE 7):

* the cache key fingerprints EVERYTHING that invalidates an executable
  — jaxpr, abstract shapes/dtypes, donation layout, static-arg values,
  device topology, backend version — and the graftlint rule statically
  rejects call sites that omit a component;
* cross-PROCESS reuse: process A compiles + persists, process B pins
  `compile_count == 0` (all deserializes) for both
  `BucketedEngine.warmup()` and an `XrayedFunction` train step;
* a stale/corrupt entry falls back to a fresh compile with a
  `cache/corrupt_entries` bump — never a crash, never a mismatched
  executable;
* `obs/excache.py` imports and key-computes backend-free
  (poisoned-platform trap), and the `graftscope cache` CLI
  lists/evicts/verifies without touching jax;
* the cold-start metrics (`warmup_ms` up-bad, `cold_vs_warm_warmup`
  down-bad) are diff-gated by `graftscope diff` like any other
  headline metric.
"""

import hashlib
import inspect
import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from tensor2robot_tpu.analysis import cache_check
from tensor2robot_tpu.bin import graftscope
from tensor2robot_tpu.obs import excache
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import runlog
from tensor2robot_tpu.obs import xray

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _hermetic_registry():
  with metrics_lib.isolated():
    xray.clear_records()
    yield
  xray.clear_records()


def _snap(name):
  return metrics_lib.snapshot().get(name, 0.0)


# ---------------------------------------------------------------------------
# Key computation (pure, backend-free).
# ---------------------------------------------------------------------------


_COMPONENTS = dict(jaxpr_fingerprint="fp", avals="f32[4,3]", mesh="n8:cpu",
                   backend_version="jax=0", donation="D-", static_args="",
                   pallas="none")


class TestCacheKey:

  def test_deterministic_and_readable(self):
    k1 = excache.cache_key("serve/engine/bucket4", **_COMPONENTS)
    k2 = excache.cache_key("serve/engine/bucket4", **_COMPONENTS)
    assert k1 == k2
    assert k1.startswith("serve-engine-bucket4-")

  @pytest.mark.parametrize("component", sorted(_COMPONENTS))
  def test_every_component_is_load_bearing(self, component):
    """Changing ANY single component must change the key — the
    invalidation-correctness satellite (mesh topology, dtypes, backend
    version, donation layout, static args all invalidate)."""
    base = excache.cache_key("fn", **_COMPONENTS)
    changed = excache.cache_key(
        "fn", **{**_COMPONENTS, component: _COMPONENTS[component] + "!"})
    assert changed != base

  def test_every_component_is_mandatory(self):
    for component in _COMPONENTS:
      partial = {k: v for k, v in _COMPONENTS.items() if k != component}
      with pytest.raises(TypeError):
        excache.cache_key("fn", **partial)

  def test_lint_rule_mirrors_the_signature(self):
    """REQUIRED_COMPONENTS (the static rule) and cache_key's mandatory
    keywords (the runtime contract) must never drift apart."""
    params = inspect.signature(excache.cache_key).parameters
    kwonly = {n for n, p in params.items()
              if p.kind is inspect.Parameter.KEYWORD_ONLY}
    assert kwonly == set(cache_check.REQUIRED_COMPONENTS)

  def test_donation_and_static_args_in_traced_components(self):
    """`key_components_from_traced` must fold in the declared donation
    layout and static-argument values (satellite: a donation flip or a
    static value change must miss, never serve the stale executable)."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((4, 3))

    def f(s, x):
      return s + x.sum(), x * 2

    plain = jax.jit(f)
    donating = jax.jit(f, donate_argnums=(0,))
    comp_plain = excache.key_components_from_traced(
        plain.trace(jnp.zeros(()), x), (jnp.zeros(()), x))
    comp_donate = excache.key_components_from_traced(
        donating.trace(jnp.zeros(()), x), (jnp.zeros(()), x))
    assert comp_plain["donation"] == "-,-"
    assert comp_donate["donation"] == "D,-"

    g = jax.jit(lambda x, n: x * n, static_argnums=(1,))
    comp4 = excache.key_components_from_traced(g.trace(x, 4), (x, 4))
    comp5 = excache.key_components_from_traced(g.trace(x, 5), (x, 5))
    assert comp4["static_args"] == "4"
    assert comp5["static_args"] == "5"
    assert (excache.cache_key("g", **comp4)
            != excache.cache_key("g", **comp5))

  def test_jaxpr_fingerprint_is_process_stable(self):
    """Object addresses inside the jaxpr string (custom_jvp thunk
    reprs — the measured cross-process key-mismatch cause) must not
    leak into the fingerprint."""
    a = excache.jaxpr_fingerprint(
        "custom_jvp jvp=<function memoized at 0x7eb802cac5e0> { eqns }")
    b = excache.jaxpr_fingerprint(
        "custom_jvp jvp=<function memoized at 0x7ea29e8745e0> { eqns }")
    assert a == b
    assert a != excache.jaxpr_fingerprint("something else")

  def test_pallas_fingerprint_none_for_kernel_free_jaxpr(self):
    """The overwhelmingly common key must stay byte-stable: kernel-free
    computations get the literal 'none' component, and the traced
    component dict carries it."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((4, 3))
    traced = jax.jit(lambda x: x * 2).trace(x)
    assert excache.pallas_fingerprint(traced.jaxpr) == "none"
    comps = excache.key_components_from_traced(traced, (x,))
    assert comps["pallas"] == "none"

  def test_pallas_fingerprint_keys_kernel_lowerings(self):
    """A pallas_call in the computation must key the cache entry on the
    kernel body + pallas (jax) version — the kernel-revision
    invalidation satellite (ISSUE 20). Two different kernel bodies over
    identical avals must fingerprint differently; the same kernel
    re-traced must fingerprint identically (process-stable)."""
    import jax
    import jax.numpy as jnp

    from tensor2robot_tpu.ops import decode_kernels

    b, s, t, h, d = 2, 4, 8, 2, 4
    q = jnp.ones((b, h, d))
    arena = jnp.zeros((s, t, h, d))
    slots = jnp.arange(1, b + 1, dtype=jnp.int32)
    index = jnp.zeros((b,), jnp.int32)
    mask = jnp.ones((b,), bool)
    args = (q, q, q, arena, arena, slots, index, mask)

    def kernel_step(*a):
      return decode_kernels.fused_decode_attention(*a, interpret=True)

    traced = jax.jit(kernel_step).trace(*args)
    fp = excache.pallas_fingerprint(traced.jaxpr)
    assert fp != "none"
    assert fp.startswith(f"jax={jax.__version__};n=")
    # Re-trace: process-stable (addresses normalized out).
    again = excache.pallas_fingerprint(jax.jit(kernel_step).trace(*args).jaxpr)
    assert fp == again
    # The component rides key_components_from_traced into the key.
    comps = excache.key_components_from_traced(traced, args)
    assert comps["pallas"] == fp
    # A different block size = different grid/kernel metadata: new key.
    def kernel_step_b4(*a):
      return decode_kernels.fused_decode_attention(*a, block_k=4,
                                                   interpret=True)

    fp_b4 = excache.pallas_fingerprint(jax.jit(kernel_step_b4).trace(*args).jaxpr)
    assert fp_b4 != fp
    assert (excache.cache_key("k", **comps)
            != excache.cache_key("k", **{**comps, "pallas": fp_b4}))


# ---------------------------------------------------------------------------
# In-process round trip through analyze_jit / XrayedFunction.
# ---------------------------------------------------------------------------


def _jit_fn():
  import jax

  return jax.jit(lambda s, x: (s + x.sum(), x * 2))


def _args():
  import jax.numpy as jnp

  return jnp.zeros(()), jnp.ones((4, 3))


class TestRoundTrip:

  def test_miss_stores_then_hit_loads_and_executes(self, tmp_path):
    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    fn = _jit_fn()
    s, x = _args()
    c1, r1 = xray.analyze_jit("step", fn, s, x, cache=cache)
    assert r1["cache"] == {"hit": False, "key": r1["cache"]["key"],
                          "stored": True}
    assert _snap("counter/cache/misses") == 1.0
    assert _snap("counter/cache/stores") == 1.0
    c2, r2 = xray.analyze_jit("step", fn, s, x, cache=cache)
    assert r2["cache"]["hit"] is True
    assert r2["cache"]["bytes"] > 0
    assert r2["lower_s"] == 0.0 and r2["compile_s"] == 0.0
    # The stored record's cost analysis survives the round trip.
    assert r2["flops"] == r1["flops"]
    assert _snap("counter/cache/hits") == 1.0
    out1, out2 = c1(s, x), c2(s, x)
    np.testing.assert_allclose(np.asarray(out1[0]), np.asarray(out2[0]))
    np.testing.assert_allclose(np.asarray(out1[1]), np.asarray(out2[1]))

  def test_different_shapes_and_dtypes_get_distinct_entries(self, tmp_path):
    import jax.numpy as jnp

    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    fn = _jit_fn()
    xray.analyze_jit("step", fn, jnp.zeros(()), jnp.ones((4, 3)),
                     cache=cache)
    xray.analyze_jit("step", fn, jnp.zeros(()), jnp.ones((8, 3)),
                     cache=cache)
    xray.analyze_jit("step", fn, jnp.zeros(()),
                     jnp.ones((4, 3), jnp.bfloat16), cache=cache)
    assert len(cache.entries()) == 3
    assert _snap("counter/cache/misses") == 3.0
    assert _snap("counter/cache/hits") == 0.0

  def test_corrupt_blob_falls_back_to_fresh_compile(self, tmp_path):
    """The injected-corruption acceptance: a flipped byte must cost ONE
    fresh compile (entry quarantined, counter bumped) — never a crash,
    never a wrong executable."""
    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    fn = _jit_fn()
    s, x = _args()
    _, r1 = xray.analyze_jit("step", fn, s, x, cache=cache)
    key = r1["cache"]["key"]
    blob_path = tmp_path / "exc" / (key + ".bin")
    blob = bytearray(blob_path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    blob_path.write_bytes(bytes(blob))
    compiled, r2 = xray.analyze_jit("step", fn, s, x, cache=cache)
    assert r2["cache"]["hit"] is False  # fell back to a fresh compile
    assert r2["compile_s"] > 0.0
    assert _snap("counter/cache/corrupt_entries") == 1.0
    out = compiled(s, x)
    assert float(out[0]) == pytest.approx(12.0)
    # Quarantined AND re-stored by the fresh compile: entry loads again.
    _, r3 = xray.analyze_jit("step", fn, s, x, cache=cache)
    assert r3["cache"]["hit"] is True

  def test_torn_sidecar_quarantines_not_raises(self, tmp_path):
    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    fn = _jit_fn()
    s, x = _args()
    _, r1 = xray.analyze_jit("step", fn, s, x, cache=cache)
    key = r1["cache"]["key"]
    (tmp_path / "exc" / (key + ".json")).write_text('{"cache_version"')
    assert cache.load(key) is None
    assert _snap("counter/cache/corrupt_entries") == 1.0
    assert not (tmp_path / "exc" / (key + ".bin")).exists()

  def test_version_skew_misses_never_loads(self, tmp_path):
    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    fn = _jit_fn()
    s, x = _args()
    _, r1 = xray.analyze_jit("step", fn, s, x, cache=cache)
    key = r1["cache"]["key"]
    meta_path = tmp_path / "exc" / (key + ".json")
    meta = json.loads(meta_path.read_text())
    meta["cache_version"] = excache.CACHE_VERSION + 1
    meta_path.write_text(json.dumps(meta))
    assert cache.load(key) is None
    assert _snap("counter/cache/corrupt_entries") == 1.0

  def test_quarantined_entry_heals_under_warm_xla_cache(self, tmp_path):
    """The heal loop with BOTH tiers armed: a corrupt entry must cost
    ONE fresh compile and then refill — the AOT-miss compile bypasses
    the warm XLA compilation cache (whose artifacts don't serialize),
    so the re-store validates instead of being rejected forever."""
    import jax

    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    xla_dir = excache.enable_xla_cache()
    fn = _jit_fn()
    s, x = _args()
    # The same HLO through the plain jit: the XLA tier is warm for it.
    fn(s, x)
    assert os.listdir(xla_dir)
    _, r1 = xray.analyze_jit("step", fn, s, x, cache=cache)
    assert r1["cache"]["stored"] is True
    key = r1["cache"]["key"]
    blob_path = tmp_path / "exc" / (key + ".bin")
    blob = bytearray(blob_path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    blob_path.write_bytes(bytes(blob))
    # Fresh compile (XLA tier warm for this HLO) must still produce a
    # serializable executable and REFILL the entry...
    _, r2 = xray.analyze_jit("step", fn, s, x, cache=cache)
    assert r2["cache"] == {"hit": False, "key": key, "stored": True}
    assert _snap("counter/cache/store_rejected") == 0.0
    # ...so the next process-equivalent hits again: healed.
    _, r3 = xray.analyze_jit("step", fn, s, x, cache=cache)
    assert r3["cache"]["hit"] is True
    # The bypass switched the tier off and on again, never moved it.
    assert jax.config.jax_compilation_cache_dir == xla_dir
    assert jax.config.jax_enable_compilation_cache

  def test_xrayed_function_warm_starts_from_cache(self, tmp_path):
    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    s, x = _args()
    f1 = xray.XrayedFunction("step", _jit_fn(), cache=cache)
    f1(s, x)
    assert f1.record["cache"]["hit"] is False
    # A FRESH wrapper (new process stand-in): first call deserializes.
    f2 = xray.XrayedFunction("step", _jit_fn(), cache=cache)
    out = f2(s, x)
    assert f2.record["cache"]["hit"] is True
    assert float(out[0]) == pytest.approx(12.0)

  def test_store_rejection_persists_nothing(self, tmp_path, monkeypatch):
    """A payload that fails its round-trip validation (an executable
    that came out of the XLA persistent cache) must not persist, is
    counted, and leaves the XLA tier alone — that tier is placed from
    outside and is not this module's to delete."""
    from jax.experimental import serialize_executable as se

    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    xla_dir = tmp_path / "exc" / "xla"
    xla_dir.mkdir(parents=True)
    (xla_dir / "artifact").write_bytes(b"x")

    def poisoned(*args, **kwargs):
      raise RuntimeError("Symbols not found (simulated)")

    monkeypatch.setattr(se, "deserialize_and_load", poisoned)
    fn = _jit_fn()
    s, x = _args()
    compiled = fn.trace(s, x).lower().compile()
    assert cache.store("fn-poisoned1", compiled) is False
    assert _snap("counter/cache/store_rejected") == 1.0
    assert (xla_dir / "artifact").exists()
    assert cache.entries() == []

  def test_cache_trouble_never_breaks_analyze(self, tmp_path):
    """An unwritable cache directory degrades to uncached analysis."""
    deny = tmp_path / "deny"
    deny.write_text("not a directory")
    cache = excache.ExecutableCache(str(deny / "sub"))
    fn = _jit_fn()
    s, x = _args()
    compiled, record = xray.analyze_jit("step", fn, s, x, cache=cache)
    assert record["cache"]["stored"] is False
    assert _snap("counter/cache/store_failures") == 1.0
    assert float(compiled(s, x)[0]) == pytest.approx(12.0)


# ---------------------------------------------------------------------------
# Maintenance: entries / verify / evict.
# ---------------------------------------------------------------------------


class TestMaintenance:

  def _populate(self, tmp_path, n=2):
    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    import jax.numpy as jnp

    fn = _jit_fn()
    for i in range(n):
      xray.analyze_jit(f"fn{i}", fn, jnp.zeros(()),
                       jnp.ones((4 + i, 3)), cache=cache)
    return cache

  def test_entries_and_verify(self, tmp_path):
    cache = self._populate(tmp_path)
    entries = cache.entries()
    assert len(entries) == 2
    assert all(e["blob_present"] and e["blob_bytes"] > 0 for e in entries)
    ok, bad = cache.verify()
    assert len(ok) == 2 and bad == []

  def test_verify_flags_bitrot_without_jax(self, tmp_path):
    cache = self._populate(tmp_path)
    victim = cache.entries()[0]["key"]
    blob = tmp_path / "exc" / (victim + ".bin")
    blob.write_bytes(blob.read_bytes()[:-1])
    ok, bad = cache.verify()
    assert bad == [victim] and len(ok) == 1

  def test_evict_all_one_and_by_age(self, tmp_path):
    cache = self._populate(tmp_path)
    key0 = cache.entries()[0]["key"]
    assert cache.evict(key=key0) == 1
    assert len(cache.entries()) == 1
    assert cache.evict(older_than_secs=1e6) == 0  # too young
    assert cache.evict() == 1
    assert cache.entries() == []

  def test_evict_all_wipes_xla_tier(self, tmp_path):
    cache = self._populate(tmp_path)
    xla_dir = tmp_path / "exc" / "xla"
    xla_dir.mkdir()
    (xla_dir / "artifact").write_bytes(b"x")
    cache.evict()
    assert not xla_dir.exists()

  def test_evict_by_name_prefix_spares_other_namespaces(self, tmp_path):
    """A caller resets ONLY its own namespace — a blanket evict in a
    shared cache dir would re-tax every other caller's entries (one
    compile each)."""
    import jax.numpy as jnp

    cache = excache.ExecutableCache(str(tmp_path / "exc"))
    fn = _jit_fn()
    xray.analyze_jit("cache_smoke/train_step", fn, jnp.zeros(()),
                     jnp.ones((4, 3)), cache=cache)
    xray.analyze_jit("bench/train_step", fn, jnp.zeros(()),
                     jnp.ones((8, 3)), cache=cache)
    xla_dir = tmp_path / "exc" / "xla"
    xla_dir.mkdir()
    (xla_dir / "artifact").write_bytes(b"x")
    assert cache.evict(name_prefix="cache_smoke/") == 1
    names = {e.get("name") for e in cache.entries()}
    assert names == {"bench/train_step"}
    # Selective evicts leave the XLA tier alone.
    assert xla_dir.exists()

  def test_orphan_blob_listed_and_collected(self, tmp_path):
    cache = self._populate(tmp_path, n=1)
    (tmp_path / "exc" / "orphan-abc.bin").write_bytes(b"dangling")
    entries = cache.entries()
    orphans = [e for e in entries if e.get("orphan")]
    assert len(orphans) == 1 and orphans[0]["key"] == "orphan-abc"
    _, bad = cache.verify()
    assert "orphan-abc" in bad
    assert cache.evict() == 2
    assert cache.entries() == []


# ---------------------------------------------------------------------------
# Cross-process reuse: compile in A, deserialize-only in B (tier-1).
# ---------------------------------------------------------------------------


_CROSS_PROCESS_BODY = """
import sys
import jax
jax.config.update("jax_platforms", "cpu")
from tensor2robot_tpu import serving, specs as specs_lib
from tensor2robot_tpu.obs import excache, metrics, xray
from tensor2robot_tpu.parallel import train_step as ts
from tensor2robot_tpu.predictors import predictors as predictors_lib
from tensor2robot_tpu.research.qtopt import flagship
from tensor2robot_tpu import modes

phase, cache_dir = sys.argv[1], sys.argv[2]
model = flagship.make_flagship_model("cpu", smoke=True)

# Serving half: the whole bucket ladder through warmup().
predictor = predictors_lib.CheckpointPredictor(model=model,
                                               model_dir="/nonexistent")
predictor.init_randomly()
engine = serving.BucketedEngine(predictor=predictor, max_batch_size=2,
                                cache=cache_dir)
engine.warmup()

# Trainer half: the train step through an XrayedFunction.
feature_spec = model.preprocessor.get_out_feature_specification(modes.TRAIN)
label_spec = model.preprocessor.get_out_label_specification(modes.TRAIN)
features = specs_lib.make_random_numpy(feature_spec, batch_size=4, seed=0)
labels = specs_lib.make_random_numpy(label_spec, batch_size=4, seed=1)
state, _ = ts.create_train_state(model, jax.random.PRNGKey(0), features)
step = xray.XrayedFunction("train_step", ts.make_train_step(model),
                           cache=excache.ExecutableCache(cache_dir))
state, metrics_out = step(state, features, labels)
loss = float(metrics_out["loss"])
assert loss == loss, "non-finite loss"

train_hit = bool((step.record.get("cache") or {}).get("hit"))
snap = metrics.snapshot()
print(f"RESULT {phase} engine_compiles={engine.compile_count} "
      f"engine_loads={engine.cache_loads} train_hit={train_hit} "
      f"hits={snap.get('counter/cache/hits', 0):.0f} "
      f"misses={snap.get('counter/cache/misses', 0):.0f} "
      f"corrupt={snap.get('counter/cache/corrupt_entries', 0):.0f}")
"""


def _run_phase(phase, cache_dir):
  env = {**os.environ, "PYTHONPATH": REPO_ROOT, "JAX_PLATFORMS": "cpu"}
  env.pop("XLA_FLAGS", None)  # single-device child: topology-keyed
  result = subprocess.run(
      [sys.executable, "-c", _CROSS_PROCESS_BODY, phase, cache_dir],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  line = [l for l in result.stdout.splitlines()
          if l.startswith(f"RESULT {phase}")][0]
  return dict(kv.split("=") for kv in line.split()[2:])


def test_cross_process_warm_start_deserializes_everything(tmp_path):
  """ISSUE 7 acceptance: process A compiles + persists; process B pins
  `compile_count == 0` (all executables served from disk) for BOTH the
  BucketedEngine bucket ladder and the XrayedFunction train step."""
  cache_dir = str(tmp_path / "exc")
  cold = _run_phase("cold", cache_dir)
  assert cold["engine_compiles"] == "2"  # buckets [1, 2]
  assert cold["engine_loads"] == "0"
  assert cold["train_hit"] == "False"
  assert cold["misses"] == "3" and cold["hits"] == "0"
  warm = _run_phase("warm", cache_dir)
  assert warm["engine_compiles"] == "0"
  assert warm["engine_loads"] == "2"
  assert warm["train_hit"] == "True"
  assert warm["hits"] == "3" and warm["misses"] == "0"
  assert warm["corrupt"] == "0"


# ---------------------------------------------------------------------------
# graftscope cache CLI (backend-free maintenance).
# ---------------------------------------------------------------------------


def _fake_entry(cache_dir, key, name="fn", payload=b"payload"):
  os.makedirs(cache_dir, exist_ok=True)
  with open(os.path.join(cache_dir, key + ".bin"), "wb") as f:
    f.write(payload)
  meta = {"cache_version": excache.CACHE_VERSION, "key": key,
          "name": name, "created_unix": 0.0,
          "blob_bytes": len(payload),
          "blob_sha256": hashlib.sha256(payload).hexdigest(),
          "backend_version": "jax=test"}
  with open(os.path.join(cache_dir, key + ".json"), "w") as f:
    json.dump(meta, f)


class TestCacheCLI:

  def test_list_and_verify_ok(self, tmp_path, capsys):
    cache_dir = str(tmp_path / "exc")
    _fake_entry(cache_dir, "train_step-abc", name="train_step")
    _fake_entry(cache_dir, "serve-engine-bucket4-def",
                name="serve/engine/bucket4")
    assert graftscope.main(["cache", cache_dir, "--verify"]) == 0
    out = capsys.readouterr().out
    assert "2 entries" in out
    assert "train_step" in out and "serve/engine/bucket4" in out
    assert out.count("  ok") == 2

  def test_verify_flags_corruption_exit_1(self, tmp_path, capsys):
    cache_dir = str(tmp_path / "exc")
    _fake_entry(cache_dir, "train_step-abc")
    with open(os.path.join(cache_dir, "train_step-abc.bin"), "wb") as f:
      f.write(b"tampered")
    assert graftscope.main(["cache", cache_dir, "--verify"]) == 1
    assert "CORRUPT" in capsys.readouterr().out

  def test_evict_all_and_by_key(self, tmp_path, capsys):
    cache_dir = str(tmp_path / "exc")
    _fake_entry(cache_dir, "a-1")
    _fake_entry(cache_dir, "b-2")
    assert graftscope.main(["cache", cache_dir, "--evict",
                            "--key", "a-1"]) == 0
    assert "evicted 1 entry" in capsys.readouterr().out
    assert graftscope.main(["cache", cache_dir, "--evict"]) == 0
    assert "evicted 1 entry" in capsys.readouterr().out
    assert excache.ExecutableCache(cache_dir).entries() == []

  def test_evict_by_name_prefix(self, tmp_path, capsys):
    cache_dir = str(tmp_path / "exc")
    _fake_entry(cache_dir, "cache-smoke-a", name="cache_smoke/serve")
    _fake_entry(cache_dir, "bench-b", name="bench/train_step")
    assert graftscope.main(["cache", cache_dir, "--evict",
                            "--name-prefix", "cache_smoke/"]) == 0
    assert "evicted 1 entry" in capsys.readouterr().out
    names = {e.get("name")
             for e in excache.ExecutableCache(cache_dir).entries()}
    assert names == {"bench/train_step"}

  def test_missing_dir_exits_2(self, tmp_path, capsys):
    assert graftscope.main(["cache", str(tmp_path / "nope")]) == 2
    assert "no cache directory" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# graftlint: cache-key-missing-component.
# ---------------------------------------------------------------------------


class TestCacheKeyLint:

  def test_flags_omitted_components(self):
    source = (
        "from tensor2robot_tpu.obs import excache\n"
        "key = excache.cache_key('fn', jaxpr_fingerprint=fp,\n"
        "                        avals=avals, donation=d)\n")
    findings = cache_check.check_python_source("x.py", source)
    assert len(findings) == 1
    assert findings[0].rule == "cache-key-missing-component"
    for component in ("mesh", "backend_version", "static_args", "pallas"):
      assert component in findings[0].message

  def test_full_call_and_splat_pass(self):
    source = (
        "key1 = cache_key('fn', jaxpr_fingerprint=a, avals=b, mesh=c,\n"
        "                 backend_version=d, donation=e, static_args=f,\n"
        "                 pallas=g)\n"
        "key2 = cache_key('fn', **components)\n")
    assert cache_check.check_python_source("x.py", source) == []

  def test_suppression_honored(self):
    source = ("key = cache_key('fn', avals=b)"
              "  # graftlint: disable=cache-key-missing-component\n")
    path = "/tmp/does-not-matter.py"
    findings = cache_check.check_python_source(path, source)
    assert len(findings) == 1  # raw check still sees it
    from tensor2robot_tpu.analysis.findings import (filter_findings,
                                                    load_suppressions)

    assert filter_findings(findings, load_suppressions(source)) == []

  def test_unrelated_calls_ignored(self):
    source = "cache.get('fn')\ncompute_key('fn')\nd['cache_key']\n"
    assert cache_check.check_python_source("x.py", source) == []


# ---------------------------------------------------------------------------
# Cold-start regression gating (runlog thresholds).
# ---------------------------------------------------------------------------


class TestColdStartGating:

  def _record(self, warmup_ms, ratio):
    return runlog.make_record(
        "bench", platform="cpu",
        bench={"metric": "qtopt_warm_start_ms_cpu_smoke",
               "value": warmup_ms, "unit": "ms",
               "warmup_ms": warmup_ms, "cold_vs_warm_warmup": ratio})

  def test_key_metrics_extracts_cache_headline(self):
    metrics = runlog.key_metrics(self._record(1500.0, 2.9))
    assert metrics["warmup_ms"] == 1500.0
    assert metrics["cold_vs_warm_warmup"] == 2.9
    # "ms" unit must NOT fold into examples_per_sec.
    assert "examples_per_sec" not in metrics

  def test_warmup_regression_is_up_bad(self):
    deltas = runlog.diff_records(self._record(1000.0, 3.0),
                                 self._record(1800.0, 3.1))
    flagged = {d["metric"] for d in deltas if d["regressed"]}
    assert "warmup_ms" in flagged
    # A warmup IMPROVEMENT never flags.
    deltas = runlog.diff_records(self._record(1800.0, 3.0),
                                 self._record(1000.0, 3.1))
    assert not any(d["regressed"] for d in deltas
                   if d["metric"] == "warmup_ms")

  def test_cache_speedup_collapse_is_down_bad(self):
    """cold/warm dropping toward 1.0 = the cache stopped saving
    compiles — the ISSUE 7 down-bad acceptance gate."""
    deltas = runlog.diff_records(self._record(1000.0, 3.0),
                                 self._record(1050.0, 1.05))
    flagged = {d["metric"] for d in deltas if d["regressed"]}
    assert "cold_vs_warm_warmup" in flagged

  def test_cross_metric_bench_diff_warns_but_never_flags(self):
    """A cold-start record diffed against a warm-start one (or any two
    different bench headlines) lists deltas with a not-comparable
    warning but never exits 3 — a bogus gate failure across a metric
    boundary trains people to ignore the gate."""
    cold = runlog.make_record(
        "bench", platform="cpu",
        bench={"metric": "qtopt_cold_start_ms_cpu_smoke",
               "value": 5200.0, "unit": "ms", "warmup_ms": 5200.0})
    warm = self._record(1800.0, 2.9)
    deltas = runlog.diff_records(cold, warm)
    assert not any(d["regressed"] for d in deltas)
    assert any("bench metric differs" in w
               for w in runlog.comparability_warnings(cold, warm))

  def test_smoke_semantics_boundary_warns_but_never_flags(self):
    """PR-7 boundary: the same qtopt_grasps_per_sec_cpu_smoke name
    switched from synthetic to record-fed semantics (ISSUE 7 keeps the
    name). Old-vs-new reads ~4x down — a measurement change, not a
    regression: warned, listed, never flagged."""
    old = runlog.make_record(
        "bench", platform="cpu",
        bench={"metric": "qtopt_grasps_per_sec_cpu_smoke",
               "value": 3643.0, "unit": "examples/sec"})
    new = runlog.make_record(
        "bench", platform="cpu",
        bench={"metric": "qtopt_grasps_per_sec_cpu_smoke",
               "value": 810.0, "unit": "examples/sec",
               "data_vs_synthetic": 0.65})
    deltas = runlog.diff_records(old, new)
    assert not any(d["regressed"] for d in deltas)
    assert any("semantics differ" in w
               for w in runlog.comparability_warnings(old, new))
    # Two record-fed runs still gate normally.
    new_bad = runlog.make_record(
        "bench", platform="cpu",
        bench={"metric": "qtopt_grasps_per_sec_cpu_smoke",
               "value": 700.0, "unit": "examples/sec",
               "data_vs_synthetic": 0.30})
    deltas = runlog.diff_records(new, new_bad)
    assert any(d["regressed"] for d in deltas
               if d["metric"] == "data_vs_synthetic")

  def test_cache_hit_vs_miss_compile_time_warns_not_flags(self):
    """A warm record (cache hit: compile_s ~0) diffed against a
    legitimate later miss must not flag compile_time_s — the delta
    prices cache economics, not the compiler. Miss-vs-miss still
    gates."""
    def rec(hit, compile_s):
      return runlog.make_record(
          "train", platform="cpu",
          compile_records=[{"name": "train_step", "trace_s": 0.1,
                            "lower_s": 0.0 if hit else 0.5,
                            "compile_s": compile_s,
                            "cache": {"hit": hit, "key": "k"}}])

    warm, miss = rec(True, 0.0), rec(False, 25.0)
    deltas = {d["metric"]: d for d in runlog.diff_records(warm, miss)}
    assert not deltas["compile_time_s"]["regressed"]
    assert any("cache hit/miss differs" in w
               for w in runlog.comparability_warnings(warm, miss))
    deltas = {d["metric"]: d
              for d in runlog.diff_records(rec(False, 10.0),
                                           rec(False, 25.0))}
    assert deltas["compile_time_s"]["regressed"]

  def test_data_vs_synthetic_is_down_bad(self):
    a = runlog.make_record(
        "bench", platform="cpu",
        bench={"metric": "qtopt_grasps_per_sec_cpu_smoke",
               "value": 800.0, "unit": "examples/sec",
               "data_vs_synthetic": 0.65})
    b = runlog.make_record(
        "bench", platform="cpu",
        bench={"metric": "qtopt_grasps_per_sec_cpu_smoke",
               "value": 820.0, "unit": "examples/sec",
               "data_vs_synthetic": 0.30})
    deltas = runlog.diff_records(a, b)
    flagged = {d["metric"] for d in deltas if d["regressed"]}
    assert "data_vs_synthetic" in flagged


def _serving_predictor():
  from tensor2robot_tpu.predictors import predictors as predictors_lib
  from tensor2robot_tpu.utils import mocks

  predictor = predictors_lib.CheckpointPredictor(
      model=mocks.MockT2RModel(device_type="cpu"), model_dir="/nonexistent")
  predictor.init_randomly()
  return predictor


class TestCachePlacement:
  """ISSUE 22 §4: the compile cache is placed from outside or sits at
  one fixed path in the checkout — never under a model_dir, never
  nulled, never moved by trainer or server."""

  def test_env_var_places_both_tiers(self, monkeypatch, tmp_path):
    import jax

    placed = str(tmp_path / "placed")
    os.makedirs(placed)
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    try:
      assert excache.xla_cache_dir() == placed
      assert excache.cache_root() == os.path.join(placed, "graftcache")
      assert excache.enable_xla_cache() == placed
      assert jax.config.jax_compilation_cache_dir == placed
    finally:
      monkeypatch.undo()
      assert excache.enable_xla_cache() == before

  def test_unset_env_means_the_fixed_checkout_path(self, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO_ROOT, ".graftcache")
    assert excache.cache_root() == fixed
    assert excache.xla_cache_dir() == os.path.join(fixed, "xla")

  def test_bypass_restores_the_same_directory(self):
    import jax

    xla_dir = excache.enable_xla_cache()
    with excache.xla_cache_bypassed():
      assert jax.config.jax_compilation_cache_dir == xla_dir
      assert not jax.config.jax_enable_compilation_cache
    assert jax.config.jax_compilation_cache_dir == xla_dir
    assert jax.config.jax_enable_compilation_cache

  def test_trainer_and_server_never_move_the_cache(
      self, tmp_path, monkeypatch):
    """Every `jax_compilation_cache_dir` update made by the trainer
    (train and eval modes, a resume among them) and a serving engine
    warmup is recorded: none names another directory than the placed
    one, and train mode does not null it. "auto" is the cache root, not
    `<model_dir>/excache`."""
    import jax

    from tensor2robot_tpu import serving, train_eval
    from tensor2robot_tpu.utils import mocks

    placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
    seen = []
    real_update = jax.config.update

    def spy(name, value):
      if name == "jax_compilation_cache_dir":
        seen.append(value)
      return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    model_dir = str(tmp_path / "m")
    for mode, steps in (("train", 2), ("evaluate", 2), ("train", 4)):
      with metrics_lib.isolated():
        train_eval.train_eval_model(
            model=mocks.MockT2RModel(device_type="cpu"),
            model_dir=model_dir, mode=mode, max_train_steps=steps,
            checkpoint_every_n_steps=2, eval_steps=1,
            input_generator_train=mocks.MockInputGenerator(batch_size=8),
            input_generator_eval=mocks.MockInputGenerator(batch_size=8),
            step_stats_every_n_steps=1, log_every_n_steps=2)
      assert jax.config.jax_compilation_cache_dir == placed
    assert not os.path.exists(os.path.join(model_dir, "excache"))
    assert os.path.isdir(excache.cache_root())
    predictor = _serving_predictor()
    with metrics_lib.isolated():
      serving.BucketedEngine(predictor=predictor, max_batch_size=2,
                             cache=excache.cache_root()).warmup()
    assert jax.config.jax_compilation_cache_dir == placed
    assert set(seen) <= {placed}, seen

  def test_resume_loads_the_train_step_it_stored(self, tmp_path):
    """The donating-mesh warm-cache path (re-run on jax 0.9.0: clean):
    a resumed trainer deserializes the mesh-sharded, state-donating
    train step the first run stored, and trains on with it."""
    import json

    from tensor2robot_tpu import train_eval
    from tensor2robot_tpu.utils import mocks

    model_dir = str(tmp_path / "m")
    cache_dir = str(tmp_path / "exc")
    for steps in (2, 6):
      train_eval.train_eval_model(
          model=mocks.MockT2RModel(device_type="cpu"),
          model_dir=model_dir, mode="train", max_train_steps=steps,
          checkpoint_every_n_steps=2,
          input_generator_train=mocks.MockInputGenerator(batch_size=8),
          step_stats_every_n_steps=1, log_every_n_steps=2,
          executable_cache_dir=cache_dir)
    records = [json.loads(line)
               for line in open(os.path.join(model_dir, "runs.jsonl"))]
    cold, warm = records[0], records[-1]
    assert cold["extra"]["cache"]["counter/cache/stores"] == 1.0
    assert warm["extra"]["cache"]["counter/cache/hits"] == 1.0
    assert warm["extra"]["cache"]["counter/cache/misses"] == 0.0
    assert warm["extra"]["final_step"] == 6
    (step_rec,) = [r for r in warm["compile"]
                   if r["name"] == "train_step"]
    assert step_rec["cache"]["hit"] is True
    assert np.isfinite(warm["extra"]["final_metrics"]["loss"])


# ---------------------------------------------------------------------------
# Tier-1: excache + the cache CLI are backend-free (poisoned trap).
# ---------------------------------------------------------------------------


def test_excache_imports_and_key_computes_backend_free(tmp_path):
  """`obs/excache.py` must import, compute keys, and run every
  maintenance surface (entries/verify/evict + the `graftscope cache`
  CLI) without initializing any JAX backend — the repo-standard
  poisoned-platform trap."""
  cache_dir = str(tmp_path / "exc")
  _fake_entry(cache_dir, "train_step-feedbeef")
  code = f"""
from tensor2robot_tpu.obs import excache

key = excache.cache_key("train_step",
                        jaxpr_fingerprint="fp", avals="f32[4]",
                        mesh="n8:cpu", backend_version="jax=x",
                        donation="D-", static_args="", pallas="none")
assert key.startswith("train_step-"), key
assert excache.jaxpr_fingerprint("a 0xdead b") == \\
    excache.jaxpr_fingerprint("a 0xbeef b")

cache = excache.ExecutableCache({cache_dir!r})
entries = cache.entries()
assert len(entries) == 1, entries
ok, bad = cache.verify()
assert ok and not bad, (ok, bad)

from tensor2robot_tpu.bin import graftscope
assert graftscope.main(["cache", {cache_dir!r}, "--verify"]) == 0
assert cache.evict() == 1

from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {{sorted(live)}}"
print("EXCACHE_NO_BACKEND_OK")
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "excache_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", code],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "EXCACHE_NO_BACKEND_OK" in result.stdout
