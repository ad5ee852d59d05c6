"""Tests for the graftlint static-analysis subsystem.

Three contracts:

* the repo itself is permanently clean (`test_repo_clean` — tier-1, so
  any future violation fails the suite);
* each rule family actually fires on violating fixtures (config /
  tracer-hygiene / spec-sharding), and the CLI exits non-zero on them;
* analysis NEVER initializes a JAX backend: the CLI runs over the whole
  repo in a subprocess whose JAX_PLATFORMS names a nonexistent platform
  — any backend init raises immediately (on a machine with a chip it
  would instead take it).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tensor2robot_tpu import specs
from tensor2robot_tpu.analysis import (cache_check, config_check,
                                       engine as engine_lib,
                                       findings as findings_lib, fleet_check,
                                       forge_check, lint, loop_check,
                                       native_check, pp_check, retry_check,
                                       session_check, spec_check,
                                       thread_check, trace_check,
                                       tracer_check)
from tensor2robot_tpu.utils import config
from tensor2robot_tpu.utils import mocks  # registers MockT2RModel  # noqa: F401

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_PATHS = [os.path.join(REPO_ROOT, "tensor2robot_tpu"),
              os.path.join(REPO_ROOT, "scripts"),
              os.path.join(REPO_ROOT, "chip_smoke.py")]

MESH_AXES = {"data", "fsdp", "model"}


def _rules(findings):
  return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# The repo is clean, and stays clean.
# ---------------------------------------------------------------------------


def test_repo_clean():
  findings = lint.run(LINT_PATHS)
  assert not findings, "graftlint findings in the repo:\n" + "\n".join(
      str(f) for f in findings)


def test_list_rules_runs():
  assert lint.main(["--list-rules"]) == 0


# ---------------------------------------------------------------------------
# Config rule family.
# ---------------------------------------------------------------------------


def _check_gin(tmp_path, text, name="fixture.gin"):
  path = tmp_path / name
  path.write_text(text)
  return config_check.check_config_file(str(path))


def test_config_unknown_configurable(tmp_path):
  out = _check_gin(tmp_path, "TotallyUnknownThing.param = 1\n")
  assert _rules(out) == {"unknown-configurable"}
  assert out[0].line == 1


def test_config_missing_import(tmp_path):
  # MockT2RModel IS registered in this test process (imported above), but
  # the config has no import line covering utils.mocks — a fresh trainer
  # process would fail to resolve it. The static closure catches that.
  out = _check_gin(tmp_path, "MockT2RModel.device_type = 'cpu'\n")
  assert _rules(out) == {"missing-import"}
  assert "tensor2robot_tpu.utils.mocks" in out[0].message


def test_config_import_line_covers(tmp_path):
  out = _check_gin(tmp_path,
                   "import tensor2robot_tpu.utils.mocks\n"
                   "MockT2RModel.device_type = 'cpu'\n")
  assert not out


def test_config_unknown_parameter(tmp_path):
  # MockInputGenerator has a closed signature; MockT2RModel would NOT
  # flag (it forwards **kwargs, so any parameter name is plausible).
  out = _check_gin(tmp_path,
                   "import tensor2robot_tpu.utils.mocks\n"
                   "MockInputGenerator.not_a_real_parameter = 3\n")
  assert _rules(out) == {"unknown-parameter"}
  assert out[0].line == 2
  out = _check_gin(tmp_path,
                   "import tensor2robot_tpu.utils.mocks\n"
                   "MockT2RModel.not_a_real_parameter = 3\n",
                   name="kwargs.gin")
  assert not out


def test_config_duplicate_binding(tmp_path):
  out = _check_gin(tmp_path,
                   "train_eval_model.max_train_steps = 5\n"
                   "train_eval_model.max_train_steps = 9\n")
  assert _rules(out) == {"duplicate-binding"}
  assert out[0].line == 2
  assert ":1" in out[0].message  # points at the shadowed first binding


def test_config_undefined_macro(tmp_path):
  out = _check_gin(tmp_path,
                   "train_eval_model.max_train_steps = %NOT_DEFINED\n")
  assert _rules(out) == {"undefined-macro"}


def test_config_defined_macro_ok(tmp_path):
  out = _check_gin(tmp_path,
                   "NUM_STEPS = 7\n"
                   "train_eval_model.max_train_steps = %NUM_STEPS\n")
  assert not out


def test_config_reference_inside_macro_value_checked(tmp_path):
  # A bad @reference (or %macro) hidden behind a macro definition fails
  # at resolve time just the same — the checker must look inside macro
  # values, not only binding RHSs.
  out = _check_gin(tmp_path,
                   "MODEL = @NoSuchModelAnywhere\n"
                   "train_eval_model.model = %MODEL\n")
  assert _rules(out) == {"unknown-configurable"}
  out = _check_gin(tmp_path,
                   "OTHER = %NEVER_DEFINED\n"
                   "train_eval_model.max_train_steps = %OTHER\n",
                   name="chain.gin")
  assert _rules(out) == {"undefined-macro"}


def test_config_type_mismatch(tmp_path):
  out = _check_gin(tmp_path,
                   "train_eval_model.max_train_steps = 'lots'\n")
  assert _rules(out) == {"type-mismatch"}
  out = _check_gin(tmp_path, "train_eval_model.model_dir = 3\n",
                   name="fixture2.gin")
  assert _rules(out) == {"type-mismatch"}


def test_config_type_ok_int_for_float_and_refs(tmp_path):
  out = _check_gin(tmp_path,
                   "train_eval_model.eval_throttle_secs = 5\n"
                   "train_eval_model.model = @MockT2RModel()\n"
                   "import tensor2robot_tpu.utils.mocks\n")
  assert not out


def test_config_broken_import(tmp_path):
  out = _check_gin(tmp_path, "import tensor2robot_tpu.no_such_module\n")
  assert "broken-import" in _rules(out)


def test_config_suppression(tmp_path):
  out = _check_gin(
      tmp_path,
      "TotallyUnknownThing.param = 1  # graftlint: disable=unknown-configurable\n")
  assert not out


def test_config_suppression_multiline_statement(tmp_path):
  # The finding anchors at the statement's first line; the disable
  # comment may sit on ANY physical line of the statement.
  out = _check_gin(
      tmp_path,
      "TotallyUnknownThing.param = [\n"
      "    1,\n"
      "]  # graftlint: disable=unknown-configurable\n")
  assert not out


def test_config_include_followed(tmp_path):
  (tmp_path / "base.gin").write_text("UnknownInBase.param = 1\n")
  out = _check_gin(tmp_path, "include 'base.gin'\n")
  assert _rules(out) == {"unknown-configurable"}
  assert out[0].path.endswith("base.gin")


def test_config_include_then_override_not_duplicate(tmp_path):
  # gin's standard idiom: include a base, override its bindings. Only
  # same-file rebinds are mistakes.
  (tmp_path / "base.gin").write_text(
      "train_eval_model.max_train_steps = 5\n")
  out = _check_gin(tmp_path,
                   "include 'base.gin'\n"
                   "train_eval_model.max_train_steps = 9\n")
  assert not out


# ---------------------------------------------------------------------------
# Tracer-hygiene rule family.
# ---------------------------------------------------------------------------


_TRACER_FIXTURE = """
import time
import functools
import jax
import jax.numpy as jnp
import numpy as np

_CENTERS = jnp.array([[1.0]])
_DEVICES = jax.devices()

def barrier(x):
  return jax.block_until_ready(x)

@jax.jit
def step(x, y):
  t = time.time()
  z = np.random.rand(3)
  v = float(x)
  w = np.asarray(y)
  return x.sum().item()

def _wrapped(a):
  return int(a)

wrapped = jax.jit(_wrapped)

@functools.partial(jax.jit, static_argnums=0)
def step2(n, x):
  return np.random.randint(0, n)
"""


def test_tracer_rules_fire():
  out = tracer_check.check_python_source(_TRACER_FIXTURE, "fixture.py")
  # `barrier()`'s jax.block_until_ready is NOT a finding: measured on
  # the v5e (PR 22) it is a barrier, and the rule against it is gone.
  assert _rules(out) == {"import-time-backend", "impure-in-jit",
                         "host-sync-in-jit"}
  by_rule = {}
  for f in out:
    by_rule.setdefault(f.rule, []).append(f)
  assert len(by_rule["import-time-backend"]) == 2
  # float(x), np.asarray(y), .item(), int(a) in the jit-wrapped fn.
  assert len(by_rule["host-sync-in-jit"]) == 4
  # time.time, np.random.rand, np.random.randint (partial(jax.jit) form).
  assert len(by_rule["impure-in-jit"]) == 3


def test_tracer_clean_outside_jit():
  src = """
import jax
import numpy as np

def fine(x):
  return float(np.asarray(x).item())

def also_fine():
  return jax.devices()

if __name__ == "__main__":
  print(jax.default_backend())
"""
  assert not tracer_check.check_python_source(src, "fixture.py")


def test_tracer_suppression():
  src = "import jax\n_D = jax.devices()  # graftlint: disable=import-time-backend\n"
  assert not tracer_check.check_python_source(src, "fixture.py")
  src_all = "import jax\n_D = jax.devices()  # graftlint: disable\n"
  assert not tracer_check.check_python_source(src_all, "fixture.py")


def test_tracer_block_until_ready_closes_a_timed_window():
  """The `block-until-ready` rule's old cases, as cases of
  `device-timing`: the call is allowed on every path, and it is a
  closing barrier — the same window without it is the finding."""
  backend_py = os.path.join(REPO_ROOT, "tensor2robot_tpu", "utils",
                            "backend.py")
  assert not tracer_check.check_python_file(backend_py)
  src = "import jax\ndef f(x):\n  return jax.block_until_ready(x)\n"
  assert tracer_check.check_python_source(src, "other.py") == []
  window = ("import time\nimport jax\nimport jax.numpy as jnp\n"
            "def f(x):\n"
            "  t0 = time.perf_counter()\n"
            "  y = jnp.dot(x, x)\n"
            "{close}"
            "  return time.perf_counter() - t0\n")
  for close in ("  jax.block_until_ready(y)\n",
                "  y.block_until_ready()\n"):
    assert tracer_check.check_python_source(
        window.format(close=close), "other.py") == [], close
  assert _rules(tracer_check.check_python_source(
      window.format(close=""), "other.py")) == {"device-timing"}


def test_tracer_import_time_default_arg():
  src = "import jax.numpy as jnp\ndef f(x=jnp.zeros(3)):\n  return x\n"
  out = tracer_check.check_python_source(src, "fixture.py")
  assert _rules(out) == {"import-time-backend"}


def test_tracer_import_time_decorator():
  # Decorator expressions execute at import time, exactly like the
  # grasp2vec module constant this PR fixed.
  src = ("import functools\n"
         "import jax.numpy as jnp\n"
         "def register(fn, table):\n"
         "  return fn\n"
         "@functools.partial(register, table=jnp.eye(3))\n"
         "def f(x):\n"
         "  return x\n")
  out = tracer_check.check_python_source(src, "fixture.py")
  assert _rules(out) == {"import-time-backend"}
  # ...but a plain @jax.jit decorator is lazy and must NOT flag.
  src_ok = "import jax\n@jax.jit\ndef f(x):\n  return x\n"
  assert not tracer_check.check_python_source(src_ok, "fixture.py")


def test_tracer_suppression_multiline_call():
  src = ("import jax\n"
         "_D = jax.devices(\n"
         ")  # graftlint: disable=import-time-backend\n")
  assert not tracer_check.check_python_source(src, "fixture.py")


# ---------------------------------------------------------------------------
# Spec/sharding rule family.
# ---------------------------------------------------------------------------


def test_spec_static_rules():
  src = """
from tensor2robot_tpu import specs

GOOD = specs.TensorSpec(shape=(8, 4), sharding=(None, 'model'))
BAD_AXIS = specs.TensorSpec(shape=(8, 4), sharding=(None, 'modle'))
DUP = specs.TensorSpec(shape=(8, 4), sharding=('model', 'model'))
LONG = specs.TensorSpec(shape=(8,), sharding=('data', 'model'))
"""
  out = spec_check.check_python_source(src, "fixture.py", MESH_AXES)
  assert _rules(out) == {"unknown-mesh-axis", "duplicate-sharding-axis",
                         "sharding-rank-mismatch"}
  assert len(out) == 3


def test_spec_suppression_multiline_call():
  src = ("from tensor2robot_tpu import specs\n"
         "S = specs.TensorSpec(\n"
         "    shape=(4,),\n"
         "    sharding=('custom',))  # graftlint: disable=unknown-mesh-axis\n")
  assert not spec_check.check_python_source(src, "fixture.py", MESH_AXES)


def test_spec_axes_from_configs_extend_vocabulary(tmp_path):
  gin = tmp_path / "mesh.gin"
  gin.write_text("train_eval_model.mesh_axis_names = ('data', 'sp', 'model')\n")
  axes = spec_check.known_mesh_axes([str(gin)])
  assert {"data", "fsdp", "model", "sp"} <= axes
  src = "from tensor2robot_tpu import specs\n" \
        "S = specs.TensorSpec(shape=(4, 4), sharding=('sp', None))\n"
  assert not spec_check.check_python_source(src, "fixture.py", axes)


def test_spec_structure_checker_conflict():
  feature = specs.SpecStruct()
  feature["state/obs"] = specs.TensorSpec(shape=(8, 4),
                                          sharding=(None, "model"))
  label = specs.SpecStruct()
  label["state/obs"] = specs.TensorSpec(shape=(8, 4),
                                        sharding=("model", None))
  out = spec_check.check_spec_structures(feature, label,
                                         mesh_axes=MESH_AXES)
  assert _rules(out) == {"sharding-conflict"}
  ok = spec_check.check_spec_structures(feature, feature,
                                        mesh_axes=MESH_AXES)
  assert not ok


def test_spec_structure_checker_unknown_axis():
  feature = specs.SpecStruct()
  feature["x"] = specs.TensorSpec(shape=(4,), sharding=("bogus",))
  out = spec_check.check_spec_structures(feature, mesh_axes=MESH_AXES)
  assert _rules(out) == {"unknown-mesh-axis"}


def test_sharding_axes_helper():
  struct = specs.SpecStruct()
  struct["a"] = specs.TensorSpec(shape=(4, 2), sharding=(None, "model"))
  struct["b/c"] = specs.TensorSpec(shape=(3,))
  axes = specs.sharding_axes(struct)
  assert dict(axes) == {"a": (None, "model")}


# ---------------------------------------------------------------------------
# CLI contract: exit codes + no backend init.
# ---------------------------------------------------------------------------


def test_cli_nonzero_on_violations(tmp_path, capsys):
  bad_dir = tmp_path / "badcode"
  bad_dir.mkdir()
  (bad_dir / "bad_config.gin").write_text("NopeNotAThing.x = 1\n")
  (bad_dir / "bad_tracer.py").write_text(
      "import jax\n_D = jax.devices()\n")
  (bad_dir / "bad_spec.py").write_text(
      "from tensor2robot_tpu import specs\n"
      "S = specs.TensorSpec(shape=(4,), sharding=('nope',))\n")
  rc = lint.main([str(bad_dir)])
  assert rc == 1
  printed = capsys.readouterr().out
  for rule in ("unknown-configurable", "import-time-backend",
               "unknown-mesh-axis"):
    assert rule in printed, printed


def test_cli_zero_on_clean_file(tmp_path):
  clean = tmp_path / "clean.py"
  clean.write_text("import numpy as np\n\nX = np.zeros(3)\n")
  assert lint.main([str(clean)]) == 0


def test_cli_single_file_sees_repo_axis_vocabulary(tmp_path):
  """Linting one .py must validate sharding against the axes the repo's
  shipped configs declare (e.g. 'sp'), not just DEFAULT_AXES — a
  per-file run may not contradict the full-repo run."""
  model = tmp_path / "model.py"
  model.write_text(
      "from tensor2robot_tpu import specs\n"
      "S = specs.TensorSpec(shape=(4, 4), sharding=('sp', None))\n")
  assert lint.main([str(model)]) == 0


def test_cli_missing_path(tmp_path):
  assert lint.main([str(tmp_path / "nope")]) == 2


def test_cli_unsupported_file_type_is_an_error(tmp_path):
  """An explicitly named non-.py/.gin file must not silently read as
  'clean'."""
  script = tmp_path / "thing.sh"
  script.write_text("echo hi\n")
  assert lint.main([str(script)]) == 2


def test_lint_never_initializes_backend():
  """Acceptance: full-repo lint in a fresh process must create NO jax
  backend. Two independent layers: (a) the child asserts jax's live
  backend cache is still empty after the full run — direct evidence,
  valid even where env-var pinning is unreliable; (b) JAX_PLATFORMS names a nonexistent platform
  so any init that does slip through raises instead of ever touching
  hardware (and the child can therefore never hang mid TPU-client-init,
  making the subprocess timeout safe)."""
  code = """
import sys
from tensor2robot_tpu.analysis import lint
rc = lint.main(["tensor2robot_tpu", "scripts"])
from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("NO_BACKEND_OK")
sys.exit(rc)
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "graftlint_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run(
      [sys.executable, "-c", code],
      capture_output=True, text=True, timeout=600, cwd=REPO_ROOT, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "NO_BACKEND_OK" in result.stdout


def test_package_import_is_backend_free():
  """Regression for the grasp2vec losses import-time jnp.array: every
  package module must import without initializing a backend."""
  code = """
import importlib, pkgutil, sys
import tensor2robot_tpu
skip = {"tensor2robot_tpu.bin", "tensor2robot_tpu.native"}
failed = []
for m in pkgutil.walk_packages(tensor2robot_tpu.__path__, "tensor2robot_tpu."):
    if any(m.name == s or m.name.startswith(s + ".") for s in skip):
        continue  # bins re-define absl flags; native .so is not importable
    try:
        importlib.import_module(m.name)
    except Exception as e:
        failed.append(f"{m.name}: {type(e).__name__}: {e}")
assert not failed, "\\n".join(failed)
from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("OK")
"""
  env = {**os.environ, "PYTHONPATH": REPO_ROOT,
         "JAX_PLATFORMS": "graftlint_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600,
                          cwd=REPO_ROOT, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "OK" in result.stdout


def _make_native_pkg(tmp_path, cc_text, init_text):
  native_dir = tmp_path / "native"
  native_dir.mkdir()
  (native_dir / "x.cc").write_text(cc_text)
  (native_dir / "__init__.py").write_text(init_text)
  return str(native_dir)


def test_native_binding_missing_fires(tmp_path):
  from tensor2robot_tpu.analysis import native_check

  native_dir = _make_native_pkg(
      tmp_path,
      'extern "C" {\n'
      "int64_t t2r_bound(void* h) { return 0; }\n"
      "void* t2r_unbound(void* h) { return h; }\n"
      "}\n",
      "lib.t2r_bound.restype = ctypes.c_int64\n")
  found = native_check.check_native_bindings(native_dir)
  assert _rules(found) == {"native-binding-missing"}
  assert "t2r_unbound" in found[0].message


def test_native_binding_unknown_fires(tmp_path):
  from tensor2robot_tpu.analysis import native_check

  native_dir = _make_native_pkg(
      tmp_path,
      'extern "C" int64_t t2r_bound(void* h) { return 0; }\n',
      "lib.t2r_bound.restype = ctypes.c_int64\n"
      "lib.t2r_typoed.restype = None\n")
  found = native_check.check_native_bindings(native_dir)
  assert _rules(found) == {"native-binding-unknown"}
  assert found[0].line == 2


def test_native_binding_call_sites_and_wildcards_ignored(tmp_path):
  """A C++-side CALL of an exported symbol is not a second export, a
  `hasattr` probe counts as a binding, and prose like `t2r_stager_*`
  or `libt2r_native.so` never registers as a symbol reference."""
  from tensor2robot_tpu.analysis import native_check

  native_dir = _make_native_pkg(
      tmp_path,
      'extern "C" uint32_t t2r_crc(const uint8_t* d, int64_t n);\n'
      'extern "C" {\n'
      "int t2r_probe_only(void* h) { return 0; }\n"
      "uint32_t t2r_crc(const uint8_t* d, int64_t n) {\n"
      "  if (t2r_crc(d, 0)) return t2r_crc(d, 1);\n"
      "  return 0;\n"
      "}\n"
      "}\n",
      '"""Wrapper for libt2r_native.so; see the `t2r_*` exports and the\n'
      "`t2r_probe_*` family.\"\"\"\n"
      "lib.t2r_crc.restype = ctypes.c_uint32\n"
      'if hasattr(lib, "t2r_probe_only"):\n'
      "  pass\n")
  assert native_check.check_native_bindings(native_dir) == []


def test_native_binding_suppression(tmp_path):
  from tensor2robot_tpu.analysis import native_check

  native_dir = _make_native_pkg(
      tmp_path,
      'extern "C" int64_t t2r_bound(void* h) { return 0; }\n',
      "lib.t2r_bound.restype = ctypes.c_int64\n"
      "lib.t2r_gone.restype = None"
      "  # graftlint: disable=native-binding-unknown\n")
  assert native_check.check_native_bindings(native_dir) == []


def test_native_binding_repo_symbols_all_covered():
  """Every real exported symbol is seen by the checker (a regression
  here means the export regex stopped matching the repo's .cc style)."""
  from tensor2robot_tpu.analysis import native_check

  native_dir = os.path.join(REPO_ROOT, "tensor2robot_tpu", "native")
  exported = set()
  for name in os.listdir(native_dir):
    if name.endswith(".cc"):
      exported |= native_check.exported_symbols(
          os.path.join(native_dir, name))
  for symbol in ("t2r_crc32c", "t2r_masked_crc32c", "t2r_reader_open",
                 "t2r_parser_parse_batch", "t2r_parser_gather_plane",
                 "t2r_stager_open", "t2r_stager_next_batch",
                 "t2r_staged_free", "t2r_decode_jpeg_batch"):
    assert symbol in exported, symbol


def test_grasp2vec_quadrant_centers_is_host_constant():
  """The fixed violation stays fixed in-process too: the module constant
  must be a host numpy array, not a device array."""
  from tensor2robot_tpu.research.grasp2vec import losses

  assert type(losses._QUADRANT_CENTERS) is np.ndarray


# ---------------------------------------------------------------------------
# Pallas rule family: pallas-missing-fallback.
# ---------------------------------------------------------------------------


class TestPallasFallbackLint:

  _IMPORT = "from jax.experimental import pallas as pl\n"

  def test_plain_import_is_the_discipline(self):
    """Pallas ships inside the one installed jax: a plain import with an
    `interpret` seam is clean — no try-guarded import is asked for."""
    from tensor2robot_tpu.analysis import pallas_check

    source = (self._IMPORT
              + "out = pl.pallas_call(kernel, interpret=True)(x)\n")
    assert pallas_check.check_python_source("x.py", source) == []

  def test_flags_missing_interpret_seam(self):
    from tensor2robot_tpu.analysis import pallas_check

    source = self._IMPORT + "out = pl.pallas_call(kernel, grid=(4,))(x)\n"
    findings = pallas_check.check_python_source("x.py", source)
    assert len(findings) == 1
    assert findings[0].rule == "pallas-missing-fallback"
    assert "interpret" in findings[0].message

  def test_interpret_argument_or_splat_passes(self):
    from tensor2robot_tpu.analysis import pallas_check

    source = (self._IMPORT
              + "out = pl.pallas_call(kernel, interpret=flag)(x)\n"
              + "out2 = pl.pallas_call(kernel, **kw)(x)\n")
    assert pallas_check.check_python_source("x.py", source) == []

  def test_kernel_free_and_unparseable_modules_pass(self):
    from tensor2robot_tpu.analysis import pallas_check

    assert pallas_check.check_python_source(
        "x.py", "from jax.experimental import pallas as pl\n") == []
    assert pallas_check.check_python_source("x.py", "def broken(:\n") == []

  def test_suppression_honored(self):
    from tensor2robot_tpu.analysis import pallas_check

    source = ("out = pallas_call(kernel)"
              "  # graftlint: disable=pallas-missing-fallback\n")
    raw = pallas_check.check_python_source("p.py", source)
    assert len(raw) == 1  # raw check still sees it
    assert findings_lib.filter_findings(
        raw, findings_lib.load_suppressions(source)) == []

  def test_engine_runs_the_rule(self, tmp_path):
    """Registered in the single-pass engine: a fixture violation
    surfaces through run_engine (catalogued + CHECK_ORDER wired)."""
    bad = tmp_path / "bad_kernel.py"
    bad.write_text("from jax.experimental import pallas as pl\n"
                   "out = pl.pallas_call(kernel)(x)\n")
    result = engine_lib.run_engine([str(tmp_path)])
    assert _rules(result.findings) == {"pallas-missing-fallback"}

  def test_repo_kernel_modules_pin_clean(self):
    """The two shipped kernel tiers ARE the discipline the rule
    enforces — they must stay clean (an interpret seam at every call)."""
    from tensor2robot_tpu.analysis import pallas_check

    for rel in ("ops/attention.py", "ops/decode_kernels.py"):
      path = os.path.join(REPO_ROOT, "tensor2robot_tpu", rel)
      assert pallas_check.check_python_file(path) == [], rel


# ---------------------------------------------------------------------------
# The rule engine (analysis/engine.py): parity, catalog, JSON, baseline,
# incremental cache.
# ---------------------------------------------------------------------------


def _seed_engine_fixtures(tmp_path):
  """A fixture tree dense enough that any ordering, filtering, or
  suppression drift between the engine and the per-checker pipeline
  shows up: several rule families, a multi-finding file, a syntax
  error, a suppressed finding, and a broken config."""
  (tmp_path / "bad_tracer.py").write_text(
      "import time\n"
      "import jax\n"
      "import numpy as np\n"
      "_D = jax.devices()\n"
      "@jax.jit\n"
      "def step(x):\n"
      "  t = time.time()\n"
      "  return float(x)\n")
  (tmp_path / "bad_spec.py").write_text(
      "from tensor2robot_tpu import specs\n"
      "A = specs.TensorSpec(shape=(4,), sharding=('nope',))\n"
      "B = specs.TensorSpec(shape=(4, 4), sharding=('model', 'model'))\n")
  (tmp_path / "bad_syntax.py").write_text("def broken(:\n")
  (tmp_path / "suppressed.py").write_text(
      "import jax\n"
      "_D = jax.devices()  # graftlint: disable=import-time-backend\n")
  (tmp_path / "bad_config.gin").write_text(
      "NopeNotAThing.x = 1\n"
      "train_eval_model.max_train_steps = 'lots'\n")


def _per_checker_pipeline(paths):
  """The pre-engine `lint.run` replicated verbatim (one parse per
  checker per file; the checkers' standalone entry points are
  unchanged). The engine must match it finding-for-finding."""
  py_files, gin_files = engine_lib.discover(list(paths))
  package_dir = os.path.dirname(os.path.abspath(lint.__file__))
  _, repo_gin = engine_lib.discover([os.path.dirname(package_dir)])
  mesh_axes = spec_check.known_mesh_axes(
      sorted(set(gin_files) | set(repo_gin)))
  findings = []
  for path in gin_files:
    findings.extend(config_check.check_config_file(path))
  for path in py_files:
    findings.extend(tracer_check.check_python_file(path))
    findings.extend(spec_check.check_python_file(path, mesh_axes))
    findings.extend(cache_check.check_python_file(path))
    findings.extend(pp_check.check_python_file(path))
    findings.extend(session_check.check_python_file(path))
    findings.extend(fleet_check.check_python_file(path))
    findings.extend(forge_check.check_python_file(path))
    findings.extend(retry_check.check_python_file(path))
    findings.extend(thread_check.check_python_file(path))
    findings.extend(loop_check.check_python_file(path))
    findings.extend(trace_check.check_python_file(path))
    if (os.path.basename(path) == "__init__.py"
        and os.path.basename(os.path.dirname(path)) == "native"):
      findings.extend(native_check.check_native_bindings(
          os.path.dirname(path)))
  return sorted(findings, key=lambda f: (f.path, f.line, f.rule))


def test_engine_parity_on_seeded_fixtures(tmp_path):
  """Tentpole acceptance: the single-parse engine's findings are
  byte-identical to the per-checker pipeline's."""
  _seed_engine_fixtures(tmp_path)
  old = _per_checker_pipeline([str(tmp_path)])
  result = engine_lib.run_engine([str(tmp_path)])
  assert [str(f) for f in result.findings] == [str(f) for f in old]
  # The fixtures seed a dense report — an empty==empty pass proves
  # nothing. parse-error, 4 tracer, 2 spec, 2 config findings; the
  # suppressed one appears on neither side.
  assert len(old) >= 8
  assert "parse-error" in _rules(old)
  assert not any("suppressed.py" in f.path for f in old)
  # One `ast.parse` per .py file (incl. the failed one) — not one per
  # checker per file; .gin goes through the config statement parser.
  assert result.stats["parses"] == 4


def test_engine_parity_on_repo():
  """And over the real tree (both sides empty — test_repo_clean pins
  that — but this pins that the engine discovers the same file set)."""
  old = _per_checker_pipeline(LINT_PATHS)
  result = engine_lib.run_engine(LINT_PATHS)
  assert [str(f) for f in result.findings] == [str(f) for f in old]
  assert result.stats["files"] == (result.stats["py_files"]
                                   + result.stats["gin_files"])
  assert result.stats["parses"] <= result.stats["files"]


def test_engine_suppression_provenance(tmp_path):
  _seed_engine_fixtures(tmp_path)
  result = engine_lib.run_engine([str(tmp_path)])
  supp = [(f, line) for f, line in result.suppressed
          if f.path.endswith("suppressed.py")]
  assert len(supp) == 1
  finding, at_line = supp[0]
  assert finding.rule == "import-time-backend"
  assert at_line == 2


def test_json_output_enriched(tmp_path, capsys):
  _seed_engine_fixtures(tmp_path)
  rc = lint.main(["--json", str(tmp_path)])
  assert rc == 1
  records = [json.loads(line)
             for line in capsys.readouterr().out.splitlines()]
  for record in records:
    assert set(record) >= {"path", "line", "rule", "severity", "message",
                           "suppressed"}
    assert record["severity"] in ("error", "warning")
  suppressed = [r for r in records if r["suppressed"]]
  assert len(suppressed) == 1
  assert suppressed[0]["rule"] == "import-time-backend"
  assert suppressed[0]["suppressed_by"] == 2
  live = [r for r in records if not r["suppressed"]]
  assert live and all("suppressed_by" not in r for r in live)


def test_plain_output_byte_stable(tmp_path, capsys):
  """Existing scripts parse `path:line: [rule] message`; the plain
  printer must not grow fields."""
  _seed_engine_fixtures(tmp_path)
  lint.main([str(tmp_path)])
  out = capsys.readouterr().out
  assert out
  for line in out.splitlines():
    assert ": [" in line, line
    assert line.split(":")[1].isdigit(), line
    assert str(findings_lib.Finding(
        line.split(":")[0], int(line.split(":")[1]),
        line.split("[")[1].split("]")[0],
        line.split("] ", 1)[1])) == line


def test_baseline_round_trip(tmp_path, capsys):
  _seed_engine_fixtures(tmp_path)
  baseline = tmp_path / "baseline.json"
  assert lint.main(["--write-baseline", str(baseline), str(tmp_path)]) == 0
  capsys.readouterr()
  # Everything baselined: clean.
  assert lint.main(["--baseline", str(baseline), str(tmp_path)]) == 0
  assert capsys.readouterr().out == ""
  # A NEW violation still gates.
  (tmp_path / "new_bad.py").write_text("import jax\n_D = jax.devices()\n")
  assert lint.main(["--baseline", str(baseline), str(tmp_path)]) == 1
  out = capsys.readouterr().out
  assert "new_bad.py" in out and "bad_tracer.py" not in out


def test_baseline_fingerprint_survives_line_drift(tmp_path):
  _seed_engine_fixtures(tmp_path)
  findings = engine_lib.run_engine([str(tmp_path)]).findings
  fingerprints = {engine_lib.finding_fingerprint(f) for f in findings}
  # Shift bad_tracer.py down two lines; fingerprints must not move.
  bad = tmp_path / "bad_tracer.py"
  bad.write_text("\n\n" + bad.read_text())
  shifted = engine_lib.run_engine([str(tmp_path)]).findings
  assert {engine_lib.finding_fingerprint(f) for f in shifted} == fingerprints


def test_incremental_cache_and_changed_only(tmp_path, capsys):
  _seed_engine_fixtures(tmp_path)
  cache = tmp_path / "cache.json"
  first = engine_lib.run_engine([str(tmp_path)], cache_path=str(cache))
  assert first.stats["cache_hits"] == 0
  # Warm: every .py served from cache, findings identical.
  second = engine_lib.run_engine([str(tmp_path)], cache_path=str(cache))
  assert second.stats["cache_hits"] >= 4
  assert ([str(f) for f in second.findings]
          == [str(f) for f in first.findings])
  # --changed-only: nothing moved -> nothing reported, exit 0.
  rc = lint.main(["--cache-file", str(cache), "--changed-only",
                  str(tmp_path)])
  assert rc == 0
  capsys.readouterr()
  # Touch ONE file -> only its findings come back.
  bad = tmp_path / "bad_spec.py"
  bad.write_text(bad.read_text() + "\n# touched\n")
  rc = lint.main(["--cache-file", str(cache), "--changed-only",
                  str(tmp_path)])
  assert rc == 1
  out = capsys.readouterr().out
  assert "bad_spec.py" in out and "bad_tracer.py" not in out


def test_changed_only_requires_cache_file(tmp_path):
  assert lint.main(["--changed-only", str(tmp_path)]) == 2


def test_cache_invalidated_by_vocab_change(tmp_path):
  """The cache stamp includes the mesh-axis vocabulary: a config
  declaring a new axis must re-validate cached spec findings."""
  (tmp_path / "model.py").write_text(
      "from tensor2robot_tpu import specs\n"
      "S = specs.TensorSpec(shape=(4, 4), sharding=('zz', None))\n")
  cache = tmp_path / "cache.json"
  first = engine_lib.run_engine([str(tmp_path)], cache_path=str(cache))
  assert _rules(first.findings) == {"unknown-mesh-axis"}
  (tmp_path / "mesh.gin").write_text(
      "train_eval_model.mesh_axis_names = ('data', 'zz')\n")
  second = engine_lib.run_engine([str(tmp_path)], cache_path=str(cache))
  assert second.stats["cache_hits"] == 0  # stamp moved, full re-run
  assert not second.findings


def test_stats_and_runs_telemetry(tmp_path):
  from tensor2robot_tpu.obs import runlog

  runs = tmp_path / "runs.jsonl"
  (tmp_path / "clean.py").write_text("X = 1\n")
  rc = lint.main(["--runs", str(runs), str(tmp_path / "clean.py")])
  assert rc == 0
  records = [json.loads(line) for line in
             runs.read_text().splitlines()]
  assert len(records) == 1
  bench = records[0]["bench"]
  assert bench["name"] == "lint"
  assert bench["lint_parse_ms"] >= 0 and bench["lint_rules_ms"] >= 0
  assert records[0]["extra"]["lint"]["files"] == 1
  # The diff gate knows these metrics.
  assert "lint_parse_ms" in runlog.DEFAULT_THRESHOLDS
  assert "lint_rules_ms" in runlog.DEFAULT_THRESHOLDS
  metrics = runlog.key_metrics(records[0])
  assert set(metrics) == {"lint_parse_ms", "lint_rules_ms"}


def test_catalog_single_source_of_truth(capsys):
  """--list-rules, docs/ARCHITECTURE.md, and the registry agree. The
  docs table is generated (see the marker comments) — regenerate with
  engine.catalog_markdown() after touching any RuleInfo."""
  engine_lib.load_builtin_rules()
  assert lint.main(["--list-rules"]) == 0
  listed = capsys.readouterr().out
  for info in engine_lib.rule_infos():
    assert info.id in listed, info.id
  doc = open(os.path.join(REPO_ROOT, "docs", "ARCHITECTURE.md")).read()
  begin = doc.index("<!-- graftlint-catalog:begin -->")
  end = doc.index("<!-- graftlint-catalog:end -->")
  table = doc[begin + len("<!-- graftlint-catalog:begin -->"):end].strip()
  assert table == engine_lib.catalog_markdown().strip()


def test_parse_error_is_unsuppressible(tmp_path):
  (tmp_path / "bad.py").write_text(
      "def broken(:  # graftlint: disable=parse-error\n")
  findings = engine_lib.run_engine([str(tmp_path)]).findings
  assert _rules(findings) == {"parse-error"}


@pytest.fixture(autouse=True)
def _clean_config():
  config.clear_config()
  yield
  config.clear_config()
