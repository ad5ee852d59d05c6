"""`obs/xray.py`'s op table: the program's names (phase, declared scope,
module path) for the instructions of a compiled step, the reduction of a
device's op line under it, where it is kept, and the scopes it reads.

Everything compiles on the CPU at tiny sizes: the names come from JAX's
`op_name`, which is the same on every backend; which instructions XLA fuses is
not, so fusions are checked on text written by hand."""

import contextlib
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.hooks import profiler as profiler_hook
from tensor2robot_tpu.obs import excache
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.obs import xray
from tensor2robot_tpu.utils import config

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, "tensor2robot_tpu")


@pytest.fixture(autouse=True)
def _hermetic_state():
  with metrics_lib.isolated():
    trace_lib.clear()
    trace_lib.disable()
    xray.clear_records()
    config.clear_config()
    yield
  trace_lib.clear()
  trace_lib.disable()
  xray.clear_records()
  config.clear_config()


# ---------------------------------------------------------------------------
# The rule: phase, scope and path from an `op_name`.
# ---------------------------------------------------------------------------

ROOT = "jit(t2r_train_step)"
DECODER = "transpose(jvp(_HybridDecoder))/loss/jvp(_HybridDecoder)/checkpoint"


@pytest.mark.parametrize("op_name,expected", [
    # the four cells' real paths (JAX 0.9.0, compiled for a described v5e)
    (f"{ROOT}/loss/jvp(_HybridDecoder)/layer_0/mixer/ssm_scan/closed_call/while",
     ("forward", "ssm_scan", "layer_0/mixer")),
    (f"{ROOT}/loss/{DECODER}/rematted_computation/layer_3/moe/moe_route/"
     "router/dot_general", ("recompute", "moe_route", "layer_3/moe/router")),
    (f"{ROOT}/loss/{DECODER}/layer_1/moe/moe_experts/cond/branch_0_fun/"
     "jit(_tgmm)/grouped_matmul_t/while/body/dot_general",
     ("backward", "moe_experts", "layer_1/moe/grouped_matmul_t")),
    # the short convolution's kernels: the custom call carries its scope
    (f"{ROOT}/loss/jvp(_HybridDecoder)/layer_0/mixer/gdn_conv/jit(_forward)/"
     "short_conv/pallas_call",
     ("forward", "gdn_conv", "layer_0/mixer/short_conv")),
    (f"{ROOT}/loss/{DECODER}/rematted_computation/layer_4/mixer/ssm_conv/"
     "jit(_forward)/short_conv/pallas_call",
     ("recompute", "ssm_conv", "layer_4/mixer/short_conv")),
    (f"{ROOT}/loss/{DECODER}/layer_2/mixer/gdn_conv/jit(_backward)/"
     "short_conv_bwd/pallas_call",
     ("backward", "gdn_conv", "layer_2/mixer/short_conv_bwd")),
    (f"{ROOT}/loss/transpose(jvp(lm_loss))/while/body/closed_call/checkpoint/"
     "rematted_computation/jit(take_along_axis)/gather",
     ("recompute", "lm_loss", "")),
    (f"{ROOT}/loss/jvp(lm_loss)/while/body/closed_call/dot_general",
     ("forward", "lm_loss", "")),
    (f"{ROOT}/loss/jvp(_HybridDecoder)/embed/jit(_take)/gather",
     ("forward", "loss", "embed")),
    # the primitive `transpose` is no `transpose(`: a recomputed relayout
    (f"{ROOT}/loss/{DECODER}/rematted_computation/layer_0/mixer/ssm_scan/"
     "transpose", ("recompute", "ssm_scan", "layer_0/mixer")),
    (f"{ROOT}/loss/{DECODER}/layer_0/mixer/ssm_scan/transpose",
     ("backward", "ssm_scan", "layer_0/mixer")),
    (f"{ROOT}/optimizer/mul", ("optimizer", "optimizer", "")),
    (f"{ROOT}/ema/add", ("ema", "ema", "")),
    (f"{ROOT}/metrics/reduce_sum", ("other", "metrics", "")),
    (f"{ROOT}/add", ("other", "", "")),
    ("state.params['head']", ("other", "", "")),
    # two names joined by XLA: the first counts
    (f"{ROOT}/loss/jvp(_HybridDecoder)/layer_0/mixer/ssm_scan/transpose;"
     f"{ROOT}/optimizer/mul", ("forward", "ssm_scan", "layer_0/mixer")),
    # a scope may hold a slash inside its autodiff wrapper
    (f"{ROOT}/loss/jvp(a/b)/norm_final/mul", ("forward", "loss", "norm_final")),
])
def test_classify_op_name(op_name, expected):
  assert xray.classify_op_name(op_name) == expected


# ---------------------------------------------------------------------------
# The table of a compiled step.
# ---------------------------------------------------------------------------


def _tiny_step():
  """A jitted step with the train step's shape: `value_and_grad` over a
  `jax.checkpoint`ed forward under `loss`, then `optimizer`, `ema` and
  `metrics`, with two of the layers' scopes inside the forward."""
  import optax

  def forward(params, x):
    with jax.named_scope("moe_route"):
      h = jnp.tanh(x @ params["w1"])
    with jax.named_scope("moe_experts"):
      return jnp.sin(h @ params["w2"])

  forward = jax.checkpoint(forward)

  def step(params, ema, x):
    def loss_fn(p):
      return jnp.mean(forward(p, x) ** 2)

    with jax.named_scope("loss"):
      loss, grads = jax.value_and_grad(loss_fn)(params)
    with jax.named_scope("optimizer"):
      new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
    with jax.named_scope("ema"):
      ema = jax.tree_util.tree_map(lambda e, p: 0.9 * e + 0.1 * p, ema, new)
    with jax.named_scope("metrics"):
      norm = optax.global_norm(grads)
    return new, ema, loss, norm

  step.__name__ = step.__qualname__ = "tiny_step"
  rng = np.random.RandomState(0)
  params = {"w1": jnp.asarray(rng.randn(16, 32), jnp.float32),
            "w2": jnp.asarray(rng.randn(32, 8), jnp.float32)}
  x = jnp.asarray(rng.randn(4, 16), jnp.float32)
  return jax.jit(step), (params, dict(params), x)


def _entries(table):
  return [xray.op_entry(table, name) for name in table["ops"]]


def test_table_of_a_tiny_step_holds_every_phase_with_its_scope():
  fn, args = _tiny_step()
  compiled, record = xray.analyze_jit("tiny_step", fn, *args)
  table = xray.op_scopes("tiny_step")
  assert table["module"] == "jit_tiny_step" and table["executable"] == \
      "tiny_step"
  found = {(e["phase"], e["scope"]) for e in _entries(table)}
  # (the CPU's compiler merges the forward's first product with the
  # recomputed one and keeps the latter's name: the chip's does not)
  assert {("forward", "moe_experts"), ("recompute", "moe_route"),
          ("backward", "moe_experts"), ("backward", "moe_route"),
          ("optimizer", "optimizer"), ("ema", "ema"),
          ("other", "metrics")} <= found
  dots = {(e["phase"], e["scope"]) for e in _entries(table)
          if e["opcode"] == "dot"}
  assert ("recompute", "moe_route") in dots
  assert ("backward", "moe_experts") in dots
  assert {p for p, _, _ in table["paths"]} <= set(xray.PHASES)
  assert {s for _, s, _ in table["paths"]} <= set(xray.DEVICE_SCOPES) | {""}
  # the record never gets the table
  assert len(table["ops"]) > 10
  assert "ops" not in record and "paths" not in record
  assert json.dumps(record)
  assert "tanh" in compiled.as_text()
  xray.clear_records()
  assert xray.op_scopes("tiny_step") is None


HAND_TEXT = """HloModule jit_t2r_train_step, is_scheduled=true

%fused_computation.1 (p.0: f32[8], p.1: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  %p.1 = f32[8]{0} parameter(1)
  %multiply.1 = f32[8]{0} multiply(%p.0, %p.1), metadata={op_name="jit(t2r_train_step)/loss/transpose(jvp(Net))/loss/jvp(Net)/checkpoint/dense_1/mul" stack_frame_id=3}
  ROOT %subtract.2 = f32[8]{0} subtract(%p.0, %multiply.1), metadata={op_name="jit(t2r_train_step)/optimizer/sub"}
}

%fused_computation.2 (p.2: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  %constant.5 = f32[] constant(2)
  %broadcast.6 = f32[8]{0} broadcast(%constant.5), dimensions={}
  ROOT %multiply.7 = f32[8]{0} multiply(%p.2, %broadcast.6), metadata={op_name="jit(t2r_train_step)/loss/jvp(Net)/dense_0/gdn_scan/mul"}
}

%body.3 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.8 = f32[8]{0} get-tuple-element(%t), index=1
  %tanh.9 = f32[8]{0} tanh(%get-tuple-element.8), metadata={op_name="jit(t2r_train_step)/loss/jvp(Net)/dense_0/gdn_scan/while/body/tanh"}
  %copy.10 = f32[8]{0} copy(%tanh.9)
  ROOT %tuple.11 = (s32[], f32[8]{0}) tuple(%get-tuple-element.8, %copy.10)
}

ENTRY %main.4 (a: f32[8], b: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="state.params['w']"}
  %b = f32[8]{0} parameter(1)
  %copy-start.12 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  %copy-done.13 = f32[8]{0} copy-done(%copy-start.12)
  %fusion.14 = f32[8]{0} fusion(%copy-done.13), kind=kLoop, calls=%fused_computation.2
  %while.15 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond.9, body=%body.3, metadata={op_name="jit(t2r_train_step)/loss/jvp(Net)/dense_0/gdn_scan/while"}
  %copy.16 = f32[8]{0} copy(%fusion.14)
  %pad.17 = f32[8]{0} pad(%copy.16), metadata={op_name="gather"}
  ROOT %fusion.18 = f32[8]{0} fusion(%pad.17, %b), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(t2r_train_step)/loss/transpose(jvp(Net))/loss/jvp(Net)/checkpoint/dense_1/mul"}
}
"""


def test_table_from_text_written_by_hand():
  table = xray.build_op_table(HAND_TEXT, executable="train_step")
  assert table["module"] == "jit_t2r_train_step"
  entry = lambda name: xray.op_entry(table, name)  # noqa: E731
  # a fusion of two phases reads `mixed`, under its own name's phase
  assert entry("fusion.18")["phases"] == "mixed"
  assert entry("fusion.18")["fused_phases"] == ["backward", "optimizer"]
  assert entry("fusion.18")["phase"] == "backward"
  assert (entry("fusion.18")["scope"], entry("fusion.18")["path"]) == (
      "loss", "dense_1")
  # a fusion XLA gave no name takes its parts': one phase, their scope
  assert entry("fusion.14") == {
      "name": "fusion.14", "opcode": "fusion", "phase": "forward",
      "scope": "gdn_scan", "path": "dense_0", "phases": "forward"}
  # loop bodies and fused computations are in it, under their own names
  assert entry("tanh.9")["scope"] == "gdn_scan"
  assert entry("multiply.1")["phase"] == "backward"
  assert entry("while.15")["opcode"] == "while"
  # no name of its own: the names of what made its first operand
  assert entry("copy.10")["scope"] == "gdn_scan"       # <- tanh.9
  assert entry("copy.16")["path"] == "dense_0"          # <- fusion.14
  assert entry("pad.17")["phase"] == "forward"          # a bare `gather`
  # ... and none where that is a parameter
  assert entry("copy-done.13")["phase"] == "other"
  assert entry("copy-done.13")["opcode"] == "copy-done"
  # what is no work is not in it; a trace's whole text finds its entry
  for name in ("a", "p.0", "constant.5", "tuple.11", "get-tuple-element.8"):
    assert entry(name) is None
  assert entry("%while.15 = (s32[], f32[8]{0}) while(%tuple.0), body=%b")[
      "name"] == "while.15"
  assert json.loads(json.dumps(table)) == table


# ---------------------------------------------------------------------------
# The reduction.
# ---------------------------------------------------------------------------


def test_device_time_by_scope_on_a_list_written_by_hand():
  table = xray.build_op_table(HAND_TEXT, executable="train_step")
  ms = 1e6
  ops = [
      ("%fusion.14 = f32[8]{0} fusion(%copy-done.13), kind=kLoop", 0, 2 * ms),
      ("%while.15 = (s32[], f32[8]{0}) while(%tuple.0)", 2 * ms, 10 * ms),
      ("%tanh.9 = f32[8]{0} tanh(%x)", 3 * ms, 4 * ms),        # in the loop
      ("%copy.10 = f32[8]{0} copy(%tanh.9)", 7 * ms, 1 * ms),  # in the loop
      ("%copy.16 = f32[8]{0} copy(%fusion.14)", 12 * ms, 3 * ms),
      ("%fusion.18 = f32[8]{0} fusion(%pad.17, %b)", 15 * ms, 4 * ms),
      ("%copy-done.13 = f32[8]{0} copy-done(%copy-start.12)", 19 * ms, ms),
      ("%late.99 = f32[8]{0} add(%a, %b)", 20 * ms, 2 * ms),   # no entry
      ("%fusion.14 = f32[8]{0} fusion(%p)", 50 * ms, 5 * ms),  # other module
  ]
  modules = [("jit_t2r_train_step(1)", 0, 23 * ms),
             ("jit_other(2)", 50 * ms, 5 * ms)]
  out = xray.device_time_by_scope(ops, table, module_events=modules)
  assert out["steps"] == 1 and out["ops"] == 6
  assert out["total_s"] == pytest.approx(0.022)
  assert out["by_phase"] == {
      "forward": pytest.approx(0.015), "recompute": 0.0,
      "backward": pytest.approx(0.004), "optimizer": 0.0, "ema": 0.0,
      "other": pytest.approx(0.001)}
  assert out["shared_by_phase"] == dict(
      out["by_phase"], optimizer=pytest.approx(0.004))
  assert sum(out["by_phase"].values()) + out["unknown_s"] == pytest.approx(
      out["total_s"])
  assert out["by_scope"] == {"gdn_scan": pytest.approx(0.015),
                             "loss": pytest.approx(0.004),
                             "": pytest.approx(0.001)}
  assert out["copy_by_phase"] == {"forward": pytest.approx(0.003),
                                  "other": pytest.approx(0.001)}
  assert out["unknown"] == {"late.99": pytest.approx(0.002)}
  assert out["unscoped_s"] == pytest.approx(0.001)
  assert out["mixed_s"] == pytest.approx(0.004)
  assert out["mixed_by_phases"] == {
      "backward+optimizer": pytest.approx(0.004)}
  heaviest = out["groups"][0]
  assert (heaviest["phase"], heaviest["scope"], heaviest["path"]) == (
      "forward", "gdn_scan", "dense_0")
  assert heaviest["seconds"] == pytest.approx(0.015)
  assert heaviest["copy_s"] == pytest.approx(0.003) and heaviest["ops"] == 3
  assert out["top_ops"][0]["name"] == "while.15"
  # no execution of the table's module: nothing counts
  none = xray.device_time_by_scope(ops, table, [])
  assert none["steps"] == 0 and none["ops"] == 0 and none["total_s"] == 0
  text = "\n".join(xray.format_device_scopes(out))
  assert "phase forward" in text and "while.15" in text and "late.99" in text
  assert json.loads(json.dumps(out)) == out


# ---------------------------------------------------------------------------
# Where the table is kept.
# ---------------------------------------------------------------------------


def test_table_survives_a_store_and_a_load(tmp_path):
  cache = excache.ExecutableCache(str(tmp_path / "exc"))
  fn, args = _tiny_step()
  trace_lib.enable()
  _, cold = xray.analyze_jit("tiny_step", fn, *args, cache=cache)
  table = xray.op_scopes("tiny_step")
  assert cold["cache"]["stored"] and table is not None
  assert cache.load_op_scopes(cold["cache"]["key"]) == table
  xray.clear_records()
  compiled, warm = xray.analyze_jit("tiny_step", fn, *args, cache=cache)
  assert warm["cache"]["hit"]
  assert xray.op_scopes("tiny_step") == table
  spans = [e["name"] for e in trace_lib.get_tracer().events()
           if e["name"].startswith("xray/")]
  assert spans == ["xray/trace", "xray/lower", "xray/compile",
                   "xray/op_scopes", "xray/trace", "xray/cache_load",
                   "xray/op_scopes"]
  np.testing.assert_allclose(np.asarray(compiled(*args)[2]),
                             np.asarray(fn(*args)[2]), rtol=1e-6)
  # an entry of before the tables (cache version 4) has another key: it
  # misses once and no table is ever paired with another's executable
  assert excache.CACHE_VERSION == 5
  # evicting the entry takes the table with it
  assert cache.evict(key=cold["cache"]["key"]) == 1
  assert os.listdir(cache.directory) == []


def test_hit_on_an_entry_without_a_table_does_not_fail(tmp_path,
                                                      monkeypatch):
  """`store` writes the table with the entry, so a hit finds one; where
  it is lost, or was never built, the call goes through without one."""
  cache = excache.ExecutableCache(str(tmp_path / "exc"))
  fn, args = _tiny_step()
  _, cold = xray.analyze_jit("tiny_step", fn, *args, cache=cache)
  key = cold["cache"]["key"]
  # a file that is no table reads as none
  with open(os.path.join(cache.directory, key + ".ops"), "w") as f:
    f.write("{\"not\": \"a table\"")
  assert cache.load_op_scopes(key) is None
  os.unlink(os.path.join(cache.directory, key + ".ops"))
  xray.clear_records()
  compiled, warm = xray.analyze_jit("tiny_step", fn, *args, cache=cache)
  assert warm["cache"]["hit"]
  assert xray.op_scopes("tiny_step") is None
  assert float(compiled(*args)[2]) == pytest.approx(float(fn(*args)[2]))
  # a cold compile whose table cannot be built: counted, stored without
  xray.clear_records()
  monkeypatch.setattr(xray, "build_op_table", lambda *a, **k: 1 / 0)
  cache = excache.ExecutableCache(str(tmp_path / "exc2"))
  compiled, cold = xray.analyze_jit("tiny_step", fn, *args, cache=cache)
  assert cold["cache"]["stored"] and xray.op_scopes("tiny_step") is None
  assert metrics_lib.snapshot()["counter/xray/analyze_failures"] == 1.0
  assert cache.load_op_scopes(cold["cache"]["key"]) is None
  assert float(compiled(*args)[2]) == pytest.approx(float(fn(*args)[2]))


# ---------------------------------------------------------------------------
# The declared scopes.
# ---------------------------------------------------------------------------


def test_every_named_scope_is_declared_and_every_declared_one_is_opened():
  literal = re.compile(r"""named_scope\(\s*["']([^"']+)["']\s*\)""")
  opened = {}
  for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"), recursive=True):
    with open(path) as f:
      for name in literal.findall(f.read()):
        opened.setdefault(name, []).append(os.path.relpath(path, PACKAGE))
  opened.pop("...", None)  # docstrings that speak of `named_scope("...")`
  assert set(opened) == set(xray.DEVICE_SCOPES), opened
  assert len(set(xray.DEVICE_SCOPES)) == len(xray.DEVICE_SCOPES)
  # and none is opened by a name computed at run time
  computed = re.compile(r"named_scope\(\s*[^\"'\s)]")
  for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"), recursive=True):
    with open(path) as f:
      assert not computed.search(f.read()), path


def _strip_metadata(text: str) -> str:
  """The program without its names: no `metadata={...}` on an instruction
  and no tables of files, functions and stack frames under the header."""
  text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
  text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n\n", text,
                flags=re.DOTALL)
  # An instruction's name ends in a number from a counter that the names
  # move (g44: one `convert.379` for `convert.377`, same operands): every
  # name becomes its rank of first appearance, which keeps the wiring.
  ranks = {}
  return re.sub(r"%[\w.\-]+",
                lambda m: "%" + str(ranks.setdefault(m.group(0), len(ranks))),
                text)


def _compiled_step_text(config_file, bindings, batch):
  """The compiled text of a shipped configuration's train step at tiny
  sizes, on one CPU device, lowered as `test_mosaic_lowering.py` lowers
  the shipped steps for the chip."""
  from tests import test_mosaic_lowering as lowering

  model, _ = lowering._model_from_config(
      os.path.relpath(os.path.join(REPO_ROOT, config_file), PACKAGE),
      bindings)
  mesh = lowering._trainer_mesh(jax.devices()[:1])
  return lowering._lower_step_for_mesh(
      model, mesh, batch, donate=True).compile().as_text()


def _cell_files():
  with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
    benchmark = json.load(f)
  configs = {c["name"]: c["file"] for c in benchmark["configs"]}
  for cell in benchmark["workloads"]:
    yield cell["name"], configs[cell["config"]], cell["traffic"]


@pytest.mark.parametrize("cell", [c for c, _, _ in _cell_files()])
def test_scopes_change_metadata_only(cell, monkeypatch):
  """The train step of each benchmark configuration, at its traffic file's
  `tiny` sizes, compiles to the same text with `jax.named_scope` doing
  nothing, once `metadata={...}` is stripped: a scope names ops, it moves
  none. (Flax names its modules through the same call; they go too.)"""
  _, config_file, traffic = next(c for c in _cell_files() if c[0] == cell)
  with open(os.path.join(REPO_ROOT, config_file)) as f:
    cell_config = json.load(f)
  with open(os.path.join(REPO_ROOT, "benchmarks", "traffic",
                         traffic + ".json")) as f:
    mix = json.load(f)
  bindings = (cell_config.get("bindings", []) + mix.get("bindings", [])
              + mix["tiny"]["bindings"])
  args = (cell_config["gin_files"][0], bindings, mix["tiny"]["batch_size"])
  # jax's compilation cache keys a program without its metadata, so the
  # second compile would come back as the first: off for this test.
  from jax.experimental.compilation_cache import compilation_cache

  was = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  try:
    with_scopes = _compiled_step_text(*args)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = _compiled_step_text(*args)
  finally:
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
  assert 'op_name="jit(t2r_train_step)/optimizer/' in with_scopes
  assert 'op_name="jit(t2r_train_step)/metrics/' in with_scopes
  assert "/optimizer/" not in without and "/metrics/" not in without
  assert _strip_metadata(with_scopes) == _strip_metadata(without)


# ---------------------------------------------------------------------------
# The operator's file.
# ---------------------------------------------------------------------------


def test_profiler_hook_leaves_device_scopes_beside_its_trace(
    tmp_path, monkeypatch, capsys):
  """`ProfilerHook` reduces the trace it wrote by the step's table and
  `graftscope` prints the file. The CPU's trace has no device plane, so the
  op line is handed in; with none, the hook writes nothing."""
  from tensor2robot_tpu.bin import graftscope

  table = xray.build_op_table(HAND_TEXT, executable="train_step")
  trace_dir = str(tmp_path / "model" / "profile")
  os.makedirs(trace_dir)
  assert profiler_hook.write_device_scopes(trace_dir) is None   # no trace
  ms = 1e6
  lines = {"modules": [("jit_t2r_train_step(1)", 0, 20 * ms),
                       ("jit_t2r_train_step(1)", 30 * ms, 20 * ms),
                       ("jit_other(2)", 60 * ms, 30 * ms)],
           "ops": [("%while.15 = (s32[]) while(%t)", 0, 12 * ms),
                   ("%fusion.18 = f32[8] fusion(%p)", 12 * ms, 8 * ms),
                   ("%while.15 = (s32[]) while(%t)", 30 * ms, 12 * ms),
                   ("%fusion.18 = f32[8] fusion(%p)", 42 * ms, 8 * ms)]}
  monkeypatch.setattr(xray, "read_device_lines", lambda d: lines)
  assert profiler_hook.write_device_scopes(trace_dir) is None   # no table
  monkeypatch.setattr(xray, "op_scopes",
                      lambda name: table if name == "train_step" else None)
  path = profiler_hook.write_device_scopes(trace_dir)
  assert path == os.path.join(trace_dir, profiler_hook.DEVICE_SCOPES_FILE)
  with open(path) as f:
    reduced = json.load(f)
  assert reduced["steps"] == 2 and reduced["module"] == "jit_t2r_train_step"
  assert reduced["by_phase"]["forward"] == pytest.approx(0.024)
  assert reduced["by_phase"]["backward"] == pytest.approx(0.016)
  assert reduced["shared_by_phase"]["optimizer"] == pytest.approx(0.016)
  report = graftscope.build_report(str(tmp_path / "model"))
  assert "device_scopes.json" in report
  assert "phase forward" in report and "12.000" in report   # ms a step
  assert "while.15" in report


def test_profiler_hook_waits_for_the_device_at_both_ends(tmp_path,
                                                         monkeypatch):
  """The host runs ahead of the chip: the hook's trace holds the steps it
  names only if the device has finished what was dispatched when the trace
  starts and when it stops."""
  calls = []
  monkeypatch.setattr(jax.profiler, "start_trace",
                      lambda log_dir: calls.append("start"))
  monkeypatch.setattr(jax.profiler, "stop_trace",
                      lambda: calls.append("stop"))
  monkeypatch.setattr(profiler_hook, "write_device_scopes",
                      lambda trace_dir: calls.append("reduce"))

  class Ctx:
    model_dir = str(tmp_path)

    def get_state(self):
      calls.append("barrier")
      return jnp.zeros(())

  hook = profiler_hook.ProfilerHook(start_step=2, num_steps=3)
  for step in range(1, 8):
    hook.after_step(Ctx(), step, {})
  hook.end(Ctx())
  assert calls == ["barrier", "start", "barrier", "stop", "reduce"]
