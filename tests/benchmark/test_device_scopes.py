"""The phase and scope readers on a run written by hand (a device's op line
and an op table), without a table, and on what a rehearsal of a cell leaves
behind; and their entries in `BENCHMARK.json`."""

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import device_scopes
from tests.benchmark.rehearse import rehearse

D0, D1 = "/device:TPU:0", "/device:TPU:1"
STEP = "jit_t2r_train_step"
MS = 1e6  # ns

PHASE_METRICS = ["step_forward_ms", "step_recompute_ms", "step_backward_ms",
                 "step_optimizer_ms", "step_unscoped_share"]
SCOPE_METRICS = ["scope_gdn_scan_ms", "scope_ssm_scan_ms",
                 "scope_moe_route_ms", "scope_moe_experts_ms",
                 "scope_attn_ms", "scope_lm_loss_ms"]
SPAN_METRICS = ["step_trace_s", "step_compile_s"]
NEW_METRICS = PHASE_METRICS + SCOPE_METRICS + SPAN_METRICS
EXPERT_CELLS = ["qwen3next_train_T4096", "nemotron3nano_train_T4096"]
EVERY_CELL = ["g44_train_b256", "seq_train_T2048"] + EXPERT_CELLS
# name -> (unit, layer, the cells it is listed for); `moves` follows the
# layer: the compile cache moves `setup_s`, the others `examples_per_s`.
LISTED = {
    "step_forward_ms": ("ms", "step program", EVERY_CELL),
    "step_backward_ms": ("ms", "step program", EVERY_CELL),
    "step_unscoped_share": ("%", "step program", EVERY_CELL),
    "step_recompute_ms": ("ms", "step program", EXPERT_CELLS),
    "step_optimizer_ms": ("ms", "step program", EXPERT_CELLS),
    "scope_lm_loss_ms": ("ms", "step program", EXPERT_CELLS),
    "scope_gdn_scan_ms": ("ms", "linear attention", EXPERT_CELLS[:1]),
    "scope_ssm_scan_ms": ("ms", "state space", EXPERT_CELLS[1:]),
    "scope_moe_route_ms": ("ms", "experts", EXPERT_CELLS),
    "scope_moe_experts_ms": ("ms", "experts", EXPERT_CELLS),
    "scope_attn_ms": ("ms", "kernels", EXPERT_CELLS),
    "step_trace_s": ("s", "compile cache", EVERY_CELL),
    "step_compile_s": ("s", "compile cache", EVERY_CELL),
}


def _table():
  """Instruction -> [opcode, path index, fused phases], as
  `xray.build_op_table` writes it."""
  paths = [["forward", "ssm_scan", "layer_0/mixer"],        # 0
           ["forward", "moe_route", "layer_1/moe"],         # 1
           ["recompute", "moe_experts", "layer_1/moe"],     # 2
           ["backward", "attn_plain", "layer_2/mixer"],     # 3
           ["backward", "lm_loss", ""],                     # 4
           ["optimizer", "optimizer", ""],                  # 5
           ["ema", "ema", ""],                              # 6
           ["other", "metrics", ""],                        # 7
           ["other", "", ""],                               # 8
           ["forward", "gdn_scan", "layer_3/mixer"],        # 9
           ["forward", "attn_gated", "layer_3/mixer"]]      # 10
  ops = {"while.7": ["while", 0, None], "fusion.1": ["fusion", 0, ["forward"]],
         "sort.2": ["sort", 1, None], "grouped_matmul.3": ["custom-call", 2, None],
         "copy.4": ["copy", 2, None], "flash_bwd.5": ["custom-call", 3, None],
         "while.8": ["while", 4, None],
         "fusion.9": ["fusion", 5, ["backward", "optimizer"]],
         "fusion.10": ["fusion", 6, ["ema"]], "fusion.11": ["fusion", 7, None],
         "add.12": ["add", 8, None], "gdn_inverse.13": ["custom-call", 9, None],
         "flash_fwd.14": ["custom-call", 10, None]}
  return {"executable": "train_step", "module": STEP,
          "paths": paths, "ops": ops}


def _step_events(t0):
  """One execution of the step from `t0` ms: (name, start ms, ms). 39 ms of
  top-level ops in a 40 ms module; `fusion.1` runs inside `while.7`."""
  return [
      ("%while.7 = (s32[], f32[1,8]) while(%tuple.1), body=%b", 0, 5),
      ("%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop", 1, 2),     # nested
      ("%sort.2 = (f32[4096]) sort(%a, %b)", 5, 3),
      ("%grouped_matmul.3 = f32[8] custom-call(%a)", 8, 4),
      ("%copy.4 = f32[8]{0} copy(%x)", 12, 1),
      ("%flash_bwd.5 = bf16[8] custom-call(%q)", 13, 6),
      ("%while.8 = (s32[]) while(%tuple.2), body=%c", 19, 2),
      ("%fusion.9 = f32[8]{0} fusion(%g), kind=kLoop", 21, 7),
      ("%fusion.10 = f32[8]{0} fusion(%p), kind=kLoop", 28, 3),
      ("%fusion.11 = f32[] fusion(%g), kind=kInput", 31, 2),
      ("%add.12 = s32[] add(%step, %one)", 33, 1),
      ("%gdn_inverse.13 = f32[8] custom-call(%a)", 34, 2),
      ("%flash_fwd.14 = bf16[8] custom-call(%q)", 36, 1),
      ("%mystery.15 = f32[8] custom-call(%q)", 37, 2),             # no entry
  ], t0


def _run(table=True, steps=2):
  events = []
  for i in range(steps):
    ops, t0 = _step_events(100.0 * i)
    events.append((D0, tr.MODULE_LINE, f"{STEP}(7)", t0 * MS, 40 * MS))
    events += [(D0, tr.OPS_LINE, name, (t0 + start) * MS, ms * MS)
               for name, start, ms in ops]
    # another chip's line, and another program's ops on this one
    events += [(D1, tr.OPS_LINE, name, (t0 + start) * MS, ms * MS)
               for name, start, ms in ops]
  events.append((D0, tr.MODULE_LINE, "jit_other(9)", 50 * MS, 2 * MS))
  events.append((D0, tr.OPS_LINE, "%fusion.1 = f32[8]{0} fusion(%p)",
                 50 * MS, 2 * MS))
  run = {"events": events, "steps": 5, "batch_size": 1}
  if table:
    run["op_table"] = _table()
  return run


EXPECTED = {  # ms a step on the hand-written run
    "step_forward_ms": 5 + 3 + 2 + 1, "step_recompute_ms": 4 + 1,
    "step_backward_ms": 6 + 2, "step_optimizer_ms": 7 + 3,
    "step_unscoped_share": 100.0 * (1 + 2) / 39,   # add.12 + mystery.15
    "scope_gdn_scan_ms": 2, "scope_ssm_scan_ms": 5, "scope_moe_route_ms": 3,
    "scope_moe_experts_ms": 5, "scope_attn_ms": 6 + 1, "scope_lm_loss_ms": 2,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_hand_written_run(name):
  value = manifest.layer_metric_reader(name)(_run())
  assert value == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_returns_nothing_without_a_table(name):
  """A parent commit has no `xray.op_scopes` and no `xray/` spans below
  step 1: nothing is returned, nothing raises."""
  run = dict(_run(table=False), op_table=None, program_events=[])
  assert manifest.layer_metric_reader(name)(run) is None
  assert manifest.layer_metric_reader(name)({}) is None


def test_phases_add_up_to_the_top_level_ops(capsys):
  run = _run()
  out = device_scopes.reduced(run)
  ops = tr.top_level(tr.select(run["events"], plane=D0, line=tr.OPS_LINE))
  inside = [e for e in ops if e[3] < 45 * MS or e[3] >= 100 * MS]
  assert out["steps"] == 2 and out["ops"] == len(inside) == 26
  assert out["total_s"] == pytest.approx(sum(e[4] for e in inside) / 1e9)
  assert sum(out["by_phase"].values()) + out["unknown_s"] == pytest.approx(
      out["total_s"])
  phases = sum(manifest.layer_metric_reader(n)(run) for n in PHASE_METRICS[:4])
  other = 1e3 * out["by_phase"]["other"] / out["steps"]
  assert phases + other + 2 == pytest.approx(39)     # + mystery.15
  assert out["unknown"] == {"mystery.15": pytest.approx(0.004)}
  assert out["mixed_s"] == pytest.approx(0.014)      # fusion.9, both steps
  assert out["copy_by_scope"] == {"moe_experts": pytest.approx(0.002)}
  err = capsys.readouterr().err
  assert "[bench scopes]" in err and "phase recompute" in err
  assert "while.7" in err and "mystery.15" in err
  # printed once a run, whichever reader comes first
  manifest.layer_metric_reader("step_forward_ms")(run)
  assert "[bench scopes]" not in capsys.readouterr().err


def test_reduction_is_the_programs_own_function():
  """The readers and `ProfilerHook` call one function; the op lines are
  handed over as the trace has them."""
  from tensor2robot_tpu.obs import xray

  run = _run()
  lines = {line: [(e[2], e[3], e[4]) for e in tr.select(
      run["events"], plane=D0, line=line)]
           for line in (tr.OPS_LINE, tr.MODULE_LINE)}
  assert device_scopes.reduced(run) == xray.device_time_by_scope(
      lines[tr.OPS_LINE], run["op_table"], lines[tr.MODULE_LINE])


def _ring(hit):
  def span(name, ts, dur, step=1, executable="train_step"):
    return {"name": name, "ph": "X", "ts": ts * 1e6, "dur": dur * 1e6,
            "tid": 1, "id": int(ts * 10) + 1, "step": step,
            "args": {"executable": executable}}

  events = [span("xray/trace", 0.0, 7.5)]
  if hit:
    events += [span("xray/cache_load", 7.6, 3.25)]
  else:
    events += [span("xray/lower", 7.6, 2.0), span("xray/compile", 9.6, 40.0)]
  events += [span("xray/op_scopes", 50.0, 0.5),
             span("xray/trace", 60.0, 1.0, step=9),     # a later re-trace
             span("xray/trace", 0.0, 2.0, executable="train_loop_k4")]
  return {"steps": 5, "program_events": events}


@pytest.mark.parametrize("hit,compile_s", [(False, 42.5), (True, 3.75)])
def test_compile_spans_of_step_one(hit, compile_s):
  run = _ring(hit)
  assert manifest.layer_metric_reader("step_trace_s")(run) == 7.5
  assert manifest.layer_metric_reader("step_compile_s")(run) == compile_s


@pytest.mark.parametrize("cell", EXPERT_CELLS)
def test_rehearsal_leaves_the_table_and_the_compile_spans(capsys, cell):
  """After a rehearsal the program holds the step's table (every phase of a
  rematerialised step in it, the cell's scopes among its entries) and step 1's
  `xray/` spans; the CPU's trace has no device plane, so the device metrics
  read nothing."""
  from tensor2robot_tpu.obs import xray

  result, _ = rehearse(capsys, cell, trace=1, seed=2_147_483_661)
  run = {"steps": result["counts"]["steps"]}
  table = device_scopes.op_table(run)
  assert table["module"] == STEP and len(table["ops"]) > 500
  phases = {p for p, _, _ in table["paths"]}
  assert {"forward", "recompute", "backward", "optimizer", "other"} <= phases
  scopes = {s for _, s, _ in table["paths"]}
  mixer = "gdn_scan" if cell.startswith("qwen") else "ssm_scan"
  assert {mixer, "moe_route", "moe_experts", "lm_loss", "metrics"} <= scopes
  assert scopes - {""} <= set(xray.DEVICE_SCOPES)
  assert manifest.layer_metric_reader("step_trace_s")(run) > 0
  assert manifest.layer_metric_reader("step_compile_s")(run) > 0
  assert result["metrics"] == {}  # a rehearsal puts no number under a name


def check_listing(benchmark):
  """Each phase, scope and compile-span metric is in `benchmark` with its
  unit, layer, source and cells, lower being better: whatever entries later
  PRs append."""
  per_layer = {m["name"]: m for m in benchmark["per_layer"]}
  for name, (unit, layer, cells) in LISTED.items():
    metric = per_layer[name]
    spans = layer == "compile cache"
    assert (metric["unit"], metric["layer"], metric["better"]) == (
        unit, layer, "lower"), name
    assert metric["source"] == ("program_span" if spans else "device_trace")
    assert metric["moves"] == ("setup_s" if spans else "examples_per_s")
    assert set(cells) <= set(metric["workloads"]), name
  assert sorted(LISTED) == sorted(NEW_METRICS)


def test_new_metrics_are_listed_with_their_cells():
  check_listing(manifest.load_benchmark())
