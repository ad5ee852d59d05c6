"""The Laguna-XS.2 share's cell: `model_flops` against a count by hand, its
configuration file against the catalog's row, its gin file against its
configuration, and each of its per-layer readers on nothing and on a small
trace written by hand. (Its rehearsal at the `tiny` sizes, with the fp8
control and the two faults failing their limits there, runs with every other
cell's in `test_run.py`, `test_control.py` and `test_faults.py`.)"""

import copy
import json
import os

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import peaks
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import moe_route_ms
from benchmarks.references import laguna_xs2_ep16share as ref
from tests.benchmark import test_hybrid_cell

CELL = "laguna_train_T4096"
CONFIG = "laguna_xs2_ep16share"
D0 = "/device:TPU:0"
MS = 1e6
STEP = "jit_t2r_train_step"
# The cell's own metrics, each with its layer.
METRIC_LAYERS = {
    "flash_window_fwd_roofline": "kernels",
    "flash_window_bwd_roofline": "kernels",
    "scope_attn_window_ms": "kernels", "scope_attn_global_ms": "kernels",
    "moe_e256_buffer_fill": "experts"}
NEW_METRICS = list(METRIC_LAYERS)

_FULL, _SLIDING = "full_attention", "sliding_attention"
# The catalog row's `config` (model-configs guide, architectures.jsonl,
# Laguna-XS.2), every key of it.
CATALOG_ROW = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        _FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
        _SLIDING: {"rope_type": "default", "rope_theta": 10000,
                   "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": [_FULL, _SLIDING, _SLIDING, _SLIDING] * 10,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}


def _config():
  with open(os.path.join(manifest.BENCH_DIR, "configs",
                         CONFIG + ".json")) as f:
    return json.load(f)


def _sizes():
  cell = manifest.Cell(CELL)
  return ref.sizes_from_bindings({**cell.config["model"],
                                  **cell.traffic["model"]})


def test_model_flops_by_hand():
  """418.4 M multiply-adds a token, 10.28 TFLOP a step."""
  sizes = _sizes()
  band = 512 * 513 / 2 + (4096 - 512) * 512        # sum_i min(i + 1, 512)
  assert band == 1_966_336
  full_projections = 2048 * 12288 + 2 * 2048 * 1024 + 6144 * 2048
  sliding_projections = 2048 * 16384 + 2 * 2048 * 1024 + 8192 * 2048
  causal = 2 * (4096 / 2) * 6144
  windowed = 2 * band / 4096 * 8192
  assert (full_projections, sliding_projections) == pytest.approx(
      (41.94e6, 54.53e6), rel=1e-3)
  assert (causal, windowed) == pytest.approx((25.17e6, 7.865e6), rel=1e-3)
  dense = 3 * 2048 * 8192
  # router, ungated shared expert (gate, up, down at 512), 8 x 16 / 256 =
  # 0.5 routed experts a token at 512
  sparse = 2048 * 256 + 3 * 2048 * 512 + 0.5 * 3 * 2048 * 512
  head = 2048 * 12544
  per_token = (full_projections + causal + dense
               + 3 * (sliding_projections + windowed + sparse)
               + full_projections + causal + sparse + head)
  assert per_token == pytest.approx(418.4e6, rel=1e-3)
  assert ref.model_flops(sizes, 1) == pytest.approx(6.0 * per_token * 4096)
  assert ref.model_flops(sizes, 1) == pytest.approx(10.28e12, rel=1e-3)
  parts = ref.macs_per_token(sizes)
  assert parts["attention/layer_1"] == pytest.approx(windowed)
  assert parts["attention/layer_4"] == causal and parts["head"] == head
  # the attention sublayers' share of the work: 77 %, the sliding ones 45 %
  attention = (2 * (full_projections + causal)
               + 3 * (sliding_projections + windowed))
  assert attention / per_token == pytest.approx(0.77, abs=0.005)
  assert 3 * (sliding_projections + windowed) / per_token == pytest.approx(
      0.45, abs=0.005)


def test_configuration_file_holds_every_key_of_the_catalogs_row():
  config = _config()
  assert set(CATALOG_ROW) <= set(config)
  differs = {k for k, v in CATALOG_ROW.items() if config[k] != v}
  assert differs == {"num_experts", "vocab_size"} <= set(config["reduced"])
  assert config["reduced"] == ["layers", "num_experts", "vocab_size"]
  assert set(config["reduced"]) == set(config["reduced_stands_for"])
  assert config["published"] == {"num_hidden_layers": 40, "num_experts": 256,
                                 "vocab_size": 100352}
  assert config["source"] == (
      "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")


def test_configuration_file_states_the_cut():
  config, model = _config(), _config()["model"]
  # layers 0-4: the leading dense layer and one whole period after it
  assert model["layer_types"] == CATALOG_ROW["layer_types"][:5]
  assert model["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
  assert model["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
  assert CATALOG_ROW["layer_types"][1:5] == [_SLIDING] * 3 + [_FULL]
  assert config["layers"] == 5
  # the guide's floors: four layers after the dense one, at least 8
  # experts, an eighth of the ids
  assert config["num_experts"] == model["num_experts"] == 16
  assert config["vocab_size"] * 8 == 100352 == 8 * model["vocab_size"]
  assert model["router_width"] == 256 and model["first_expert"] == 0
  assert model["rope_parameters"] == CATALOG_ROW["rope_parameters"]
  for key in ("hidden_size", "num_key_value_heads", "head_dim",
              "sliding_window", "rms_norm_eps", "intermediate_size",
              "moe_intermediate_size", "shared_expert_intermediate_size",
              "num_experts_per_tok"):
    assert model[key] == CATALOG_ROW[key], key    # no width cut
  assert model["routed_scaling_factor"] == CATALOG_ROW[
      "moe_routed_scaling_factor"]
  for words in ("128 chips", "8 pipeline stages", "16 chips", "layers 0-4",
                "experts 0-15", "ids 0-12543", "240 absent", "T 4096"):
    assert words in config["deployment"], words
  for key in ("router", "gate", "qk_norm", "norms", "activation", "rotary",
              "weights", "optimizer", "expert_buffer_factor", "kv_layout",
              "projection_layout", "batch_size", "sequence_length"):
    assert key in config["assumed"], key
  assert config["control_precision"] == "fp8"
  assert config["first_gradient"] == {"from": "mu", "scale": 10.0}


def test_parameters_by_hand():
  """The cut's arithmetic (PERF.md section 4): 565.2 M parameters."""
  norms = 2 * 2048 + 2 * 128
  full = 2048 * 12288 + 2 * 2048 * 1024 + 6144 * 2048 + norms
  sliding = 2048 * 16384 + 2 * 2048 * 1024 + 8192 * 2048 + norms
  sparse = 2048 * 256 + 3 * 2048 * 512 + 16 * 3 * 2048 * 512
  total = (2 * 12544 * 2048 + 2048 + full + 3 * 2048 * 8192
           + 3 * (sliding + sparse) + full + sparse)
  assert total == pytest.approx(565.2e6, rel=5e-4)
  import jax

  shapes = jax.eval_shape(lambda: ref.init_state(0, _sizes())[0])
  assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == total


def test_gin_file_binds_the_configurations_sizes():
  from tensor2robot_tpu.utils import config as config_lib

  cell = manifest.Cell(CELL)
  config_lib.clear_config()
  try:
    config_lib.parse_config_files_and_bindings(
        [os.path.join(manifest.ROOT, f) for f in cell.config["gin_files"]],
        list(cell.traffic["bindings"]))
    model = config_lib.query_parameter("train_eval_model.model")
    batch = config_lib.query_parameter(
        "train_eval_model.input_generator_train").batch_size
  finally:
    config_lib.clear_config()
  decoder = model._decoder_config
  sizes = cell.config["model"]
  for key in ("hidden_size", "rms_norm_eps", "num_key_value_heads",
              "head_dim", "sliding_window", "intermediate_size",
              "num_experts_per_tok", "moe_intermediate_size",
              "shared_expert_intermediate_size", "routed_scaling_factor",
              "expert_buffer_factor"):
    assert getattr(decoder, key) == sizes[key], key
  assert decoder.num_experts == sizes["router_width"] == 256
  assert decoder.experts_held == (sizes["first_expert"], sizes["num_experts"])
  kinds = {"global": _FULL, "sliding": _SLIDING}
  assert [kinds[k] for k in decoder.layer_types] == sizes["layer_types"]
  assert list(decoder.mlp_layer_types) == sizes["mlp_layer_types"]
  assert list(decoder.num_attention_heads_per_layer) == sizes[
      "num_attention_heads_per_layer"]
  for kind, entry in (("global", _FULL), ("sliding", _SLIDING)):
    assert decoder.rope(kind) == sizes["rope_parameters"][entry]
  assert model._vocab_size == sizes["vocab_size"] == 12544
  assert model._sequence_length == cell.traffic["model"]["sequence_length"]
  assert batch == cell.traffic["batch_size"] == 1
  assert cell.traffic["fields"]["features/tokens"]["high"] == 12544
  assert cell.traffic["fields"]["labels/targets"]["high"] == 12544
  assert cell.traffic["driver"] == "trainer_streamed"
  assert cell.traffic["pool_batches"] == 4
  assert cell.chips == 1 and cell.traffic_name == "pool_b1_T4096_v12544"
  # the rehearsal's sizes: both kinds, both feed-forwards, two head counts
  tiny = cell.traffic["tiny"]
  assert "HybridDecoderLM.sliding_window = 16" in tiny["bindings"]
  assert tiny["model"]["sequence_length"] <= 128
  assert set(tiny["model"]["num_attention_heads_per_layer"]) == {2, 4}


# -- the readers ---------------------------------------------------------------


def _kernel(name, heads, outputs):
  shape = f"bf16[1,4096,{heads * 128}]"
  results = ", ".join([shape] * outputs)
  return (f"%{name} = ({results}) custom-call({shape} %q), "
          'custom_call_target="tpu_custom_call"')


def _trace():
  """Two steps of 100 ms; in each, per sliding layer (3) two windowed
  forwards of 1.5 ms and a backward of 2.5, per global layer (2) two causal
  forwards of 2 ms and a backward of 3.5, and 20 ms of something else."""
  events = []
  for step in range(2):
    t = 1000.0 + 100.0 * step
    events.append((D0, tr.MODULE_LINE, f"{STEP}(123)", t * MS, 100 * MS))
    ops = []
    for i in range(3):
      ops += [(_kernel(f"flash_window_fwd.{2 * i}", 64, 1), 1.5),
              (_kernel(f"flash_window_fwd.{2 * i + 1}", 64, 1), 1.5),
              (_kernel(f"flash_window_bwd.{i}", 64, 3), 2.5)]
    for i in range(2):
      ops += [(_kernel(f"flash_fwd.{2 * i}", 48, 1), 2.0),
              (_kernel(f"flash_fwd.{2 * i + 1}", 48, 1), 2.0),
              (_kernel(f"flash_bwd.{i}", 48, 3), 3.5)]
    ops.append(("%fusion.9 = bf16[1,4096,2048]{2,1,0} fusion(%y)", 20.0))
    start = t + 1
    for text, ms in ops:
      events.append((D0, tr.OPS_LINE, text, start * MS, ms * MS))
      start += ms
  return events


def _table():
  """The program's op table for the hand trace (`xray.build_op_table`'s
  form): the windowed kernels under `attn_window`, the causal ones under
  `attn_global`."""
  paths = [["forward", "attn_window", "layer_1/mixer"],
           ["backward", "attn_window", "layer_1/mixer"],
           ["forward", "attn_global", "layer_0/mixer"],
           ["backward", "attn_global", "layer_0/mixer"],
           ["other", "", ""]]
  ops = {}
  for i in range(6):
    ops[f"flash_window_fwd.{i}"] = ["custom-call", 0, None]
  for i in range(3):
    ops[f"flash_window_bwd.{i}"] = ["custom-call", 1, None]
  for i in range(4):
    ops[f"flash_fwd.{i}"] = ["custom-call", 2, None]
  for i in range(2):
    ops[f"flash_bwd.{i}"] = ["custom-call", 3, None]
  ops["fusion.9"] = ["fusion", 4, None]
  return {"executable": "train_step", "module": STEP, "paths": paths,
          "ops": ops}


def _run():
  layers = (1, 2, 3, 4)
  record = {f"moe_buffer_fill/layer_{i}": 0.25 + 0.01 * i for i in layers}
  later = dict(record, **{"moe_buffer_fill/layer_2": 0.463})
  return {"events": _trace(), "sizes": _sizes(), "batch_size": 1,
          "peaks": peaks.peaks_for("TPU v5 lite"), "op_table": _table(),
          "stepstats": [(10, record), (20, later), (30, dict(record))]}


def _expected():
  v5e = peaks.peaks_for("TPU v5 lite")
  flops, bw = v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"]
  band = 512 * 513 / 2 + (4096 - 512) * 512
  bh, t, d = 64, 4096, 128
  fwd = max(2 * 2 * bh * d * band / flops, (4 * bh * t * d * 2 + bh * t * 4)
            / bw)
  bwd = max(5 * 2 * bh * d * band / flops,
            (7 * bh * t * d * 2 + 2 * bh * t * 4) / bw)
  return {
      "flash_window_fwd_roofline": 100.0 * fwd / 1.5e-3,
      "flash_window_bwd_roofline": 100.0 * bwd / 2.5e-3,
      "scope_attn_window_ms": 3 * (1.5 + 1.5 + 2.5),
      "scope_attn_global_ms": 2 * (2.0 + 2.0 + 3.5),
      "moe_e256_buffer_fill": 46.3,
  }


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_on_a_trace_written_by_hand(name):
  read = manifest.layer_metric_reader(name)
  assert read(_run()) == pytest.approx(_expected()[name], rel=1e-6)
  assert read({}) is None
  # A program without the kernels, the scopes or the counters (the parent
  # commit), and another configuration's run: nothing to read.
  bare = dict(_run(), events=[e for e in _trace() if "window" not in e[2]],
              op_table=dict(_table(), paths=[["other", "", ""]] * 5),
              stepstats=[(10, {"data_wait_ms": 0.05})])
  assert read(bare) is None
  if name.startswith("flash_window"):
    other = dict(_run(), sizes={"mamba_num_heads": 64,
                                "sequence_length": 4096})
    assert read(other) is None


def test_shares_stay_under_their_ceiling_on_the_hand_trace():
  for name in NEW_METRICS:
    if name.endswith("_roofline"):
      assert 0 < manifest.layer_metric_reader(name)(_run()) <= 105.0


def test_the_accepted_flash_readers_find_nothing_in_this_cells_run():
  """The causal flash rooflines are keyed to their own cells' sizes: they
  stay silent here and are not listed for this cell."""
  for name in ("flash_fwd_roofline", "flash_bwd_roofline",
               "flash_d256_fwd_roofline", "flash_d256_bwd_roofline",
               "flash_d128_fwd_roofline", "flash_d128_bwd_roofline",
               "moe_route_ms", "moe_e128_route_ms"):
    assert manifest.layer_metric_reader(name)(_run()) is None, name


def check_listing(benchmark):
  """This cell, its configuration and its own metrics are in `benchmark`,
  each metric with the layer and the end-to-end metric it had when the cell
  came, and the cell reports them and the generic ones: whatever entries
  later PRs append."""
  per_layer = {m["name"]: m for m in benchmark["per_layer"]}
  for name, layer in METRIC_LAYERS.items():
    assert CELL in per_layer[name]["workloads"], name
    assert per_layer[name]["layer"] == layer, name
    assert per_layer[name]["moves"] == "examples_per_s", name
  reported = {m["name"] for m in manifest.Cell(CELL, benchmark).metrics(
      "per_layer")}
  assert reported >= set(NEW_METRICS) | test_hybrid_cell.GENERIC_METRICS
  assert CELL in {w["name"] for w in benchmark["workloads"]}
  assert CONFIG in {c["name"] for c in benchmark["configs"]}


def test_new_metrics_are_listed_with_their_cell():
  check_listing(manifest.load_benchmark())


def test_listing_holds_after_a_later_pr_appends():
  benchmark = copy.deepcopy(manifest.load_benchmark())
  benchmark["workloads"].append(dict(benchmark["workloads"][-1],
                                     name="later_train_T2048"))
  benchmark["per_layer"].append({
      "name": "later_kernel_roofline", "unit": "%", "better": "higher",
      "source": "device_trace", "layer": "kernels",
      "moves": "examples_per_s", "workloads": [CELL, "later_train_T2048"]})
  check_listing(benchmark)


def test_buffer_rows_are_the_programs():
  from tensor2robot_tpu.layers import moe as moe_lib

  sizes = _sizes()
  layer = moe_lib.ShardedExpertsMoE(
      num_experts=256, experts_held=(0, 16), top_k=8,
      buffer_factor=sizes["expert_buffer_factor"])
  # 4096 x 8 x 16 / 256 = 2048 rows balanced, 128 an expert
  assert layer.clone(buffer_factor=1.0).buffer_rows(4096) == 2048
  assert layer.buffer_rows(4096) == moe_route_ms.buffer_rows(sizes, 4096)
  assert layer.buffer_rows(4096) == 2048 * sizes["expert_buffer_factor"]
