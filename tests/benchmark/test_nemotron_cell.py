"""The Nemotron-3-Nano share's cell: `model_flops` against a count by hand,
its configuration file against the catalog's row, its gin file against its
configuration, and each of its per-layer readers on nothing and on a small
trace written by hand. (Its rehearsal at the `tiny` sizes, with the fp8
control and the two faults failing their limits there, runs with every other
cell's in `test_run.py`, `test_control.py` and `test_faults.py`.)"""

import json
import os

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import peaks
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import moe_route_ms
from benchmarks.references import nemotron3_nano_30b_a3b_ep16share as ref
from tests.benchmark import test_hybrid_cell

CELL = "nemotron3nano_train_T4096"
CONFIG = "nemotron3_nano_30b_a3b_ep16share"
D0 = "/device:TPU:0"
# The cell's own metrics, each with its layer.
METRIC_LAYERS = {
    "ssd_scan_ms": "state space", "moe_e128_route_ms": "experts",
    "moe_e128_experts_roofline": "kernels", "moe_e128_buffer_fill": "experts",
    "moe_e128_load_max_over_mean": "experts",
    "flash_d128_fwd_roofline": "kernels", "flash_d128_bwd_roofline": "kernels"}
NEW_METRICS = list(METRIC_LAYERS)

# The catalog row's `config` (model-configs guide, architectures.jsonl,
# NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), every key of it.
CATALOG_ROW = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def _config():
  with open(os.path.join(manifest.BENCH_DIR, "configs",
                         CONFIG + ".json")) as f:
    return json.load(f)


def _sizes():
  cell = manifest.Cell(CELL)
  return ref.sizes_from_bindings({**cell.config["model"],
                                  **cell.traffic["model"]})


def test_model_flops_by_hand():
  sizes = _sizes()
  # A Mamba-2 layer: [z | x, B, C | dt] (2688 -> 4096 + 6144 + 64), the
  # convolution of 4 over 6144 channels, out (4096 -> 2688).
  projections = 2688 * 10304 + 6144 * 4 + 4096 * 2688
  assert projections == pytest.approx(38.73e6, rel=2e-3)
  # The chunked scan a chunk of 128: C B^T once a group (8 x 128 x 128 x
  # 128), and a head of 64 the scores on dt x (128 x 128 x 64), the chunk's
  # state and the entering state's read (2 x 128 x 64 x 128); 64 heads.
  scan = (8 * 128 * 128 * 128 + 64 * (128 * 128 * 64 + 2 * 128 * 64 * 128)
          ) / 128
  assert scan == pytest.approx(1.704e6, rel=1e-3)
  # An expert layer: the router, the un-gated shared expert (up and down at
  # 3712), 6 x 8 / 128 = 0.375 routed experts a token at 1856.
  experts = 2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856
  assert experts == pytest.approx(24.04e6, rel=2e-3)
  # Attention: q (4096), k and v (256 each), out; scores and weighted sum
  # over half of 4096 x 4096 at 32 x 128.
  attention_projections = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688
  assert attention_projections == pytest.approx(23.40e6, rel=2e-3)
  attention = 2 * (4096 / 2) * 4096
  head = 2688 * 16384
  per_token = (4 * (projections + scan) + 4 * experts
               + attention_projections + attention + head)
  assert per_token == pytest.approx(342.1e6, rel=2e-3)
  assert ref.model_flops(sizes, 1) == pytest.approx(6.0 * per_token * 4096)
  assert ref.model_flops(sizes, 1) == pytest.approx(8.41e12, rel=2e-3)
  parts = ref.macs_per_token(sizes)
  assert parts["scan"] == pytest.approx(scan)
  assert parts["attention"] == attention and parts["head"] == head
  assert parts["mamba_projections"] == projections


def test_configuration_file_holds_every_key_of_the_catalogs_row():
  config = _config()
  assert set(CATALOG_ROW) <= set(config)
  differs = {k for k, v in CATALOG_ROW.items() if config[k] != v}
  assert differs == {"n_routed_experts", "vocab_size"} <= set(
      config["reduced"])
  assert config["reduced"] == ["layers", "n_routed_experts", "vocab_size"]
  assert set(config["reduced"]) == set(config["reduced_stands_for"])
  assert config["published"] == {"num_hidden_layers": 52,
                                 "n_routed_experts": 128,
                                 "vocab_size": 131072}
  assert config["source"].endswith(
      "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")


def test_configuration_file_states_the_cut():
  config, model = _config(), _config()["model"]
  # layers 0-8 of the published pattern: a whole period and what follows
  pattern = CATALOG_ROW["hybrid_override_pattern"]
  assert len(pattern) == 52
  assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (
      23, 23, 6)
  assert model["pattern"] == pattern[:9] == "MEMEM*EME"
  assert config["layers"] == 9 == len(model["pattern"])
  assert pattern[:6] == "MEMEM*" and pattern[6:12] == "EMEMEM"
  # the floors of a model_config PR: a whole period and at least four of the
  # layers... (nine is what the driver counts), 8 experts, 1/8 of the ids
  assert config["n_routed_experts"] == model["num_experts"] == 8
  assert config["vocab_size"] * 8 == 131072 == 8 * model["vocab_size"]
  assert model["router_width"] == 128 and model["first_expert"] == 0
  for key, value in model.items():     # one size, one value: no width cut
    if key in CATALOG_ROW and key != "vocab_size":
      assert value == CATALOG_ROW[key], key
  for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
              "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "num_experts_per_tok", "routed_scaling_factor", "norm_eps"):
    assert key in model, key
  for words in ("96 chips", "6 pipeline stages", "16 chips", "layers 0-8",
                "experts 0-7", "ids 0-16383", "120 absent"):
    assert words in config["deployment"], words
  for key in ("batch_size", "sequence_length", "positional_embedding",
              "e_score_correction_bias", "gated_norm_group_size", "weights",
              "rescale_prenorm_residual", "time_step_limit", "optimizer",
              "expert_buffer_factor", "kv_layout", "projection_layout"):
    assert key in config["assumed"], key
  assert config["control_precision"] == "fp8"
  assert config["first_gradient"] == {"from": "mu", "scale": 10.0}


def test_parameters_by_hand():
  """The cut's arithmetic (PERF.md section 4): 667 M parameters."""
  mamba = 2688 * 10304 + 4096 * 2688 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 2688
  expert_layer = (2688 * 128 + 2 * 2688 * 3712 + 2688
                  + 8 * 2 * 2688 * 1856)
  attention = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 + 2688
  total = 4 * mamba + 4 * expert_layer + attention + 2 * 16384 * 2688 + 2688
  assert total == pytest.approx(667.0e6, rel=1e-3)
  # and it is what the reference draws at the cell's sizes
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
  for key in ("hidden_size", "norm_eps", "mamba_num_heads", "mamba_head_dim",
              "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "num_experts_per_tok", "moe_intermediate_size",
              "moe_shared_expert_intermediate_size", "routed_scaling_factor",
              "expert_buffer_factor"):
    assert getattr(decoder, key) == sizes[key], key
  assert decoder.n_routed_experts == sizes["router_width"] == 128
  assert decoder.experts_held == (sizes["first_expert"], sizes["num_experts"])
  letters = {"mamba": "M", "experts": "E", "attention": "*"}
  assert "".join(letters[k] for k in decoder.layer_types) == sizes["pattern"]
  assert model._vocab_size == sizes["vocab_size"] == 16384
  assert model._sequence_length == cell.traffic["model"]["sequence_length"]
  assert batch == cell.traffic["batch_size"] == 1
  assert cell.traffic["fields"]["features/tokens"]["high"] == 16384
  assert cell.traffic["fields"]["labels/targets"]["high"] == 16384
  assert cell.traffic["driver"] == "trainer_streamed"
  assert cell.traffic["pool_batches"] == 4
  assert cell.chips == 1 and cell.traffic_name == "pool_b1_T4096_v16384"
  # the rehearsal's pattern has every kind: at least M E * E
  tiny = cell.traffic["tiny"]["model"]
  assert set(tiny["pattern"]) == {"M", "E", "*"} and len(
      tiny["pattern"]) >= 4
  assert tiny["router_width"] == 8 and tiny["num_experts"] == 4


# -- the readers ---------------------------------------------------------------


def _op(name, start_ms, dur_ms, line=tr.OPS_LINE):
  return (D0, line, name, start_ms * 1e6, dur_ms * 1e6)


def _trace():
  """Two steps of 100 ms; in each: a scan over chunks of 6 ms whose body ops
  lie inside it, three chunk-layout fusions of 2, 1.5 and 0.5 (one with the
  batch dropped, one turned), a sort of 1, a gather of 3, a router fusion of
  1, four grouped products of 0.5, one flash forward of 2 and backward of 4,
  and 10 ms of something else."""
  events = []
  for step in range(2):
    t = 1000.0 + 100.0 * step
    events.append(_op("jit_t2r_train_step(123)", t, 100.0, tr.MODULE_LINE))
    events.append(_op(
        "%while.78 = (s32[], f32[1,8,8,64,128]{4,3,2,1,0}, bf16[32,1,8,8,64,"
        "128]{5,4,3,2,1,0}) while((s32[], f32[1,8,8,64,128]{4,3,2,1,0}) "
        "%tuple.1), condition=%c, body=%b", t, 6.0))
    events.append(_op("%fusion.7 = f32[1,8,8,64,128]{4,3,2,1,0} fusion(f32[1,"
                      "8,8,64,128]{4,3,2,1,0} %p)", t + 1, 3.0))  # in the loop
    events.append(_op("%fusion.8 = bf16[32,1,8,8,128,128]{5,4,3,2,1,0} fusion("
                      "f32[32,1,8,8,128]{4,3,2,1,0} %g), kind=kLoop", t + 6,
                      2.0))
    events.append(_op("%fusion.9 = f32[32,8,128,128]{3,2,1,0} fusion(bf16[1,"
                      "4096,8,128]{3,2,1,0} %c), kind=kOutput", t + 8, 1.5))
    events.append(_op("%fusion.10 = f32[32,128,8,8]{3,2,1,0} fusion(f32[1,4096,"
                      "64]{2,1,0} %dt), kind=kLoop", t + 9.5, 0.5))
    events.append(_op("%sort.3 = (s32[24576]{0}, s32[24576]{0}) sort(s32[24576]"
                      "{0} %k, s32[24576]{0} %i), dimensions={0}", t + 22, 1.0))
    events.append(_op("%fusion.11 = bf16[6144,2688]{1,0} fusion(bf16[4096,2688]"
                      "{1,0} %x, s32[6144]{0} %t), kind=kCustom", t + 23, 3.0))
    events.append(_op("%fusion.12 = f32[4096,128]{1,0} fusion(bf16[4096,2688]"
                      "{1,0} %x, bf16[2688,128]{1,0} %w), kind=kOutput",
                      t + 26, 1.0))
    for i, (out, lhs, rhs) in enumerate([
        ("f32[6144,1856]", "bf16[6144,2688]", "bf16[8,2688,1856]"),
        ("f32[6144,2688]", "bf16[6144,1856]", "bf16[8,1856,2688]"),
        ("f32[6144,2688]", "bf16[6144,1856]", "bf16[8,2688,1856]"),
        ("f32[8,2688,1856]", "bf16[6144,2688]", "bf16[6144,1856]")]):
      events.append(_op(
          f"%ragged-dot-none.{i} = {out}{{1,0}} custom-call(s32[1]{{0}} %m, "
          f"{lhs}{{1,0}} %a, {rhs}{{1,0}} %b), custom_call_target="
          '"tpu_custom_call"', t + 27 + 0.5 * i, 0.5))
    events.append(_op("%ragged-dot-metadata.1 = (s32[9]{0}, s32[51]{0}) "
                      'custom-call(s32[8]{0} %gs), custom_call_target='
                      '"tpu_custom_call"', t + 29, 0.1))
    events.append(_op("%flash_fwd.3 = (bf16[1,4096,4096]{2,1,0}, f32[32,4096,1]"
                      "{2,1,0}) custom-call(bf16[1,4096,4096]{2,1,0} %q), "
                      'custom_call_target="tpu_custom_call"', t + 30, 2.0))
    events.append(_op("%flash_bwd.5 = (bf16[1,4096,4096]{2,1,0}, bf16[1,4096,"
                      "4096]{2,1,0}, bf16[1,4096,4096]{2,1,0}) custom-call("
                      'bf16[1,4096,4096]{2,1,0} %q), custom_call_target='
                      '"tpu_custom_call"', t + 34, 4.0))
    events.append(_op("%fusion.99 = bf16[1,4096,2688]{2,1,0} fusion(bf16[1,4096,"
                      "2688]{2,1,0} %y), kind=kLoop", t + 42, 10.0))
  return events


LAYERS = (1, 3, 6, 8)


def _run():
  record = {f"moe_rows_held/layer_{i}": 3072.0 for i in LAYERS}
  record.update({f"moe_buffer_fill/layer_{i}": 0.25 + 0.01 * i
                 for i in LAYERS})
  record.update({f"moe_load_max_over_mean/layer_{i}": 1.2 + 0.1 * i
                 for i in LAYERS})
  later = dict(record, **{"moe_load_max_over_mean/layer_1": 2.4,
                          "moe_buffer_fill/layer_3": 0.41})
  return {"events": _trace(), "sizes": _sizes(), "batch_size": 1,
          "peaks": peaks.peaks_for("TPU v5 lite"),
          "stepstats": [(10, record), (20, later), (30, dict(record))]}


def _expected():
  v5e = peaks.peaks_for("TPU v5 lite")
  flops, bw = v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"]
  square = 2 * 32 * 4096 * 4096 / 2.0
  fwd = max(2 * square * 128 / flops,
            (4 * 32 * 4096 * 128 * 2 + 32 * 4096 * 4) / bw)
  bwd = max(5 * square * 128 / flops,
            (7 * 32 * 4096 * 128 * 2 + 2 * 32 * 4096 * 4) / bw)
  # the four grouped products: FLOPs of 3072 rows against their bytes at half
  # a buffer
  matrix = 2688 * 1856
  least = 0.0
  for weights, rows in [
      (8 * matrix * 2, 6144 * 1856 * 4 + 6144 * 2688 * 2),
      (8 * matrix * 2, 6144 * 2688 * 4 + 6144 * 1856 * 2),
      (8 * matrix * 2, 6144 * 2688 * 4 + 6144 * 1856 * 2),
      (8 * matrix * 4, 6144 * 2688 * 2 + 6144 * 1856 * 2)]:
    least += max(2 * 3072 * matrix / flops, (weights + rows * 0.5) / bw)
  return {
      "ssd_scan_ms": 10.0,            # the loop and the three layout fusions
      "moe_e128_route_ms": 5.0,       # sort, gather, router
      "moe_e128_experts_roofline": 100.0 * least / 2e-3,
      "flash_d128_fwd_roofline": 100.0 * fwd / 2e-3,
      "flash_d128_bwd_roofline": 100.0 * bwd / 4e-3,
      "moe_e128_buffer_fill": 41.0,
      "moe_e128_load_max_over_mean": 2.0,  # worst layer 2.0, 2.4, 2.0
  }


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_on_a_trace_written_by_hand(name):
  read = manifest.layer_metric_reader(name)
  assert read(_run()) == pytest.approx(_expected()[name], rel=1e-6)
  assert read({}) is None
  # Another configuration's run, and a program without the counters (the
  # parent commit): nothing to read, nothing returned.
  seq = dict(_run(), sizes={"num_heads": 8, "hidden_size": 512,
                            "sequence_length": 2048})
  hybrid = dict(_run(), sizes={"linear_num_value_heads": 32,
                               "sequence_length": 4096})
  bare = dict(_run(), stepstats=[(10, {"data_wait_ms": 0.05})])
  counters = ("moe_e128_buffer_fill", "moe_e128_load_max_over_mean")
  if name in counters + ("moe_e128_experts_roofline",):
    assert read(bare) is None
  if name not in counters:
    assert read(seq) is None and read(hybrid) is None
    assert read(dict(_run(), events=[])) is None


def test_shares_stay_under_their_ceiling_on_the_hand_trace():
  for name in NEW_METRICS:
    if name.endswith("_roofline"):
      assert 0 < manifest.layer_metric_reader(name)(_run()) <= 105.0


def test_the_accepted_hybrid_readers_find_nothing_in_this_cells_run():
  """`gdn_*`, `moe_*` and `flash_d256_*` are keyed to the qwen3next sizes:
  they stay silent here and are not listed for this cell."""
  for name in ("gdn_scan_ms", "moe_route_ms", "moe_experts_roofline",
               "flash_d256_fwd_roofline", "flash_d256_bwd_roofline"):
    assert manifest.layer_metric_reader(name)(_run()) is None


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


def test_buffer_rows_are_the_programs():
  from tensor2robot_tpu.layers import moe as moe_lib

  sizes = _sizes()
  layer = moe_lib.ShardedExpertsMoE(
      num_experts=128, experts_held=(0, 8), top_k=6,
      buffer_factor=sizes["expert_buffer_factor"])
  # 4096 x 6 x 8 / 128 = 1536 rows balanced, 192 an expert
  assert layer.clone(buffer_factor=1.0).buffer_rows(4096) == 1536
  assert layer.buffer_rows(4096) == moe_route_ms.buffer_rows(sizes, 4096)
  assert layer.buffer_rows(4096) == 1536 * sizes["expert_buffer_factor"]
  assert layer.buffer_rows(256) == moe_route_ms.buffer_rows(sizes, 256)
