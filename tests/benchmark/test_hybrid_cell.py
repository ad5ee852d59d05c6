"""The hybrid decoder's cell: `model_flops` against a count by hand, its
configuration file against itself, and each of its per-layer readers on
nothing and on a small trace written by hand."""

import json
import os

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import peaks
from benchmarks.harness import trace_reduce as tr
from benchmarks.layer_metrics import moe_route_ms
from benchmarks.references import qwen3next_80b_a3b_ep16share as ref

CELL = "qwen3next_train_T4096"
CONFIG = "qwen3next_80b_a3b_ep16share"
D0 = "/device:TPU:0"
# The cell's own metrics, each with its layer.
METRIC_LAYERS = {
    "gdn_scan_ms": "linear attention", "moe_route_ms": "experts",
    "moe_experts_roofline": "kernels", "flash_d256_fwd_roofline": "kernels",
    "flash_d256_bwd_roofline": "kernels", "moe_buffer_fill": "experts",
    "moe_load_max_over_mean": "experts"}
NEW_METRICS = list(METRIC_LAYERS)
# The metrics with no `workloads` list, which every cell reports.
GENERIC_METRICS = {"first_step_s", "data_wait_ms", "host_gap_ms",
                   "step_device_ms", "step_mfu", "device_idle_share",
                   "hbm_peak_gb"}


def _config():
  with open(os.path.join(manifest.BENCH_DIR, "configs",
                         CONFIG + ".json")) as f:
    return json.load(f)


def _sizes():
  return ref.sizes_from_bindings(_config()["model"])


def test_model_flops_by_hand():
  sizes = _sizes()
  # A Gated-DeltaNet layer outside its experts: q, k, v, z (2048 -> 12288),
  # b, a (2048 -> 64), the convolution of 4 over 8192 channels, out.
  linear = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048
  assert linear == pytest.approx(33.7e6, rel=2e-3)
  # The chunked rule a chunk of 64 and head of 128: k_beta.k^T, q.k^T, the
  # inverse on k_beta (3 x 64 x 64 x 128), the inverse on v_beta and the
  # scores on the writes (2 x 64 x 64 x 128), forward substitution (64^3 / 3),
  # three products with the [128, 128] state; 32 heads, 64 tokens a chunk.
  rule = (5 * 64 * 64 * 128 + 64 ** 3 / 3 + 3 * 64 * 128 * 128) * 32 / 64
  assert rule == pytest.approx(2.93e6, rel=2e-3)
  # Gated attention: q and its gate, k, v, out; scores and weighted sum over
  # half of 4096 x 4096 at 16 x 256.
  full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
  attention = 2 * (4096 / 2) * 4096
  # Every layer: router, shared expert with its gate, 10 x 32 / 512 = 0.625
  # routed experts a token.
  moe = 2048 * 512 + (3 * 2048 * 512 + 2048) + 0.625 * 3 * 2048 * 512
  assert moe == pytest.approx(6.16e6, rel=2e-3)
  head = 2048 * 18992
  per_token = 3 * (linear + rule + moe) + (full + attention + moe) + head
  assert per_token == pytest.approx(217.5e6, rel=2e-3)
  assert ref.model_flops(sizes, 1) == pytest.approx(6.0 * per_token * 4096)
  assert ref.model_flops(sizes, 2) == pytest.approx(10.69e12, rel=2e-3)
  parts = ref.macs_per_token(sizes)
  assert parts["delta_rule"] == pytest.approx(rule)
  assert parts["attention"] == attention and parts["head"] == head


def test_configuration_file_states_the_cut():
  config = _config()
  catalog_row = {  # the source's config.json, every number of it
      "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
      "hidden_size": 2048, "intermediate_size": 5120,
      "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
      "linear_num_key_heads": 16, "linear_num_value_heads": 32,
      "linear_value_head_dim": 128, "max_position_embeddings": 262144,
      "moe_intermediate_size": 512, "num_attention_heads": 16,
      "num_experts": 512, "num_experts_per_tok": 10, "num_hidden_layers": 48,
      "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
      "rms_norm_eps": 1e-06, "rope_theta": 10000000,
      "shared_expert_intermediate_size": 512, "vocab_size": 151936}
  differs = {k for k, v in catalog_row.items() if config[k] != v}
  assert differs == {"num_experts", "vocab_size"} <= set(config["reduced"])
  assert config["reduced"] == ["layers", "num_experts", "vocab_size"]
  assert set(config["reduced"]) == set(config["reduced_stands_for"])
  assert config["layers"] == 4 == config["model"]["layers"]
  assert config["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                 "vocab_size": 151936}
  # the floors of a model_config PR: a whole period, 8 experts, 1/8 of ids
  assert config["layers"] % config["full_attention_interval"] == 0
  assert config["num_experts"] >= 8
  assert config["vocab_size"] * 8 == 151936
  for key, value in config["model"].items():  # one size, one value
    if key in catalog_row and key not in ("num_experts", "vocab_size"):
      assert value == catalog_row[key], key
  assert config["model"]["router_width"] == 512
  assert config["model"]["num_experts"] == config["num_experts"] == 32
  assert "16" in config["deployment"] and "192 chips" in config["deployment"]
  for key in ("expert_buffer_factor", "optimizer", "weights",
              "multi_token_prediction", "kv_layout", "batch_size",
              "sequence_length"):
    assert key in config["assumed"]


def test_gin_file_binds_the_configurations_sizes():
  from tensor2robot_tpu.utils import config as config_lib

  cell = manifest.Cell(CELL)
  config_lib.clear_config()
  try:
    config_lib.parse_config_files_and_bindings(
        [os.path.join(manifest.ROOT, f) for f in cell.config["gin_files"]],
        list(cell.traffic["bindings"]))
    model = config_lib.query_parameter("train_eval_model.model")
  finally:
    config_lib.clear_config()
  decoder = model._decoder_config
  sizes = cell.config["model"]
  for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "partial_rotary_factor", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim",
              "num_experts_per_tok", "moe_intermediate_size",
              "shared_expert_intermediate_size", "expert_buffer_factor",
              "rms_norm_eps"):
    assert getattr(decoder, key) == sizes[key], key
  assert decoder.rope_theta == sizes["rope_theta"]
  assert decoder.num_experts == sizes["router_width"] == 512
  assert decoder.experts_held == (sizes["first_expert"], sizes["num_experts"])
  assert decoder.layer_types == ("linear", "linear", "linear", "full")
  assert model._vocab_size == sizes["vocab_size"] == 18992
  assert model._sequence_length == cell.traffic["model"]["sequence_length"]
  assert cell.traffic["fields"]["features/tokens"]["high"] == 18992
  assert cell.traffic["fields"]["labels/targets"]["high"] == 18992


# -- the readers ---------------------------------------------------------------


def _op(name, start_ms, dur_ms, line=tr.OPS_LINE):
  return (D0, line, name, start_ms * 1e6, dur_ms * 1e6)


def _trace():
  """Two steps of 100 ms; in each: a scan of 20 ms whose body ops lie inside
  it, a chunk-layout fusion of 2, a sort of 1, a gather of 3, a router fusion
  of 1, four grouped products of 0.5, one flash forward of 4 and backward of
  8, and 10 ms of something else."""
  events = []
  for step in range(2):
    t = 1000.0 + 100.0 * step
    events.append(_op("jit_t2r_train_step(123)", t, 100.0, tr.MODULE_LINE))
    events.append(_op(
        "%while.9 = (s32[], f32[2,32,128,128]{3,2,1,0}, bf16[64,2,32,64,128]"
        "{4,3,2,1,0}) while((s32[], f32[2,32,128,128]{3,2,1,0}) %tuple.1), "
        "condition=%c, body=%b", t, 20.0))
    events.append(_op("%fusion.7 = f32[2,32,64,128]{3,2,1,0} fusion(f32[2,32,"
                      "128,128]{3,2,1,0} %p)", t + 1, 5.0))  # inside the loop
    events.append(_op("%fusion.8 = f32[64,2,32,64,64]{4,3,2,1,0} fusion("
                      "f32[64,2,32,64]{3,2,1,0} %g), kind=kLoop", t + 20, 2.0))
    events.append(_op("%sort.3 = (s32[81920]{0}, s32[81920]{0}) sort(s32[81920]"
                      "{0} %k, s32[81920]{0} %i), dimensions={0}", t + 22, 1.0))
    events.append(_op("%fusion.11 = bf16[10240,2048]{1,0} fusion(bf16[8192,2048]"
                      "{1,0} %x, s32[10240]{0} %t), kind=kCustom", t + 23, 3.0))
    events.append(_op("%fusion.12 = f32[8192,512]{1,0} fusion(bf16[8192,2048]"
                      "{1,0} %x, bf16[2048,512]{1,0} %w), kind=kOutput",
                      t + 26, 1.0))
    for i, (out, lhs, rhs) in enumerate([
        ("f32[10240,1024]", "bf16[10240,2048]", "bf16[32,2048,1024]"),
        ("f32[10240,2048]", "bf16[10240,512]", "bf16[32,512,2048]"),
        ("f32[10240,2048]", "bf16[10240,1024]", "bf16[32,2048,1024]"),
        ("f32[32,2048,1024]", "bf16[10240,2048]", "bf16[10240,1024]")]):
      events.append(_op(
          f"%ragged-dot-none.{i} = {out}{{1,0}} custom-call(s32[1]{{0}} %m, "
          f"{lhs}{{1,0}} %a, {rhs}{{1,0}} %b), custom_call_target="
          '"tpu_custom_call"', t + 27 + 0.5 * i, 0.5))
    events.append(_op("%ragged-dot-metadata.1 = (s32[33]{0}, s32[51]{0}) "
                      'custom-call(s32[32]{0} %gs), custom_call_target='
                      '"tpu_custom_call"', t + 29, 0.1))
    events.append(_op("%flash_fwd.3 = (bf16[2,4096,4096]{2,1,0}, f32[32,4096,1]"
                      "{2,1,0}) custom-call(bf16[2,4096,4096]{2,1,0} %q), "
                      'custom_call_target="tpu_custom_call"', t + 30, 4.0))
    events.append(_op("%flash_bwd.5 = (bf16[2,4096,4096]{2,1,0}, bf16[2,4096,"
                      "4096]{2,1,0}, bf16[2,4096,4096]{2,1,0}) custom-call("
                      'bf16[2,4096,4096]{2,1,0} %q), custom_call_target='
                      '"tpu_custom_call"', t + 34, 8.0))
    events.append(_op("%fusion.99 = bf16[2,4096,2048]{2,1,0} fusion(bf16[2,4096,"
                      "2048]{2,1,0} %y), kind=kLoop", t + 42, 10.0))
  return events


def _run():
  record = {f"moe_rows_held/layer_{i}": 5120.0 for i in range(4)}
  record.update({f"moe_buffer_fill/layer_{i}": 0.5 + 0.01 * i
                 for i in range(4)})
  record.update({f"moe_load_max_over_mean/layer_{i}": 1.2 + 0.1 * i
                 for i in range(4)})
  later = dict(record, **{"moe_load_max_over_mean/layer_0": 1.9,
                          "moe_buffer_fill/layer_1": 0.56})
  # the hand trace is of 2 sequences a step and a buffer of twice the balance
  return {"events": _trace(),
          "sizes": dict(_sizes(), expert_buffer_factor=2.0), "batch_size": 2,
          "peaks": peaks.peaks_for("TPU v5 lite"),
          "stepstats": [(10, record), (20, later), (30, dict(record))]}


def _expected():
  v5e = peaks.peaks_for("TPU v5 lite")
  flops, bw = v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"]
  square = 2 * 16 * 4096 * 4096 / 2.0
  fwd = max(4 * square * 256 / flops,
            (4 * 32 * 4096 * 256 * 2 + 32 * 4096 * 4) / bw)
  bwd = max(10 * square * 256 / flops,
            (7 * 32 * 4096 * 256 * 2 + 2 * 32 * 4096 * 4) / bw)
  # the four grouped products: FLOPs of 5120 rows against their bytes at
  # half a buffer
  mb = 1e6
  least = 0.0
  for weights, rows in [
      (32 * 2048 * 1024 * 2, 10240 * 1024 * 4 + 10240 * 2048 * 2),
      (32 * 512 * 2048 * 2, 10240 * 2048 * 4 + 10240 * 512 * 2),
      (32 * 2048 * 1024 * 2, 10240 * 2048 * 4 + 10240 * 1024 * 2),
      (32 * 2048 * 1024 * 4, 10240 * 2048 * 2 + 10240 * 1024 * 2)]:
    matrix = 2048 * 1024 if weights != 32 * 512 * 2048 * 2 else 512 * 2048
    least += max(2 * 5120 * matrix / flops, (weights + rows * 0.5) / bw)
  del mb
  return {
      "gdn_scan_ms": 22.0,            # the loop and the chunk-layout fusion
      "moe_route_ms": 5.0,            # sort, gather, router
      "moe_experts_roofline": 100.0 * least / 2e-3,
      "flash_d256_fwd_roofline": 100.0 * fwd / 4e-3,
      "flash_d256_bwd_roofline": 100.0 * bwd / 8e-3,
      "moe_buffer_fill": 56.0,
      "moe_load_max_over_mean": 1.5,  # worst layer 1.5, 1.9, 1.5: the median
  }


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_on_a_trace_written_by_hand(name):
  read = manifest.layer_metric_reader(name)
  assert read(_run()) == pytest.approx(_expected()[name], rel=1e-6)
  assert read({}) is None
  # Another configuration's run, and a program without the counters (a parent
  # commit): nothing to read, nothing returned.
  other = dict(_run(), sizes={"num_heads": 8, "hidden_size": 512,
                              "sequence_length": 2048})
  bare = dict(_run(), stepstats=[(10, {"data_wait_ms": 0.05})])
  if name in ("moe_buffer_fill", "moe_load_max_over_mean",
              "moe_experts_roofline"):
    assert read(bare) is None
  if name not in ("moe_buffer_fill", "moe_load_max_over_mean"):
    assert read(other) is None
    assert read(dict(_run(), events=[])) is None


def test_shares_stay_under_their_ceiling_on_the_hand_trace():
  for name in NEW_METRICS:
    if name.endswith("_roofline"):
      assert 0 < manifest.layer_metric_reader(name)(_run()) <= 105.0


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
  assert reported >= set(NEW_METRICS) | GENERIC_METRICS
  assert CELL in {w["name"] for w in benchmark["workloads"]}
  assert CONFIG in {c["name"] for c in benchmark["configs"]}


def test_new_metrics_are_listed_with_their_cell():
  check_listing(manifest.load_benchmark())


def test_buffer_rows_are_the_programs():
  from tensor2robot_tpu.layers import moe as moe_lib

  layer = moe_lib.ShardedExpertsMoE(num_experts=512, experts_held=(0, 32),
                                    top_k=10)
  assert _sizes()["expert_buffer_factor"] == 8.0
  assert layer.buffer_rows(8192) == moe_route_ms.buffer_rows(
      dict(_sizes(), expert_buffer_factor=2.0), 8192) == 10240
  layer = layer.clone(buffer_factor=8.0)
  assert layer.buffer_rows(4096) == moe_route_ms.buffer_rows(
      _sizes(), 4096) == 20480
  assert layer.buffer_rows(256) == moe_route_ms.buffer_rows(
      _sizes(), 256) == 1280


# -- the streamed driver ---------------------------------------------------------


def test_streamed_numbers_are_the_comparisons_own(monkeypatch):
  """`trainer_streamed.training_numbers` against `compare.training_numbers`
  on the same trees: every number equal (to the order of a float64 sum), with
  leaves longer and shorter than a piece."""
  import numpy as np

  from benchmarks.drivers import trainer_streamed
  from benchmarks.harness import compare

  rng = np.random.default_rng(3)
  monkeypatch.setattr(trainer_streamed, "CHUNK", 16)

  def tree(scale=1.0):
    return {"a": {"kernel": rng.normal(size=(5, 7)) * scale,
                  "bias": rng.normal(size=(7,)) * 1e-6 * scale},
            "b": rng.normal(size=(3, 2, 4)) * scale,
            "c": {"d": {"e": rng.normal(size=(9,)) * scale}}}

  def noisy(t, eps):
    return {k: noisy(v, eps) if isinstance(v, dict)
            else (v * (1 + eps * rng.normal(size=v.shape))).astype(np.float32)
            for k, v in t.items()}

  params0, gradient, params = tree(), tree(0.1), tree()
  reference = {"losses": [2.0, 1.9, 1.8], "params0": params0,
               "first_gradient": gradient, "params": params}
  program = {"losses": [2.001, 1.9, float("nan")],
             "params0": noisy(params0, 0.0),
             "first_gradient": noisy(gradient, 1e-2),
             "params": noisy(params, 1e-3)}
  want = compare.training_numbers(program, reference)
  got = trainer_streamed.training_numbers(program, reference)
  assert sorted(got) == sorted(want)
  for name in want:
    for key in want[name]:
      g, w = got[name][key], want[name][key]
      if isinstance(w, float) and w == w:  # sums taken in pieces: the last bits
        assert g == pytest.approx(w, rel=1e-12), (name, key)
      elif isinstance(w, dict):
        assert g == pytest.approx(w, rel=1e-12), (name, key)
      else:
        assert g == w or (g != g and w != w), (name, key)  # nan beside nan
  assert got["param_change"]["left_out"] == ["a/bias"]
  assert got["loss3"]["value"] == float("inf")
  with pytest.raises(ValueError):
    trainer_streamed.training_numbers(
        dict(program, params={"a": program["params"]["a"]}), reference)


def test_streamed_driver_is_the_trainers_run(monkeypatch):
  from benchmarks.drivers import trainer
  from benchmarks.drivers import trainer_streamed
  from benchmarks.harness import compare

  seen = {}
  monkeypatch.setattr(trainer, "run", lambda cell, options: seen.update(
      compare=trainer.compare) or {"ok": True})
  assert trainer_streamed.run("cell", {}) == {"ok": True}
  assert seen["compare"] is trainer_streamed.STREAMED
  assert trainer.compare is compare  # put back, also after an error
  assert trainer_streamed.STREAMED.decide is compare.decide
  assert manifest.Cell(CELL).traffic["driver"] == "trainer_streamed"
