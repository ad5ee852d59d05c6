"""The `laguna` layer kinds of `layers/decoder.py` (`global`, `sliding`), the
dense feed-forward, the gated-SiLU experts under a sigmoid router and
`models/hybrid_lm.py` over them, at tiny sizes on the CPU, against the
benchmark's plain reference of Laguna-XS.2 and against themselves.

Tolerances: program and reference are float32 here and differ in the order
of their sums alone (the sorted grouped products against a masked loop,
flash's online softmax against whole rows), so values agree to 2e-5 absolute
where entries are of order 1, and gradient and update norms to 2e-3 of the
leaf's (the bounds `test_nemotron_lm.py` holds the one-slot kinds to)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import compare
from benchmarks.harness import traffic
from benchmarks.references import laguna_xs2_ep16share as ref
from tensor2robot_tpu.layers import decoder
from tensor2robot_tpu.layers import moe as moe_lib
from tensor2robot_tpu.models import hybrid_lm

SEED = 2_147_483_671  # more than 32 signed bits hold

ROPE = {"full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                           "factor": 64,
                           "original_max_position_embeddings": 4096,
                           "beta_slow": 1, "beta_fast": 64,
                           "attention_factor": 1.4158883083359672,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
# The leading dense layer, a sliding layer and a global one with experts:
# both kinds, both feed-forwards and both head counts, at 2 and 4 heads of 32.
KINDS = ("global", "sliding", "global")
FFN = ("dense", "sparse", "sparse")
HEADS = (2, 4, 2)
TINY = {
    "sequence_length": 128, "vocab_size": 96, "hidden_size": 64,
    "rms_norm_eps": 1e-6, "num_key_value_heads": 2, "head_dim": 32,
    "sliding_window": 16, "intermediate_size": 96, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 48,
    "routed_scaling_factor": 2.5,
}
IDENTITY = lambda y: y  # noqa: E731


LISTS = {"layer_types": KINDS, "mlp_layer_types": FFN,
         "num_attention_heads_per_layer": HEADS}


def _model(**kwargs):
  return hybrid_lm.HybridDecoderLM(**{
      "device_type": "cpu", **LISTS, "rope_parameters": ROPE,
      "num_experts": 8, "experts_held": (0, 4), "loss_chunk": 64, **TINY,
      **kwargs})


def _sizes(first=0, held=4, router=8, **kwargs):
  return ref.sizes_from_bindings({
      **TINY, **LISTS, "rope_parameters": ROPE, "router_width": router,
      "num_experts": held, "first_expert": first,
      "reference_query_rows": 64, **kwargs})


def _pool(batch=4, batches=3):
  return traffic.make_pool(
      {"features/tokens": ((128,), np.int32),
       "labels/targets": ((128,), np.int32),
       "labels/weight": ((1,), np.float32)}, batch, batches, SEED,
      {"features/tokens": {"dist": "uniform_int", "low": 0, "high": 96},
       "labels/targets": {"dist": "uniform_int", "low": 0, "high": 96},
       "labels/weight": {"dist": "uniform", "low": 1.0, "high": 1.0,
                         "row_ramp": [0.5, 1.5]}})


def _cfg(**kwargs):
  sizes = {k: v for k, v in TINY.items()
           if k not in ("sequence_length", "vocab_size")}
  return decoder.DecoderConfig(**{
      "layer_types": KINDS, "mlp_layer_types": FFN,
      "num_attention_heads_per_layer": HEADS,
      "rope_parameters": decoder.freeze_rope_parameters(ROPE),
      "num_experts": 8, "experts_held": (0, 4), "flash_interpret": True,
      **sizes, **kwargs})


@pytest.fixture(scope="module")
def pair():
  """The program's and the reference's first three float32 steps."""
  from benchmarks.drivers import trainer
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.parallel import train_step as ts

  model, pool = _model(), _pool()
  features = [{"tokens": b["features/tokens"]} for b in pool]
  labels = [{"targets": b["labels/targets"], "weight": b["labels/weight"]}
            for b in pool]
  mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
  state, shardings = ts.create_train_state(
      model, jax.random.PRNGKey(SEED), features[0], mesh=mesh)
  program = {"params0": jax.device_get(state.params), "losses": [],
             "buffers": jax.device_get(state.mutable_state)}
  step = ts.make_train_step(model, mesh=mesh, shardings=shardings,
                            donate=False)
  for i, (f, l) in enumerate(zip(features, labels)):
    state, metrics = step(state, f, l)
    program["losses"].append(float(metrics["loss"]))
    if i == 0:
      program["first_gradient"] = trainer._first_gradient(
          state.opt_state, {"from": "mu", "scale": 10.0})
      program["metrics"] = {k: float(v) for k, v in metrics.items()}
  program["params"] = jax.device_get(state.params)
  return program, ref.train_steps(SEED, _sizes(), pool), model, pool


def test_reference_draws_the_trainers_weights(pair):
  program, reference, _, _ = pair
  p = compare.flatten(program["params0"])
  r = compare.flatten(reference["params0"])
  assert sorted(p) == sorted(r)
  for key in p:
    assert np.array_equal(p[key], r[key]), key
  norms = [k for k in p if "norm" in k]
  assert len(norms) == 1 + 3 * 4      # final; each layer's two and q, k
  assert all(np.all(p[k] == 1.0) for k in norms)


def test_kinds_heads_and_feed_forwards_follow_the_two_lists(pair):
  program, _, _, _ = pair
  tree = program["params0"]
  assert sorted(tree) == ["embed", "head", "layer_0", "layer_1", "layer_2",
                          "norm_final"]
  assert sorted(tree["layer_0"]) == ["mixer", "mlp", "norm_mixer", "norm_mlp"]
  assert sorted(tree["layer_0"]["mlp"]) == ["down_proj", "gate_proj",
                                            "up_proj"]
  assert tree["layer_0"]["mlp"]["gate_proj"]["kernel"].shape == (64, 96)
  for i in range(1, 3):
    assert sorted(tree[f"layer_{i}"]) == ["mixer", "moe", "norm_mixer",
                                          "norm_moe"], i
    assert sorted(tree[f"layer_{i}"]["moe"]) == [
        "experts_down", "experts_gate_up", "router", "shared_down_proj",
        "shared_gate_proj", "shared_up_proj"]
    assert tree[f"layer_{i}"]["moe"]["experts_gate_up"].shape == (4, 64, 64)
  for i, heads in enumerate(HEADS):
    mixer = tree[f"layer_{i}"]["mixer"]
    # [q | gate], heads x 32 each; k, v at 2 heads; o from heads x 32
    assert mixer["q_proj"]["kernel"].shape == (64, 2 * heads * 32), i
    assert mixer["k_proj"]["kernel"].shape == (64, 64)
    assert mixer["o_proj"]["kernel"].shape == (heads * 32, 64)
    assert mixer["q_norm"]["weight"].shape == (32,)


def test_three_float32_steps_agree_with_the_reference(pair):
  program, reference, _, _ = pair
  numbers = compare.training_numbers(program, reference)
  assert numbers["initial_weights"]["value"] == 0.0
  for name in ("loss1", "loss2", "loss3"):
    assert numbers[name]["value"] < 1e-5, numbers[name]
  assert numbers["first_gradient"]["value"] < 2e-3, numbers["first_gradient"]
  assert numbers["param_change"]["value"] < 2e-3, numbers["param_change"]
  assert numbers["param_change"]["left_out"] == []


def test_logits_agree_with_the_reference(pair):
  """The logits of the PREDICT path (every gradient is held by the three
  steps' numbers above)."""
  program, _, model, pool = pair
  params = jax.tree_util.tree_map(jnp.asarray, program["params0"])
  tokens = jnp.asarray(pool[0]["features/tokens"])
  got = jax.jit(lambda p: model.inference_network_fn(
      {**program["buffers"], "params": p}, {"tokens": tokens},
      "predict")[0]["logits"])(params)
  want = jax.jit(lambda p: ref.logits_fn(p, tokens, _sizes(), IDENTITY))(
      params)
  np.testing.assert_allclose(got, want, atol=2e-5)


def test_counters_are_those_of_the_sparse_layers(pair):
  program, _, model, _ = pair
  metrics = program["metrics"]
  assert sorted(k for k in metrics if k.startswith("moe_rows_held")) == [
      f"moe_rows_held/layer_{i}" for i in range(1, 3)]
  for i in range(1, 3):
    assert metrics[f"moe_rows_dropped/layer_{i}"] == 0.0
    assert 0 < metrics[f"moe_buffer_fill/layer_{i}"] <= 1.0
  assert model.step_counter_prefixes == ("moe_",)


# -- each new part against the reference's --------------------------------------


def _layer_params(i, seed=11):
  params, _ = ref.init_state(seed, _sizes())
  return params[f"layer_{i}"]


def _agree(got_fn, want_fn, params, atol=2e-6):
  np.testing.assert_allclose(jax.jit(got_fn)(params), jax.jit(want_fn)(params),
                             atol=atol)
  g_got = jax.jit(jax.grad(lambda p: jnp.sum(got_fn(p) ** 2)))(params)
  g_want = jax.jit(jax.grad(lambda p: jnp.sum(want_fn(p) ** 2)))(params)
  gaps = compare.leaf_gaps(compare.flatten(jax.device_get(g_got)),
                           compare.flatten(jax.device_get(g_want)))
  assert max(gaps.values()) < 1e-3, max(gaps, key=gaps.get)


@pytest.mark.parametrize("layer,reach", [(0, 128), (1, 16)])
def test_attention_of_each_kind_is_the_references(layer, reach):
  """Global at 2 heads, sliding at 4 against the reference; and a change to
  token 0 reaches rows 1 .. reach - 1 and no further (the window of 16 on a
  sliding layer, every row on a global one)."""
  params = _layer_params(layer)["mixer"]
  x = jax.random.normal(jax.random.PRNGKey(6), (1, 128, 64))
  mixer = decoder.GatedAttention(_cfg(), kind=KINDS[layer],
                                 heads=HEADS[layer])
  apply = jax.jit(lambda p, x: mixer.apply({"params": p}, x))
  _agree(lambda p: apply(p, x),
         lambda p: ref._attention(p, x, layer, _sizes(), IDENTITY), params,
         atol=5e-6)
  gap = jnp.max(jnp.abs(apply(params, x.at[:, 0].add(3.0))
                        - apply(params, x)), axis=-1)[0]
  assert np.all(np.asarray(gap[1:reach]) > 1e-6)
  assert np.all(np.asarray(gap[reach:]) == 0.0)


def test_dense_feed_forward_is_the_references():
  params = _layer_params(0)["mlp"]
  x = jax.random.normal(jax.random.PRNGKey(8), (1, 128, 64))
  _agree(lambda p: decoder.DenseMLP(_cfg()).apply({"params": p}, x),
         lambda p: ref._dense_mlp(p, x, IDENTITY), params)


def _moe(first=0, count=4, num_experts=8, **kwargs):
  return moe_lib.ShardedExpertsMoE(
      num_experts=num_experts, experts_held=(first, count), top_k=2,
      expert_width=32, shared_width=48, router_scoring="sigmoid",
      routed_scaling_factor=2.5, expert_form="gated_silu", shared_gate=False,
      **kwargs)


def _bias(n):
  return {"buffers": {"e_score_correction_bias": jnp.zeros((n,),
                                                           jnp.float32)}}


def test_the_shares_add_up_to_the_uncut_layer():
  """The guide's share test: 8 experts over 4 shares, each computing
  its own experts' part with the shared expert; the parts, with the shared
  expert counted once, are the uncut reference's layer."""
  whole_sizes = _sizes(first=0, held=8)
  whole = ref.init_state(5, whole_sizes)[0]["layer_1"]["moe"]
  x = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 64))
  shared, routed = ref.moe_parts(whole, x, whole_sizes, IDENTITY)
  count = 2
  total, held_pairs = jnp.zeros_like(routed), 0
  for share in range(4):
    first = count * share
    part = dict(whole,
                experts_gate_up=whole["experts_gate_up"][first:first + count],
                experts_down=whole["experts_down"][first:first + count])
    out, counters = _moe(first, count, buffer_factor=8.0).apply(
        {"params": part, **_bias(8)}, x)
    assert counters["moe_rows_dropped"] == 0
    held_pairs += int(counters["moe_rows_held"])
    total = total + (out.reshape(-1, 64) - shared)
  assert held_pairs == 128 * 2            # every pair held by one share
  np.testing.assert_allclose(total, routed, atol=1e-6)
  assert float(jnp.max(jnp.abs(routed))) > 1e-3


def test_no_row_is_dropped_when_the_router_sends_every_token_here():
  """A router whose held columns dwarf the others: every pair lands on the
  experts held here, and the buffer (all the pairs, at this size) holds
  them all; a buffer a quarter as large drops and counts the rest."""
  params = _layer_params(1)["moe"]
  kernel = np.zeros_like(params["router"]["kernel"])
  kernel[:, :2] = 1.0
  forced = dict(params, router={"kernel": jnp.asarray(kernel)})
  x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64))) + 0.1
  layer = _moe(buffer_factor=2.0)
  out, counters = layer.apply({"params": forced, **_bias(8)}, x)
  assert layer.buffer_rows(256) == 512
  assert counters["moe_rows_held"] == 512 and counters["moe_rows_dropped"] == 0
  shared, routed = ref.moe_parts(forced, x, _sizes(), IDENTITY)
  np.testing.assert_allclose(out.reshape(-1, 64), shared + routed, atol=5e-6)
  small = _moe(buffer_factor=0.5)
  _, counters = small.apply({"params": forced, **_bias(8)}, x)
  assert counters["moe_rows_dropped"] == 512 - small.buffer_rows(256) > 0


def test_the_vocabulary_slices_logits_are_the_whole_heads_columns():
  """On the first two layers (global + dense, sliding + experts)."""
  lists = {k: v[:2] for k, v in LISTS.items()}
  sizes = _sizes(vocab_size=96, **lists)
  params, _ = ref.init_state(7, sizes)
  tokens = jnp.asarray(_pool(batch=2, batches=1)[0]["features/tokens"]) % 24
  whole = jax.jit(lambda p: ref.logits_fn(p, tokens, sizes, IDENTITY))(params)
  sliced = dict(params, head=params["head"][:, :24],
                embed={"embedding": params["embed"]["embedding"][:24]})
  model = _model(vocab_size=24, **lists)
  out, _ = jax.jit(lambda p: model.inference_network_fn(
      {"params": p, "buffers": {"layer_1": {"moe": _bias(8)["buffers"]}}},
      {"tokens": tokens}, "predict"))(sliced)
  np.testing.assert_allclose(out["logits"], whole[..., :24], atol=2e-5)


def test_experts_follow_mlp_layer_types():
  assert [decoder.has_experts(_cfg(), i) for i in range(3)] == [
      False, True, True]
  cfg = _cfg(mlp_layer_types=("sparse",) * 3)
  assert [decoder.has_experts(cfg, i) for i in range(3)] == [True] * 3
