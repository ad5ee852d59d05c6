"""The one-slot layer kinds of `layers/decoder.py` (`mamba`, `attention`,
`experts`), the sigmoid-routed relu^2 form of `layers/moe.ShardedExpertsMoE`
and `models/hybrid_lm.py` over them, at tiny sizes on the CPU, against the
benchmark's plain reference of Nemotron-3-Nano and against themselves.

Tolerances: program and reference are float32 here and differ in the order
of their sums alone (the chunked scan against the recurrence, the sorted
grouped products against a masked loop, flash's online softmax against whole
rows), so values agree to 2e-5 absolute where entries are of order 1, and
gradient and update norms to 2e-3 of the leaf's (the same bounds
`test_hybrid_lm.py` holds the two-slot kinds to)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import compare
from benchmarks.harness import traffic
from benchmarks.references import nemotron3_nano_30b_a3b_ep16share as ref
from tensor2robot_tpu.layers import decoder
from tensor2robot_tpu.layers import moe as moe_lib
from tensor2robot_tpu.models import hybrid_lm
from tensor2robot_tpu.ops import attention as attention_ops
from tensor2robot_tpu.ops import grouped_matmul
from tensor2robot_tpu.parallel import train_step as ts

SEED = 2_147_483_659  # more than 32 signed bits hold

TINY = {
    "sequence_length": 128, "vocab_size": 96, "hidden_size": 64,
    "norm_eps": 1e-5, "mamba_num_heads": 4, "mamba_head_dim": 16,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "chunk_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "routed_scaling_factor": 2.5,
}
PATTERN = "ME*E"
KINDS = ("mamba", "experts", "attention", "experts")
IDENTITY = lambda y: y  # noqa: E731


def _model(experts_held=(0, 4), n_routed_experts=8, **kwargs):
  return hybrid_lm.HybridDecoderLM(
      device_type="cpu", layer_types=KINDS,
      n_routed_experts=n_routed_experts, experts_held=experts_held,
      loss_chunk=64, **{**TINY, **kwargs})


def _sizes(first=0, held=4, router=8, **kwargs):
  return ref.sizes_from_bindings({
      **TINY, "pattern": PATTERN, "router_width": router,
      "num_experts": held, "first_expert": first,
      "reference_query_rows": 64, "reference_span": 16, **kwargs})


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
  return decoder.DecoderConfig(
      layer_types=KINDS, n_routed_experts=8, experts_held=(0, 4),
      flash_interpret=True, **{**sizes, **kwargs})


@pytest.fixture(scope="module")
def pair():
  """The program's and the reference's first three float32 steps."""
  from benchmarks.drivers import trainer
  from tensor2robot_tpu.parallel import mesh as mesh_lib

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
  program["buffers_after"] = jax.device_get(state.mutable_state)
  return program, ref.train_steps(SEED, _sizes(), pool), model, pool


def test_reference_draws_the_trainers_weights(pair):
  program, reference, _, _ = pair
  p = compare.flatten(program["params0"])
  r = compare.flatten(reference["params0"])
  assert sorted(p) == sorted(r)
  for key in p:
    assert np.array_equal(p[key], r[key]), key
  mixer = "layer_0/mixer/"
  np.testing.assert_allclose(p[mixer + "A_log"], np.log([1, 2, 3, 4]),
                             rtol=1e-6)
  assert np.all(p[mixer + "D"] == 1.0)
  # dt_bias is the inverse softplus of a step in [0.001, 0.1]
  dt = np.log1p(np.exp(p[mixer + "dt_bias"]))
  assert np.all((dt > 0.00099) & (dt < 0.1001))
  assert np.all(np.abs(p[mixer + "conv_kernel"]) <= 0.5)
  assert np.all(np.abs(p[mixer + "conv_bias"]) <= 0.5)
  assert np.ptp(p[mixer + "conv_bias"]) > 0.3
  for norm in ("layer_0/norm/weight", "layer_1/norm/weight",
               "norm_final/weight", mixer + "norm_weight"):
    assert np.all(p[norm] == 1.0), norm


def test_tree_has_one_slot_a_layer(pair):
  program, _, _, _ = pair
  tree = program["params0"]
  assert sorted(tree) == ["embed", "head", "layer_0", "layer_1", "layer_2",
                          "layer_3", "norm_final"]
  assert sorted(tree["layer_0"]) == ["mixer", "norm"]
  assert sorted(tree["layer_1"]) == ["moe", "norm"]
  assert sorted(tree["layer_2"]) == ["mixer", "norm"]
  assert sorted(tree["layer_0"]["mixer"]) == [
      "A_log", "D", "conv_bias", "conv_kernel", "dt_bias", "in_proj",
      "norm_weight", "out_proj"]
  # [z | x, B, C | dt]: 64 + (64 + 2 x 2 x 16) + 4
  assert tree["layer_0"]["mixer"]["in_proj"]["kernel"].shape == (64, 196)
  assert sorted(tree["layer_1"]["moe"]) == [
      "experts_down", "experts_up", "router", "shared_down_proj",
      "shared_up_proj"]
  assert tree["layer_1"]["moe"]["experts_up"].shape == (4, 64, 32)
  assert tree["layer_1"]["moe"]["shared_up_proj"]["kernel"].shape == (64, 48)
  assert sorted(tree["layer_2"]["mixer"]) == ["k_proj", "o_proj", "q_proj",
                                              "v_proj"]
  assert tree["layer_2"]["mixer"]["q_proj"]["kernel"].shape == (64, 128)


def test_selection_bias_is_a_buffer_no_step_moves(pair):
  program, _, _, _ = pair
  for buffers in (program["buffers"], program["buffers_after"]):
    flat = compare.flatten(buffers)
    assert sorted(flat) == [
        "buffers/layer_1/moe/e_score_correction_bias",
        "buffers/layer_3/moe/e_score_correction_bias"]
    assert all(v.shape == (8,) and not v.any() for v in flat.values())
  assert "e_score_correction_bias" not in str(sorted(compare.flatten(
      program["params0"])))


def test_three_float32_steps_agree_with_the_reference(pair):
  program, reference, _, _ = pair
  numbers = compare.training_numbers(program, reference)
  assert numbers["initial_weights"]["value"] == 0.0
  for name in ("loss1", "loss2", "loss3"):
    assert numbers[name]["value"] < 1e-5, numbers[name]
  assert numbers["first_gradient"]["value"] < 2e-3, numbers["first_gradient"]
  assert numbers["param_change"]["value"] < 2e-3, numbers["param_change"]
  assert numbers["param_change"]["left_out"] == []
  # A state left unchanged reads 1.
  unchanged = dict(reference, params=reference["params0"])
  assert compare.training_numbers(unchanged, reference)["param_change"][
      "value"] == pytest.approx(1.0)


def test_logits_and_their_gradients_agree_with_the_reference(pair):
  program, _, model, pool = pair
  params = jax.tree_util.tree_map(jnp.asarray, program["params0"])
  variables = {"params": params, **program["buffers"]}
  tokens = jnp.asarray(pool[0]["features/tokens"])
  probe = jax.random.normal(jax.random.PRNGKey(1), tokens.shape + (96,))

  def program_logits(p):
    out, _ = model.inference_network_fn(dict(variables, params=p),
                                        {"tokens": tokens}, "predict")
    return out["logits"]

  def reference_logits(p):
    return ref.logits_fn(p, tokens, _sizes(), IDENTITY)

  np.testing.assert_allclose(program_logits(params),
                             reference_logits(params), atol=2e-5)
  g_got = jax.grad(lambda p: jnp.sum(program_logits(p) * probe))(params)
  g_want = jax.grad(lambda p: jnp.sum(reference_logits(p) * probe))(params)
  gaps = compare.leaf_gaps(compare.flatten(jax.device_get(g_got)),
                           compare.flatten(jax.device_get(g_want)))
  assert max(gaps.values()) < 2e-3, max(gaps, key=gaps.get)


def test_half_the_batch_reads_far_above_rounding(pair):
  _, reference, _, pool = pair
  half = ref.train_steps(SEED, _sizes(), pool, rows=slice(0, 2))
  numbers = compare.training_numbers(half, reference)
  assert numbers["loss1"]["value"] > 0.2


def test_counters_are_those_of_the_layers_that_have_experts(pair):
  program, _, model, _ = pair
  metrics = program["metrics"]
  assert model.step_counter_prefixes == ("moe_",)
  assert sorted(k for k in metrics if k.startswith("moe_rows_held")) == [
      "moe_rows_held/layer_1", "moe_rows_held/layer_3"]
  for layer in (1, 3):
    held = metrics[f"moe_rows_held/layer_{layer}"]
    # 4 x 128 tokens x 2 a token, half of the 8 experts held: 512 balanced.
    assert 350 < held < 700 and held == int(held)
    assert metrics[f"moe_buffer_fill/layer_{layer}"] == pytest.approx(
        held / 1024)
    assert metrics[f"moe_rows_dropped/layer_{layer}"] == 0.0
    assert 1.0 <= metrics[f"moe_load_max_over_mean/layer_{layer}"] < 4.0


# -- each new layer against the reference's --------------------------------------


def _layer_params(kind, seed=11):
  params, _ = ref.init_state(seed, _sizes())
  return params[f"layer_{KINDS.index(kind)}"]


def _agree(got_fn, want_fn, params, atol=2e-6):
  np.testing.assert_allclose(got_fn(params), want_fn(params), atol=atol)
  g_got = jax.grad(lambda p: jnp.sum(got_fn(p) ** 2))(params)
  g_want = jax.grad(lambda p: jnp.sum(want_fn(p) ** 2))(params)
  gaps = compare.leaf_gaps(compare.flatten(jax.device_get(g_got)),
                           compare.flatten(jax.device_get(g_want)))
  assert max(gaps.values()) < 1e-3, max(gaps, key=gaps.get)


@pytest.mark.parametrize("chunk", [32, 48])   # 48 does not divide 128
def test_mamba_mixer_is_the_references(chunk):
  params = _layer_params("mamba")["mixer"]
  # a conv bias and a D that differ from their neighbours, a dt that bites
  params = dict(params, D=jnp.linspace(0.5, 1.5, 4),
                dt_bias=params["dt_bias"] + 3.0)
  x = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 64))
  mixer = decoder.Mamba2Mixer(_cfg(chunk_size=chunk))
  _agree(lambda p: mixer.apply({"params": p}, x),
         lambda p: ref._mamba(p, x, _sizes(), IDENTITY), params, atol=5e-6)


def test_mamba_norm_gates_first_and_norms_each_group():
  """y * silu(z), then x / rms over each of the 2 groups of 32 channels."""
  params = _layer_params("mamba")["mixer"]
  x = jax.random.normal(jax.random.PRNGKey(7), (1, 128, 64))
  out = decoder.Mamba2Mixer(_cfg()).apply({"params": params}, x)
  # Scaling one group's norm weight scales that group's part of the result
  # alone: doubling both halves doubles the result.
  doubled = dict(params, norm_weight=params["norm_weight"] * 2.0)
  np.testing.assert_allclose(
      decoder.Mamba2Mixer(_cfg()).apply({"params": doubled}, x), 2.0 * out,
      atol=1e-5)
  # A gate z scaled towards 0 scales silu(z), and the norm takes it out
  # again (up to eps): the gate is inside the norm.
  kernel = np.array(params["in_proj"]["kernel"])
  flipped = kernel.copy()
  flipped[:, :64] *= -1.0   # z -> -z: silu(-z) != silu(z), the result moves
  moved = decoder.Mamba2Mixer(_cfg()).apply(
      {"params": dict(params, in_proj={"kernel": jnp.asarray(flipped)})}, x)
  assert float(jnp.max(jnp.abs(moved - out))) > 1e-3


def test_plain_attention_is_the_references_and_has_no_position():
  params = _layer_params("attention")["mixer"]
  x = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 64))
  layer = decoder.PlainAttention(_cfg())
  _agree(lambda p: layer.apply({"params": p}, x),
         lambda p: ref._attention(p, x, _sizes(), IDENTITY), params)
  # No positional embedding: the last token's output does not change when
  # the tokens before it change places.
  order = jnp.concatenate([jnp.arange(127)[::-1], jnp.array([127])])
  out = layer.apply({"params": params}, x)
  np.testing.assert_allclose(layer.apply({"params": params}, x[:, order])[
      :, -1], out[:, -1], atol=2e-6)


def test_flash_attention_at_thirty_two_heads_of_128_interpreted():
  """The cell's side of `lane_block` (one head of 128 a program) and of
  `_sum_rides`, interpreted."""
  assert attention_ops.lane_block(32, 128) == 128
  keys = jax.random.split(jax.random.PRNGKey(8), 3)
  q, k, v = (jax.random.normal(key, (1, 256, 32 * 128)) * 0.3
             for key in keys)
  heads = lambda y: y.reshape(1, 256, 32, 128).transpose(0, 2, 1, 3)  # noqa
  want = attention_ops.attention(heads(q), heads(k), heads(v), causal=True)
  got = attention_ops.flash_attention(q, k, v, 32, causal=True,
                                      block_q=128, block_k=128,
                                      interpret=True)
  np.testing.assert_allclose(heads(got), want, atol=2e-5)


def _moe(first=0, count=4, **kwargs):
  return moe_lib.ShardedExpertsMoE(
      num_experts=8, experts_held=(first, count), top_k=2, expert_width=32,
      shared_width=48, router_scoring="sigmoid", routed_scaling_factor=2.5,
      expert_form="relu2", shared_gate=False, **kwargs)


def _moe_params(sizes=None, seed=5):
  params, _ = ref.init_state(seed, sizes or _sizes())
  return params["layer_1"]["moe"]


def _bias(values):
  return {"buffers": {"e_score_correction_bias": jnp.asarray(values,
                                                             jnp.float32)}}


def test_expert_layer_is_the_references():
  params = _moe_params()
  x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))

  def want(p):
    shared, routed = ref.moe_parts(p, x, _sizes(), IDENTITY)
    return shared + routed

  _agree(lambda p: _moe().apply({"params": p, **_bias(np.zeros(8))}, x)[
      0].reshape(-1, 64), want, params, atol=5e-6)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
  """The guide's share test at the deployment's own counts: 128 experts, 6 a
  token, over 16 shares of 8. The shares' routed parts, with the shared
  expert counted once, are the uncut reference's layer. (Sums of up to six
  float32 terms of order 0.01 in another order: 1e-6 absolute.)"""
  whole_sizes = _sizes(first=0, held=128, router=128, num_experts_per_tok=6)
  whole = _moe_params(whole_sizes)
  x = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 64))
  shared, routed = ref.moe_parts(whole, x, whole_sizes, IDENTITY)
  total = jnp.zeros_like(routed)
  held_pairs = 0
  for share in range(16):
    first = 8 * share
    part = dict(whole, experts_up=whole["experts_up"][first:first + 8],
                experts_down=whole["experts_down"][first:first + 8])
    layer = moe_lib.ShardedExpertsMoE(
        num_experts=128, experts_held=(first, 8), top_k=6, expert_width=32,
        shared_width=48, buffer_factor=8.0, router_scoring="sigmoid",
        routed_scaling_factor=2.5, expert_form="relu2", shared_gate=False)
    out, counters = layer.apply({"params": part, **_bias(np.zeros(128))}, x)
    assert counters["moe_rows_dropped"] == 0
    held_pairs += int(counters["moe_rows_held"])
    total = total + (out.reshape(-1, 64) - shared)
    if share in (0, 15):  # the reference given the same share: the same part
      _, ref_part = ref.moe_parts(
          part, x, _sizes(first=first, held=8, router=128,
                          num_experts_per_tok=6), IDENTITY)
      np.testing.assert_allclose(out.reshape(-1, 64) - shared, ref_part,
                                 atol=1e-6)
  assert held_pairs == 128 * 6           # every pair is held by one share
  np.testing.assert_allclose(total, routed, atol=1e-6)
  assert float(jnp.max(jnp.abs(routed))) > 1e-3


def test_sigmoid_router_picks_from_s_plus_b_and_weighs_by_s():
  sizes = _sizes()
  params = _moe_params()
  x = jax.random.normal(jax.random.PRNGKey(3), (1, 128, 64))
  tokens = x.reshape(-1, 64)
  scores = jax.nn.sigmoid(tokens @ params["router"]["kernel"])
  # b lifts experts 6 and 7 over all others: every token picks them ...
  bias = np.zeros(8, np.float32)
  bias[6:] = 10.0
  weights, picks = ref.router_picks(params, tokens, sizes, IDENTITY,
                                    jnp.asarray(bias))
  assert set(np.asarray(picks).reshape(-1)) == {6, 7}
  # ... and their weights are 2.5 x s_i / (s_6 + s_7): from s, not s + b.
  s67 = np.asarray(scores[:, 6:])
  want = 2.5 * s67 / s67.sum(-1, keepdims=True)
  got = np.take_along_axis(np.asarray(weights), np.argsort(
      np.asarray(picks), axis=-1), axis=-1)
  np.testing.assert_allclose(got, want, rtol=1e-5)
  np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)
  # The program with that buffer, holding experts 4-7, is the reference's
  # layer with that bias; holding 0-3 it computes the shared expert alone.
  shared, routed = ref.moe_parts(params, x, _sizes(first=4), IDENTITY,
                                 jnp.asarray(bias))
  part = dict(params, experts_up=params["experts_up"],
              experts_down=params["experts_down"])
  out, counters = _moe(4, 4).apply({"params": part, **_bias(bias)}, x)
  np.testing.assert_allclose(out.reshape(-1, 64), shared + routed, atol=5e-6)
  assert counters["moe_rows_held"] == 256        # every pair is held here
  assert counters["moe_load_max_over_mean"] == 2.0   # 128, 128, 0, 0
  out, counters = _moe(0, 4).apply({"params": part, **_bias(bias)}, x)
  np.testing.assert_allclose(out.reshape(-1, 64), shared, atol=5e-6)
  assert counters["moe_rows_held"] == 0
  # With b = 0 the picks are the two largest s.
  _, picks0 = ref.router_picks(params, tokens, sizes, IDENTITY)
  np.testing.assert_array_equal(np.sort(np.asarray(picks0), -1), np.sort(
      np.argsort(np.asarray(scores), -1)[:, -2:], -1))


def test_no_gradient_reaches_the_selection_bias():
  params = _moe_params()
  x = jax.random.normal(jax.random.PRNGKey(4), (1, 128, 64))
  grads = jax.grad(lambda variables: jnp.sum(_moe().apply(variables, x)[0]
                                             ** 2))(
      {"params": params, **_bias(np.full(8, 0.01))})
  assert not np.asarray(
      grads["buffers"]["e_score_correction_bias"]).any()
  assert np.asarray(grads["params"]["router"]["kernel"]).any()


def test_relu2_experts_have_one_up_product_and_fill_the_buffer():
  """Un-gated: two grouped products a call (up, down), their group sizes
  adding up to the buffer whatever the router picked."""
  seen = []
  real = grouped_matmul.grouped_matmul

  def spy(lhs, rhs, group_sizes, **kwargs):
    seen.append((lhs.shape, rhs.shape, group_sizes))
    return real(lhs, rhs, group_sizes, **kwargs)

  x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))
  grouped_matmul.grouped_matmul = spy
  try:
    _moe().apply({"params": _moe_params(), **_bias(np.zeros(8))}, x)
  finally:
    grouped_matmul.grouped_matmul = real
  assert [(lhs, rhs) for lhs, rhs, _ in seen] == [
      ((512, 64), (4, 64, 32)), ((512, 32), (4, 32, 64))]
  assert all(int(jnp.sum(sizes)) == 512 for _, _, sizes in seen)


def test_unknown_forms_are_refused():
  x = jnp.zeros((1, 8, 64))
  for bad in (dict(router_scoring="tanh"), dict(expert_form="gelu")):
    with pytest.raises(ValueError):
      moe_lib.ShardedExpertsMoE(**bad).init(jax.random.PRNGKey(0), x)
  with pytest.raises(ValueError):
    decoder.HybridDecoderBlock(_cfg(), "dense").init(jax.random.PRNGKey(0),
                                                     x)


# -- what stays as it was --------------------------------------------------------


def test_the_two_slot_configurations_tree_is_what_it_was():
  """Every path and shape of the qwen3next tree, at the sizes
  `test_hybrid_lm.py` uses: its reference compares leaves by path."""
  model = hybrid_lm.HybridDecoderLM(
      device_type="cpu", sequence_length=128, vocab_size=96, hidden_size=64,
      num_attention_heads=4, num_key_value_heads=2, head_dim=32,
      linear_num_key_heads=2, linear_num_value_heads=4,
      linear_key_head_dim=16, linear_value_head_dim=16, num_experts=8,
      experts_held=(0, 4), num_experts_per_tok=2, moe_intermediate_size=32,
      shared_expert_intermediate_size=32, loss_chunk=64)
  variables = jax.eval_shape(
      lambda: model.init_variables(jax.random.PRNGKey(0),
                                   {"tokens": jnp.zeros((2, 128), jnp.int32)}))
  assert sorted(variables) == ["params"]     # no buffer: the softmax router
  shapes = {k: v.shape for k, v in _flat(variables["params"]).items()}
  moe = {
      "moe/experts_down": (4, 32, 64), "moe/experts_gate_up": (4, 64, 64),
      "moe/router/kernel": (64, 8), "moe/shared_down_proj/kernel": (32, 64),
      "moe/shared_expert_gate/kernel": (64, 1),
      "moe/shared_gate_proj/kernel": (64, 32),
      "moe/shared_up_proj/kernel": (64, 32),
      "norm_mixer/weight": (64,), "norm_moe/weight": (64,)}
  linear = {
      "mixer/A_log": (4,), "mixer/conv_kernel": (4, 128),
      "mixer/dt_bias": (4,), "mixer/in_proj_ba/kernel": (64, 8),
      "mixer/in_proj_qkvz/kernel": (64, 192), "mixer/norm_weight": (16,),
      "mixer/out_proj/kernel": (64, 64)}
  full = {
      "mixer/k_norm/weight": (32,), "mixer/k_proj/kernel": (64, 64),
      "mixer/o_proj/kernel": (128, 64), "mixer/q_norm/weight": (32,),
      "mixer/q_proj/kernel": (64, 256), "mixer/v_proj/kernel": (64, 64)}
  want = {"embed/embedding": (96, 64), "head": (64, 96),
          "norm_final/weight": (64,)}
  for i, mixer in enumerate((linear, linear, linear, full)):
    want.update({f"layer_{i}/{k}": v for k, v in {**moe, **mixer}.items()})
  assert shapes == want


def _flat(tree, prefix=""):
  out = {}
  for key in sorted(tree):
    path = f"{prefix}/{key}" if prefix else key
    if hasattr(tree[key], "keys"):
      out.update(_flat(tree[key], path))
    else:
      out[path] = tree[key]
  return out
