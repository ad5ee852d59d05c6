"""The hybrid decoder (`layers/decoder.py`, `layers/moe.ShardedExpertsMoE`,
`models/hybrid_lm.py`) at tiny sizes on the CPU, against the benchmark's plain
reference and against itself."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import compare
from benchmarks.harness import traffic
from benchmarks.references import qwen3next_80b_a3b_ep16share as ref
from tensor2robot_tpu.layers import decoder
from tensor2robot_tpu.layers import moe as moe_lib
from tensor2robot_tpu.models import hybrid_lm
from tensor2robot_tpu.ops import attention as attention_ops
from tensor2robot_tpu.ops import grouped_matmul
from tensor2robot_tpu.parallel import train_step as ts

SEED = 2_147_483_659  # more than 32 signed bits hold

TINY = {
    "sequence_length": 128, "vocab_size": 96, "hidden_size": 64,
    "rms_norm_eps": 1e-6, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "partial_rotary_factor": 0.25, "rope_theta": 1e7,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
}


def _model(experts_held=(0, 4), num_experts=8, **kwargs):
  return hybrid_lm.HybridDecoderLM(
      device_type="cpu", num_experts=num_experts, experts_held=experts_held,
      loss_chunk=64, **{**TINY, **kwargs})


def _sizes(first=0, held=4, router=8, **kwargs):
  return ref.sizes_from_bindings({
      **TINY, "layers": 4, "full_attention_interval": 4,
      "router_width": router, "num_experts": held, "first_expert": first,
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


@pytest.fixture(scope="module")
def pair():
  """The program's and the reference's first three float32 steps."""
  from benchmarks.drivers import trainer

  model, pool = _model(), _pool()
  features = [{"tokens": b["features/tokens"]} for b in pool]
  labels = [{"targets": b["labels/targets"], "weight": b["labels/weight"]}
            for b in pool]
  # On a mesh, as the trainer creates it: one jitted init (compiled, the
  # scaling of a normal draw rounds in another place than op by op).
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  mesh = mesh_lib.create_mesh(devices=jax.devices()[:1])
  state, shardings = ts.create_train_state(
      model, jax.random.PRNGKey(SEED), features[0], mesh=mesh)
  program = {"params0": jax.device_get(state.params), "losses": []}
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
  assert np.all(p["layer_0/mixer/A_log"] <= np.log(16.0))
  assert np.all(p["layer_0/mixer/dt_bias"] == 1.0)


def test_three_float32_steps_agree_with_the_reference(pair):
  program, reference, _, _ = pair
  numbers = compare.training_numbers(program, reference)
  assert numbers["initial_weights"]["value"] == 0.0
  for name in ("loss1", "loss2", "loss3"):
    assert numbers[name]["value"] < 1e-5, numbers[name]
  assert numbers["first_gradient"]["value"] < 2e-3, numbers["first_gradient"]
  assert numbers["param_change"]["value"] < 2e-3, numbers["param_change"]
  # A state left unchanged reads 1; half the batch reads far above rounding.
  unchanged = dict(reference, params=reference["params0"])
  assert compare.training_numbers(unchanged, reference)["param_change"][
      "value"] == pytest.approx(1.0)


def test_logits_and_their_gradients_agree_with_the_reference(pair):
  program, _, model, pool = pair
  params = jax.tree_util.tree_map(jnp.asarray, program["params0"])
  tokens = jnp.asarray(pool[0]["features/tokens"])
  probe = jax.random.normal(jax.random.PRNGKey(1), tokens.shape + (96,))

  def program_logits(p):
    out, _ = model.inference_network_fn({"params": p}, {"tokens": tokens},
                                        "predict")
    return out["logits"]

  def reference_logits(p):
    return ref.logits_fn(p, tokens, _sizes(), lambda x: x)

  got, want = program_logits(params), reference_logits(params)
  np.testing.assert_allclose(got, want, atol=2e-5)
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


def test_counters_ride_the_steps_metrics(pair):
  program, _, model, _ = pair
  metrics = program["metrics"]
  assert model.step_counter_prefixes == ("moe_",)
  for layer in range(4):
    held = metrics[f"moe_rows_held/layer_{layer}"]
    # 4 x 128 tokens x 2 a token, half of the 8 experts held: 512 balanced.
    assert 350 < held < 700 and held == int(held)
    assert metrics[f"moe_buffer_fill/layer_{layer}"] == pytest.approx(
        held / 1024)
    assert metrics[f"moe_rows_dropped/layer_{layer}"] == 0.0
    assert 1.0 <= metrics[f"moe_load_max_over_mean/layer_{layer}"] < 4.0


# -- the share adds up ---------------------------------------------------------


def _moe_params(sizes, seed=5):
  params, _ = ref.init_state(seed, sizes)
  return params["layer_0"]["moe"]


def test_the_shares_routed_parts_add_up_to_the_uncut_layer():
  """8 experts over 4 shares of 2: the shares' routed parts, with the shared
  expert counted once, are the uncut reference's layer."""
  whole_sizes = _sizes(first=0, held=8)
  whole = _moe_params(whole_sizes)
  x = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 64))
  identity = lambda y: y  # noqa: E731
  shared, routed = ref.moe_parts(whole, x, whole_sizes, identity)
  total = jnp.zeros_like(routed)
  for share in range(4):
    first = 2 * share
    part = dict(whole,
                experts_gate_up=whole["experts_gate_up"][first:first + 2],
                experts_down=whole["experts_down"][first:first + 2])
    layer = moe_lib.ShardedExpertsMoE(
        num_experts=8, experts_held=(first, 2), top_k=2, expert_width=32,
        shared_width=32)
    out, counters = layer.apply({"params": part}, x)
    assert counters["moe_rows_dropped"] == 0
    total = total + (out.reshape(-1, 64) - shared)
    # and the reference given the same share gives the same part
    _, ref_part = ref.moe_parts(part, x, _sizes(first=first, held=2),
                                identity)
    np.testing.assert_allclose(out.reshape(-1, 64) - shared, ref_part,
                               atol=2e-6)
  np.testing.assert_allclose(total, routed, atol=5e-6)
  assert float(jnp.max(jnp.abs(routed))) > 1e-3


def test_the_vocabulary_slices_logits_are_the_whole_heads_columns():
  sizes = _sizes(vocab_size=96)
  params, _ = ref.init_state(7, sizes)
  tokens = jnp.asarray(_pool(batch=2, batches=1)[0]["features/tokens"]) % 24
  whole = ref.logits_fn(params, tokens, sizes, lambda x: x)
  sliced = dict(params, head=params["head"][:, :24],
                embed={"embedding": params["embed"]["embedding"][:24]})
  model = _model(vocab_size=24)
  out, _ = model.inference_network_fn({"params": sliced}, {"tokens": tokens},
                                      "predict")
  np.testing.assert_allclose(out["logits"], whole[..., :24], atol=2e-5)


# -- no row dropped ------------------------------------------------------------


def _forced_router(params, expert):
  """Every token to `expert` first: its router column dwarfs the others."""
  kernel = np.zeros_like(params["router"]["kernel"])
  kernel[:, expert] = 1.0
  return dict(params, router={"kernel": jnp.asarray(kernel)})


def test_no_row_is_dropped_when_every_token_goes_to_one_held_expert():
  sizes = _sizes(first=0, held=4)
  params = _forced_router(_moe_params(sizes), expert=1)
  x = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (2, 128, 64))) + 0.1
  layer = moe_lib.ShardedExpertsMoE(
      num_experts=8, experts_held=(0, 4), top_k=2, expert_width=32,
      shared_width=32, buffer_factor=2.0)
  out, counters = layer.apply({"params": params}, x)
  # 256 pairs on expert 1 and the second choices spread: 256 rows balanced,
  # a buffer of 512.
  assert layer.buffer_rows(256) == 512
  assert counters["moe_rows_dropped"] == 0
  assert counters["moe_rows_held"] >= 256
  assert counters["moe_load_max_over_mean"] >= 2.0
  shared, routed = ref.moe_parts(params, x, sizes, lambda y: y)
  np.testing.assert_allclose(out.reshape(-1, 64), shared + routed, atol=5e-6)
  small = moe_lib.ShardedExpertsMoE(
      num_experts=8, experts_held=(0, 4), top_k=2, expert_width=32,
      shared_width=32, buffer_factor=0.5)
  _, counters = small.apply({"params": params}, x)
  assert small.buffer_rows(256) == 128
  assert counters["moe_rows_dropped"] == counters["moe_rows_held"] - 128 > 0
  assert counters["moe_buffer_fill"] > 1.0


def test_group_sizes_fill_the_buffer_whatever_the_router_picks():
  """The grouped products are handed sizes that add up to the buffer, so they
  visit every row tile in every step."""
  seen = []
  real = grouped_matmul.grouped_matmul

  def spy(lhs, rhs, group_sizes, **kwargs):
    seen.append((lhs.shape[0], group_sizes))
    return real(lhs, rhs, group_sizes, **kwargs)

  sizes = _sizes()
  x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))
  layer = moe_lib.ShardedExpertsMoE(
      num_experts=8, experts_held=(0, 4), top_k=2, expert_width=32,
      shared_width=0)
  params = {k: v for k, v in _moe_params(sizes).items()
            if not k.startswith("shared")}
  grouped_matmul.grouped_matmul = spy
  try:
    layer.apply({"params": params}, x)
  finally:
    grouped_matmul.grouped_matmul = real
  assert len(seen) == 2
  for rows, group_sizes in seen:
    assert rows == 512 and int(jnp.sum(group_sizes)) == 512


# -- the gated attention mixer -------------------------------------------------


def test_rotary_turns_a_quarter_of_the_head_and_keeps_norms():
  x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 32))
  cos, sin = decoder.rotary_tables(8, 8, 1e7)
  y = decoder.apply_partial_rotary(x, cos, sin)
  np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
  np.testing.assert_array_equal(y[:, 0], x[:, 0])      # position 0: no turn
  assert not np.allclose(y[:, 1:, :, :8], x[:, 1:, :, :8])
  np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                             jnp.linalg.norm(x, axis=-1), rtol=1e-5)
  # dimension i pairs with i + 4: a turn by position x theta^(-2i/8)
  angle = 3 * 1e7 ** (-2 / 8)
  np.testing.assert_allclose(
      y[0, 3, 0, 1], x[0, 3, 0, 1] * np.cos(angle)
      - x[0, 3, 0, 5] * np.sin(angle), rtol=1e-4, atol=1e-5)
  # scores depend on the distance alone
  q = decoder.apply_partial_rotary(jnp.broadcast_to(x[:, :1], x.shape), cos,
                                   sin)
  scores = jnp.einsum("bqhd,bkhd->bhqk", q, q)
  np.testing.assert_allclose(scores[0, 0, 2, 1], scores[0, 0, 5, 4],
                             rtol=1e-4)


def test_repeated_keys_and_values_are_grouped_query_attention():
  cfg = decoder.DecoderConfig(
      hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
      head_dim=32, flash_interpret=True)
  sizes = _sizes()
  params, _ = ref.init_state(11, sizes)
  params = params["layer_3"]["mixer"]
  x = jax.random.normal(jax.random.PRNGKey(6), (2, 128, 64))
  got = decoder.GatedAttention(cfg).apply({"params": params}, x)
  want = ref._attention(params, x, sizes, lambda y: y)
  np.testing.assert_allclose(got, want, atol=2e-6)
  # the gradient of a repeated key head is the sum over its group
  g_got = jax.grad(lambda p: jnp.sum(decoder.GatedAttention(cfg).apply(
      {"params": p}, x) ** 2))(params)
  g_want = jax.grad(lambda p: jnp.sum(ref._attention(
      p, x, sizes, lambda y: y) ** 2))(params)
  for name in ("k_proj", "v_proj", "q_proj"):
    np.testing.assert_allclose(g_got[name]["kernel"], g_want[name]["kernel"],
                               atol=1e-6, rtol=1e-3)


def test_flash_attention_at_sixteen_heads_of_256_interpreted():
  """The cell's side of `lane_block` (one head of 256 a program) and of
  `_sum_rides` (the reduced denominator), interpreted."""
  assert attention_ops.lane_block(16, 256) == 256
  assert not attention_ops._sum_rides(256)
  keys = jax.random.split(jax.random.PRNGKey(8), 3)
  q, k, v = (jax.random.normal(key, (1, 256, 16 * 256)) * 0.3
             for key in keys)
  heads = lambda y: y.reshape(1, 256, 16, 256).transpose(0, 2, 1, 3)  # noqa
  want = attention_ops.attention(heads(q), heads(k), heads(v), causal=True)
  got = attention_ops.flash_attention(q, k, v, 16, causal=True,
                                      block_q=128, block_k=128,
                                      interpret=True)
  np.testing.assert_allclose(heads(got), want, atol=2e-5)
