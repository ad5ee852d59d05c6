"""Plain reference of one chip's share of Qwen3-Next-80B-A3B-Instruct on the
training path, and the count of the model's FLOPs.

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json
(`model_type` `qwen3_next`) and the public `qwen3_next` modelling code. With
rms(x) = x / sqrt(mean(x^2) + eps) and norm(x) = rms(x) * (1 + w):

  layer i:   x = x + mixer(norm(x)); x = x + moe(norm(x)); the mixer is gated
             softmax attention where (i + 1) % full_attention_interval == 0,
             else the gated delta rule. After the last layer norm, then the
             untied head onto the ids held here.
  delta rule (per value head h, key head h // 2): [q, k, v, z] = x W_qkvz,
             [b, a] = x W_ba; [q, k, v] through a causal depthwise
             convolution of 4 and SiLU; q, k L2-normalised, q scaled by
             d_k^-0.5; beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias);
             S'_t = exp(g_t) S_{t-1}; u_t = beta_t (v_t - S'_t^T k_t);
             S_t = S'_t + k_t u_t^T; o_t = S_t^T q_t;
             y = (rms(o) * w_o) * silu(z), heads joined, y W_out.
  attention: [q, gate] = x W_q, k = x W_k, v = x W_v; q, k through norm over
             the head; rotary embedding on the first quarter of each head
             (half-split pairing, positions 0..T-1); causal softmax at
             head_dim^-0.5, each key/value head serving 8 query heads;
             (attn * sigmoid(gate)) W_o.
  experts:   p = softmax(x W_r) over all 512; the 10 largest, weights
             p_i / sum of the 10; expert e (silu(x W_g) * (x W_u)) W_d;
             shared expert of the same form times sigmoid(x w_s);
             moe = shared + the picked experts' weighted terms.
  loss:      mean over rows of weight x mean over positions of the
             cross-entropy of the next token; Adam, learning rate 1e-4.

The share (PERF.md section 4): layers 0-3 of 48, experts 0-31 of each layer's
512 (the router keeps its 512 outputs and its 10 a token; what the 480 absent
experts would add is left out), ids 0-18,991 of the vocabulary.

Straightforward jax.numpy in float32 at `highest` matmul precision. It
imports nothing of the program, has no kernel, no chunked rule, no sort and
no buffer: the delta rule is the token-by-token recurrence above under
`lax.scan` (made again in the backward pass in spans of steps, or its 4,096
states would not fit); attention is a masked softmax over whole rows of
scores, taken in blocks of query rows; the experts are a loop over the 32
held, every token through each, masked by its weight. Departures from the
source are the program's (the configuration file lists them): q, k, v, z lie
one after the other in W_qkvz and q, gate in W_q (the source interleaves them
per head: a permutation of columns); the multi-token-prediction module is
left out (the config has no key for it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import refmath

LEARNING_RATE = 1e-4
INIT_STDDEV = 0.02
L2_EPS = 1e-6
CHUNK = 64  # the source's chunk, for the count of FLOPs only


def sizes_from_bindings(values: dict) -> dict:
  return {
      "sequence_length": int(values["sequence_length"]),
      "vocab_size": int(values["vocab_size"]),
      "hidden_size": int(values["hidden_size"]),
      "layers": int(values["layers"]),
      "full_attention_interval": int(values["full_attention_interval"]),
      "rms_norm_eps": float(values["rms_norm_eps"]),
      "num_attention_heads": int(values["num_attention_heads"]),
      "num_key_value_heads": int(values["num_key_value_heads"]),
      "head_dim": int(values["head_dim"]),
      "partial_rotary_factor": float(values["partial_rotary_factor"]),
      "rope_theta": float(values["rope_theta"]),
      "linear_num_key_heads": int(values["linear_num_key_heads"]),
      "linear_num_value_heads": int(values["linear_num_value_heads"]),
      "linear_key_head_dim": int(values["linear_key_head_dim"]),
      "linear_value_head_dim": int(values["linear_value_head_dim"]),
      "linear_conv_kernel_dim": int(values["linear_conv_kernel_dim"]),
      "router_width": int(values["router_width"]),
      "first_expert": int(values.get("first_expert", 0)),
      "num_experts": int(values["num_experts"]),   # held here
      "num_experts_per_tok": int(values["num_experts_per_tok"]),
      "moe_intermediate_size": int(values["moe_intermediate_size"]),
      "shared_expert_intermediate_size": int(
          values["shared_expert_intermediate_size"]),
      # the program's buffer, for the readers of its trace; not used here
      "expert_buffer_factor": float(values.get("expert_buffer_factor", 2.0)),
      "reference_query_rows": int(values.get("reference_query_rows", 512)),
      "reference_span": int(values.get("reference_span", 64)),
  }


def layer_kinds(sizes: dict):
  every = sizes["full_attention_interval"]
  return ["full" if (i + 1) % every == 0 else "linear"
          for i in range(sizes["layers"])]


# -- FLOPs --------------------------------------------------------------------


def delta_rule_macs_per_token(sizes: dict) -> float:
  """Multiply-adds a token of the chunked rule as the source states it, per
  layer: in a chunk of C tokens and per value head the four [C, C] products
  over a head (k_beta.k^T, q.k^T, and the inverse applied to v_beta and to
  k_beta), the triangular system by forward substitution (C^3 / 3), and with
  the travelling state three [C, d_k, d_v] products and the scores applied
  to the chunk's writes."""
  c = CHUNK
  d_k, d_v = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
  per_chunk = (3 * c * c * d_k + 2 * c * c * d_v + c ** 3 / 3.0
               + 3 * c * d_k * d_v)
  return per_chunk * sizes["linear_num_value_heads"] / c


def macs_per_token(sizes: dict) -> dict:
  """Forward multiply-adds a token, by part."""
  h = sizes["hidden_size"]
  key_dim = sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
  value_dim = sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"]
  attn_dim = sizes["num_attention_heads"] * sizes["head_dim"]
  kv_dim = sizes["num_key_value_heads"] * sizes["head_dim"]
  width = sizes["moe_intermediate_size"]
  return {
      "linear_projections": (
          h * (2 * key_dim + 2 * value_dim)
          + h * 2 * sizes["linear_num_value_heads"]
          + (2 * key_dim + value_dim) * sizes["linear_conv_kernel_dim"]
          + value_dim * h),
      "delta_rule": delta_rule_macs_per_token(sizes),
      "full_projections": h * 2 * attn_dim + 2 * h * kv_dim + attn_dim * h,
      # causal: scores and weighted sum over half the square
      "attention": 2 * (sizes["sequence_length"] / 2.0) * attn_dim,
      "router": h * sizes["router_width"],
      "shared_expert": 3 * h * sizes["shared_expert_intermediate_size"] + h,
      # balanced load: top_k x held / router_width experts a token
      "routed_experts": (sizes["num_experts_per_tok"] * sizes["num_experts"]
                         / sizes["router_width"]) * 3 * h * width,
      "head": h * sizes["vocab_size"],
  }


def model_flops(sizes: dict, batch_size: int) -> float:
  """FLOPs one training step needs: 2 x the forward multiply-adds x 3, the
  routed experts at the balanced load, causal attention as half the square,
  the delta rule at its chunked count, nothing for recomputation and nothing
  for buffer rows that hold no pair."""
  m = macs_per_token(sizes)
  kinds = layer_kinds(sizes)
  moe = m["router"] + m["shared_expert"] + m["routed_experts"]
  per_token = (kinds.count("linear") * (m["linear_projections"]
                                        + m["delta_rule"] + moe)
               + kinds.count("full") * (m["full_projections"]
                                        + m["attention"] + moe)
               + m["head"])
  return 2.0 * per_token * 3.0 * sizes["sequence_length"] * batch_size


# -- weights from the seed ----------------------------------------------------


def init_state(seed: int, sizes: dict):
  """(params, {}) as the trainer's seeded init draws them: normal(0.02) for
  every matrix and the embedding, A_log = log U(0, 16), dt_bias and the gated
  norm's weight 1, the zero-centred norms' weights 0."""
  rng = refmath.trainer_init_rng(seed)
  # Jitted, as the trainer's init is: compiled, the scaling of the normal
  # draw rounds in another place than op by op (one float32 ulp).
  normal = jax.jit(jax.nn.initializers.normal(INIT_STDDEV),
                   static_argnums=(1, 2))
  h = sizes["hidden_size"]

  def matrix(path, counter, shape):
    return normal(refmath.param_key(rng, path, counter), shape, jnp.float32)

  def dense(path, fan_in, fan_out):
    return {"kernel": matrix(path, 1, (fan_in, fan_out))}

  zeros = lambda n: {"weight": jnp.zeros((n,), jnp.float32)}  # noqa: E731
  key_dim = sizes["linear_num_key_heads"] * sizes["linear_key_head_dim"]
  value_dim = sizes["linear_num_value_heads"] * sizes["linear_value_head_dim"]
  v_heads = sizes["linear_num_value_heads"]
  attn_dim = sizes["num_attention_heads"] * sizes["head_dim"]
  kv_dim = sizes["num_key_value_heads"] * sizes["head_dim"]
  width, held = sizes["moe_intermediate_size"], sizes["num_experts"]
  shared = sizes["shared_expert_intermediate_size"]
  params = {"embed": {"embedding": matrix(("embed",), 1,
                                          (sizes["vocab_size"], h))},
            "head": matrix((), 1, (h, sizes["vocab_size"])),
            "norm_final": zeros(h)}
  for i, kind in enumerate(layer_kinds(sizes)):
    at = (f"layer_{i}",)
    if kind == "linear":
      mixer = {
          "in_proj_qkvz": dense(at + ("mixer", "in_proj_qkvz"), h,
                                2 * key_dim + 2 * value_dim),
          "in_proj_ba": dense(at + ("mixer", "in_proj_ba"), h, 2 * v_heads),
          "conv_kernel": matrix(at + ("mixer",), 1,
                                (sizes["linear_conv_kernel_dim"],
                                 2 * key_dim + value_dim)),
          "A_log": jnp.log(jnp.maximum(jax.random.uniform(
              refmath.param_key(rng, at + ("mixer",), 2), (v_heads,),
              jnp.float32, 0.0, 16.0), 1e-6)),
          "dt_bias": jnp.ones((v_heads,), jnp.float32),
          "norm_weight": jnp.ones((sizes["linear_value_head_dim"],),
                                  jnp.float32),
          "out_proj": dense(at + ("mixer", "out_proj"), value_dim, h),
      }
    else:
      mixer = {
          "q_proj": dense(at + ("mixer", "q_proj"), h, 2 * attn_dim),
          "k_proj": dense(at + ("mixer", "k_proj"), h, kv_dim),
          "v_proj": dense(at + ("mixer", "v_proj"), h, kv_dim),
          "q_norm": zeros(sizes["head_dim"]),
          "k_norm": zeros(sizes["head_dim"]),
          "o_proj": dense(at + ("mixer", "o_proj"), attn_dim, h),
      }
    moe = {
        "experts_gate_up": matrix(at + ("moe",), 1, (held, h, 2 * width)),
        "experts_down": matrix(at + ("moe",), 2, (held, width, h)),
        "router": dense(at + ("moe", "router"), h, sizes["router_width"]),
        "shared_gate_proj": dense(at + ("moe", "shared_gate_proj"), h, shared),
        "shared_up_proj": dense(at + ("moe", "shared_up_proj"), h, shared),
        "shared_down_proj": dense(at + ("moe", "shared_down_proj"), shared, h),
        "shared_expert_gate": dense(at + ("moe", "shared_expert_gate"), h, 1),
    }
    params[f"layer_{i}"] = {"norm_mixer": zeros(h), "mixer": mixer,
                            "norm_moe": zeros(h), "moe": moe}
  return params, {}


# -- forward, loss, step ------------------------------------------------------


def _product(subscripts, a, b, q):
  """Both operands and the result rounded to the precision asked for."""
  return q(jnp.einsum(subscripts, q(a), q(b), precision=refmath.HIGHEST))


def _dense(p, x, q):
  return _product("...i,io->...o", x, p["kernel"], q)


def _rms(x, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(p, x, eps):
  return _rms(x, eps) * (1.0 + p["weight"])


def _unit(x):
  return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(q_, k, v, g, beta, q, span: int):
  """The recurrence, token by token; q_, k [B, T, H, d_k] as the layer hands
  them over, v [B, T, H, d_v], g and beta [B, T, H]. `span` steps at a time
  are made again in the backward pass."""
  b, t, h, d_k = q_.shape
  q_, k = _unit(q_) * d_k ** -0.5, _unit(k)

  def step(state, inputs):
    q_t, k_t, v_t, g_t, beta_t = inputs
    state = state * jnp.exp(g_t)[..., None, None]
    read = _product("bhk,bhkv->bhv", k_t, state, q)
    u_t = beta_t[..., None] * (v_t - read)
    state = state + _product("bhk,bhv->bhkv", k_t, u_t, q)
    return state, _product("bhk,bhkv->bhv", q_t, state, q)

  @jax.checkpoint
  def steps(state, inputs):
    return jax.lax.scan(step, state, inputs)

  span = max(s for s in range(1, min(span, t) + 1) if t % s == 0)
  spans = [jnp.moveaxis(x, 1, 0).reshape((t // span, span) + x.shape[:1]
                                         + x.shape[2:])
           for x in (q_, k, v, g, beta)]
  state0 = jnp.zeros((b, h, d_k, v.shape[-1]), jnp.float32)
  _, o = jax.lax.scan(steps, state0, spans)
  return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _delta_net(p, x, sizes, q):
  b, t, _ = x.shape
  k_heads, v_heads = sizes["linear_num_key_heads"], sizes[
      "linear_num_value_heads"]
  d_k, d_v = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
  key_dim, value_dim = k_heads * d_k, v_heads * d_v
  width = sizes["linear_conv_kernel_dim"]
  qkvz = _dense(p["in_proj_qkvz"], x, q)
  ba = _dense(p["in_proj_ba"], x, q)
  mixed = jnp.pad(q(qkvz[..., :2 * key_dim + value_dim]),
                  ((0, 0), (width - 1, 0), (0, 0)))
  taps = q(p["conv_kernel"])
  mixed = jax.nn.silu(q(sum(mixed[:, j:j + t] * taps[j]
                            for j in range(width))))
  query = mixed[..., :key_dim].reshape(b, t, k_heads, d_k)
  key = mixed[..., key_dim:2 * key_dim].reshape(b, t, k_heads, d_k)
  value = mixed[..., 2 * key_dim:].reshape(b, t, v_heads, d_v)
  z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, v_heads, d_v)
  beta = jax.nn.sigmoid(ba[..., :v_heads])
  g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., v_heads:] + p["dt_bias"])
  query = jnp.repeat(query, v_heads // k_heads, axis=2)
  key = jnp.repeat(key, v_heads // k_heads, axis=2)
  o = delta_rule(query, key, value, g, beta, q, sizes["reference_span"])
  o = _rms(o, sizes["rms_norm_eps"]) * p["norm_weight"] * jax.nn.silu(z)
  return _dense(p["out_proj"], o.reshape(b, t, value_dim), q)


def _rotary(x, fraction, theta):
  """x [B, T, ..., D]: the first `fraction` of D rotated, dimension i paired
  with i + half."""
  t, d = x.shape[1], x.shape[-1]
  rotary = int(d * fraction)
  half = rotary // 2
  inv_freq = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
  angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
  shape = (1, t) + (1,) * (x.ndim - 3) + (half,)
  cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
  x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
  return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                         axis=-1)


def _attention(p, x, sizes, q):
  b, t, _ = x.shape
  heads, kv_heads, d = (sizes["num_attention_heads"],
                        sizes["num_key_value_heads"], sizes["head_dim"])
  group = heads // kv_heads
  eps = sizes["rms_norm_eps"]
  query, gate = jnp.split(_dense(p["q_proj"], x, q), 2, axis=-1)
  query = _norm(p["q_norm"], query.reshape(b, t, kv_heads, group, d), eps)
  key = _norm(p["k_norm"], _dense(p["k_proj"], x, q).reshape(
      b, t, kv_heads, d), eps)
  value = _dense(p["v_proj"], x, q).reshape(b, t, kv_heads, d)
  fraction, theta = sizes["partial_rotary_factor"], sizes["rope_theta"]
  query, key = _rotary(query, fraction, theta), _rotary(key, fraction, theta)
  block = min(sizes["reference_query_rows"], t)
  if t % block:
    raise ValueError(f"{t} query rows do not divide into blocks of {block}")

  @jax.checkpoint
  def rows(query_rows, start):
    scores = _product("bqgrd,bkgd->bgrqk", query_rows, key, q) * d ** -0.5
    allowed = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
    weights = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
    return _product("bgrqk,bkgd->bqgrd", weights, value, q)

  out = jnp.concatenate(
      [rows(query[:, s:s + block], s) for s in range(0, t, block)], axis=1)
  out = out.reshape(b, t, heads * d) * jax.nn.sigmoid(gate)
  return _dense(p["o_proj"], out, q)


def moe_parts(p, x, sizes, q):
  """(shared expert's part, held experts' part) of the layer, [N, hidden]."""
  tokens = x.reshape(-1, x.shape[-1])
  width = sizes["moe_intermediate_size"]
  probs = jax.nn.softmax(_dense(p["router"], tokens, q), axis=-1)
  top_probs, top_idx = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
  top_probs = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)

  @jax.checkpoint
  def term(w_gate_up, w_down, expert):
    weight = jnp.sum(jnp.where(top_idx == expert, top_probs, 0.0), axis=-1)
    gate_up = _product("ni,io->no", tokens, w_gate_up, q)
    hidden = jax.nn.silu(gate_up[:, :width]) * gate_up[:, width:]
    return _product("ni,io->no", hidden, w_down, q) * weight[:, None]

  def add(total, expert_weights):
    return total + term(*expert_weights), None

  experts = sizes["first_expert"] + jnp.arange(sizes["num_experts"])
  routed, _ = jax.lax.scan(add, jnp.zeros_like(tokens), (
      p["experts_gate_up"], p["experts_down"], experts))
  hidden = jax.nn.silu(_dense(p["shared_gate_proj"], tokens, q)) * _dense(
      p["shared_up_proj"], tokens, q)
  shared = _dense(p["shared_down_proj"], hidden, q) * jax.nn.sigmoid(
      _dense(p["shared_expert_gate"], tokens, q))
  return shared, routed


def _layer(p, x, kind, sizes, q):
  eps = sizes["rms_norm_eps"]
  mixer = _delta_net if kind == "linear" else _attention
  x = x + mixer(p["mixer"], _norm(p["norm_mixer"], x, eps), sizes, q)
  shared, routed = moe_parts(p["moe"], _norm(p["norm_moe"], x, eps), sizes, q)
  return x + (shared + routed).reshape(x.shape)


def hidden_states(params, tokens, sizes, q):
  """[B, T] ids -> the normed hidden states the head reads, [B, T, hidden]."""
  x = params["embed"]["embedding"][tokens]
  for i, kind in enumerate(layer_kinds(sizes)):
    layer = jax.checkpoint(functools.partial(_layer, kind=kind, sizes=sizes,
                                             q=q))
    x = layer(params[f"layer_{i}"], x)
  return _norm(params["norm_final"], x, sizes["rms_norm_eps"])


def logits_fn(params, tokens, sizes, q):
  return _product("bti,io->bto", hidden_states(params, tokens, sizes, q),
                  params["head"], q)


def loss_fn(params, batch, sizes, q):
  hidden = hidden_states(params, batch["features/tokens"], sizes, q)

  @jax.checkpoint
  def row_loss(h, targets):
    logits = _product("ti,io->to", h, params["head"], q)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)

  weight = batch["labels/weight"].astype(jnp.float32).reshape(-1)
  losses = jnp.stack([row_loss(hidden[r], batch["labels/targets"][r])
                      for r in range(hidden.shape[0])])
  return jnp.mean(weight * losses)


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _value_and_grad(params, batch, sizes_key, precision):
  return jax.value_and_grad(loss_fn)(
      params, batch, dict(sizes_key), refmath.quantizer(precision))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(params, mu, nu, grads, count):
  return refmath.adam_step(params, mu, nu, grads, count,
                           learning_rate=LEARNING_RATE)


def train_steps(seed: int, sizes: dict, batches, precision: str = "float32",
                rows=None):
  """Follows the trainer's first `len(batches)` steps from its seeded init.

  `batches` are the pool's host batches in the order the trainer is fed them,
  flat dicts of numpy arrays (`features/tokens`, `labels/targets`,
  `labels/weight`). `rows`, a slice, plants the fault "part of the batch left
  out, the mean taken over the rest". A step takes the whole batch at once:
  two gradients of 2.5 GB do not fit beside Adam's state. Returns `losses`,
  `params0`, `first_gradient` and `params`; the first gradient on the host,
  the two sets of parameters left on the device (`params0` drawn again from
  the seed once the steps are done), because the one-chip machine's host does
  not hold three more copies of 626 M parameters beside the trainer's own
  (`drivers/trainer_streamed.py`).
  """
  import numpy as np

  params, _ = init_state(seed, sizes)
  mu = jax.tree_util.tree_map(jnp.zeros_like, params)
  nu = jax.tree_util.tree_map(jnp.zeros_like, params)
  sizes_key = tuple(sorted(sizes.items()))
  losses, first = [], None
  for count, batch in enumerate(batches, start=1):
    if rows is not None:
      batch = {k: v[rows] for k, v in batch.items()}
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    loss, grads = _value_and_grad(params, batch, sizes_key, precision)
    if first is None:
      first = jax.device_get(grads)
    params, mu, nu = _adam(params, mu, nu, grads, count)
    del grads
    losses.append(float(loss))
  del mu, nu
  return {"losses": np.asarray(losses, np.float64),
          "params0": init_state(seed, sizes)[0], "first_gradient": first,
          "params": params}
