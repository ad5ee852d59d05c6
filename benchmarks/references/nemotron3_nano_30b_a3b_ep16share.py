"""Plain reference of one chip's share of NVIDIA-Nemotron-3-Nano-30B-A3B on
the training path, and the count of the model's FLOPs.

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json
(`model_type` `nemotron_h`) and the public `nemotron_h` modelling code. With
norm(x) = w * x / sqrt(mean(x^2) + eps), w from 1:

  layer i:   x = x + f(norm(x)), f by the i-th letter of the pattern: `M` the
             Mamba-2 mixer, `E` the experts, `*` attention. After the last
             layer norm, then the untied head onto the ids held here.
  Mamba-2 (H heads of P, I = H x P, G groups, state N, head h in group
             h // (H / G)): [z | xBC | dt] = u W_in (widths I, I + 2 G N, H);
             xBC = silu(causal depthwise conv_4(xBC) + b_conv); [x | B | C] =
             xBC; dt = softplus(dt + dt_bias); a_t = exp(-exp(A_log) dt_t);
             S_t = a_t S_{t-1} + dt_t x_t B_t^T (S_0 = 0, [P, N] a head);
             y_t = S_t C_t + D x_t; y = norm over each of G groups of I / G
             channels of (y * silu(z)), times w; y W_out.
  experts:   s = sigmoid(x W_r) over all 128; the 6 largest of s + b (b the
             source's `e_score_correction_bias`, zero and fixed here); weights
             2.5 s_i / (sum of the 6 picks' s + 1e-20); expert e
             relu(x W_up)^2 W_down; shared expert of the same form, not
             gated; moe = shared + the picked experts' weighted terms.
  attention: q = x W_q (32 heads of 128), k, v = x W_k, x W_v (2 heads);
             causal softmax(q k^T / sqrt(128)) v, each key/value head serving
             16 query heads; W_o. No bias, no gate, no q/k norm and no
             positional embedding (the source's attention module applies
             none).
  loss:      mean over rows of weight x mean over positions of the
             cross-entropy of the next token; Adam, learning rate 1e-4.

The share (PERF.md section 4): layers 0-8 of 52 (`MEMEM*EME`), experts 0-7 of
each expert layer's 128 (the router keeps its 128 outputs and its 6 a token;
what the 120 absent experts would add is left out), ids 0-16,383 of the
vocabulary.

Straightforward jax.numpy in float32 at `highest` matmul precision. It
imports nothing of the program, has no kernel, no chunked scan, no sort and
no buffer: the state-space layer is the token-by-token recurrence above under
`lax.scan` (made again in the backward pass in spans of steps, or its 4,096
states would not fit); attention is a masked softmax over whole rows of
scores, taken in blocks of query rows; the experts are a loop over the 8
held, every token through each, masked by its weight. Departures from the
source are the program's (the configuration file lists them under `assumed`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import refmath

LEARNING_RATE = 1e-4
INIT_STDDEV = 0.02
ROUTER_EPS = 1e-20
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def sizes_from_bindings(values: dict) -> dict:
  return {
      "sequence_length": int(values["sequence_length"]),
      "vocab_size": int(values["vocab_size"]),
      "hidden_size": int(values["hidden_size"]),
      "pattern": str(values["pattern"]),   # one letter a layer: M, E or *
      "norm_eps": float(values["norm_eps"]),
      "mamba_num_heads": int(values["mamba_num_heads"]),
      "mamba_head_dim": int(values["mamba_head_dim"]),
      "ssm_state_size": int(values["ssm_state_size"]),
      "n_groups": int(values["n_groups"]),
      "conv_kernel": int(values["conv_kernel"]),
      "chunk_size": int(values["chunk_size"]),   # the count of FLOPs only
      "num_attention_heads": int(values["num_attention_heads"]),
      "num_key_value_heads": int(values["num_key_value_heads"]),
      "head_dim": int(values["head_dim"]),
      "router_width": int(values["router_width"]),
      "first_expert": int(values.get("first_expert", 0)),
      "num_experts": int(values["num_experts"]),   # held here
      "num_experts_per_tok": int(values["num_experts_per_tok"]),
      "moe_intermediate_size": int(values["moe_intermediate_size"]),
      "moe_shared_expert_intermediate_size": int(
          values["moe_shared_expert_intermediate_size"]),
      "routed_scaling_factor": float(values["routed_scaling_factor"]),
      # the program's buffer, for the readers of its trace; not used here
      "expert_buffer_factor": float(values.get("expert_buffer_factor", 2.0)),
      "reference_query_rows": int(values.get("reference_query_rows", 512)),
      "reference_span": int(values.get("reference_span", 64)),
  }


def layer_kinds(sizes: dict):
  return [KINDS[letter] for letter in sizes["pattern"]]


# -- FLOPs --------------------------------------------------------------------


def scan_macs_per_token(sizes: dict) -> float:
  """Multiply-adds a token of the chunked scan, per layer: in a chunk of C
  tokens, per group C B^T ([C, N] x [N, C]), and per head the masked scores
  applied to dt x ([C, C] x [C, P]), the chunk's state ([P, C] x [C, N]) and
  the entering state read by C ([C, N] x [N, P])."""
  c, p, n = sizes["chunk_size"], sizes["mamba_head_dim"], sizes[
      "ssm_state_size"]
  per_chunk = (sizes["n_groups"] * c * c * n
               + sizes["mamba_num_heads"] * (c * c * p + 2 * c * p * n))
  return per_chunk / c


def macs_per_token(sizes: dict) -> dict:
  """Forward multiply-adds a token, by part."""
  h = sizes["hidden_size"]
  inner = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
  conv_dim = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
  attn_dim = sizes["num_attention_heads"] * sizes["head_dim"]
  kv_dim = sizes["num_key_value_heads"] * sizes["head_dim"]
  return {
      "mamba_projections": (
          h * (inner + conv_dim + sizes["mamba_num_heads"])
          + conv_dim * sizes["conv_kernel"] + inner * h),
      "scan": scan_macs_per_token(sizes),
      "attention_projections": h * attn_dim + 2 * h * kv_dim + attn_dim * h,
      # causal: scores and weighted sum over half the square
      "attention": 2 * (sizes["sequence_length"] / 2.0) * attn_dim,
      "router": h * sizes["router_width"],
      "shared_expert": 2 * h * sizes["moe_shared_expert_intermediate_size"],
      # balanced load: top_k x held / router_width experts a token
      "routed_experts": (sizes["num_experts_per_tok"] * sizes["num_experts"]
                         / sizes["router_width"])
      * 2 * h * sizes["moe_intermediate_size"],
      "head": h * sizes["vocab_size"],
  }


def model_flops(sizes: dict, batch_size: int) -> float:
  """FLOPs one training step needs: 2 x the forward multiply-adds x 3, the
  routed experts at the balanced load, causal attention as half the square,
  the scan at its chunked count, nothing for recomputation and nothing for
  buffer rows that hold no pair."""
  m = macs_per_token(sizes)
  kinds = layer_kinds(sizes)
  per_token = (
      kinds.count("mamba") * (m["mamba_projections"] + m["scan"])
      + kinds.count("experts") * (m["router"] + m["shared_expert"]
                                  + m["routed_experts"])
      + kinds.count("attention") * (m["attention_projections"]
                                    + m["attention"])
      + m["head"])
  return 2.0 * per_token * 3.0 * sizes["sequence_length"] * batch_size


# -- weights from the seed ----------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1,))
def _uniform_half(key, shape):
  return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)


@functools.partial(jax.jit, static_argnums=(1,))
def _dt_bias(key, shape):
  dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(0.001),
                                  jnp.log(0.1)))
  dt = jnp.maximum(dt, 1e-4)
  return dt + jnp.log(-jnp.expm1(-dt))


def init_state(seed: int, sizes: dict):
  """(params, {}) as the trainer's seeded init draws them: normal(0.02) for
  every matrix and the embedding, the convolution and its bias U(-1/2, 1/2),
  A_log = log(1..H), D and every norm's weight 1, dt_bias the inverse
  softplus of exp(U(log 0.001, log 0.1)) floored at 1e-4."""
  rng = refmath.trainer_init_rng(seed)
  # Jitted, as the trainer's init is: compiled, the scaling of a draw rounds
  # in another place than op by op (one float32 ulp).
  normal = jax.jit(jax.nn.initializers.normal(INIT_STDDEV),
                   static_argnums=(1, 2))
  h = sizes["hidden_size"]

  def matrix(path, counter, shape):
    return normal(refmath.param_key(rng, path, counter), shape, jnp.float32)

  def dense(path, fan_in, fan_out):
    return {"kernel": matrix(path, 1, (fan_in, fan_out))}

  ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
  heads = sizes["mamba_num_heads"]
  inner = heads * sizes["mamba_head_dim"]
  conv_dim = inner + 2 * sizes["n_groups"] * sizes["ssm_state_size"]
  attn_dim = sizes["num_attention_heads"] * sizes["head_dim"]
  kv_dim = sizes["num_key_value_heads"] * sizes["head_dim"]
  width, held = sizes["moe_intermediate_size"], sizes["num_experts"]
  shared = sizes["moe_shared_expert_intermediate_size"]
  params = {"embed": {"embedding": matrix(("embed",), 1,
                                          (sizes["vocab_size"], h))},
            "head": matrix((), 1, (h, sizes["vocab_size"])),
            "norm_final": {"weight": ones(h)}}
  for i, kind in enumerate(layer_kinds(sizes)):
    at = (f"layer_{i}",)
    layer = {"norm": {"weight": ones(h)}}
    if kind == "mamba":
      m = at + ("mixer",)
      layer["mixer"] = {
          "in_proj": dense(m + ("in_proj",), h, inner + conv_dim + heads),
          "conv_kernel": _uniform_half(refmath.param_key(rng, m, 1),
                                       (sizes["conv_kernel"], conv_dim)),
          "conv_bias": _uniform_half(refmath.param_key(rng, m, 2),
                                     (conv_dim,)),
          "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
          "dt_bias": _dt_bias(refmath.param_key(rng, m, 4), (heads,)),
          "D": ones(heads),
          "norm_weight": ones(inner),
          "out_proj": dense(m + ("out_proj",), inner, h),
      }
    elif kind == "attention":
      m = at + ("mixer",)
      layer["mixer"] = {
          "q_proj": dense(m + ("q_proj",), h, attn_dim),
          "k_proj": dense(m + ("k_proj",), h, kv_dim),
          "v_proj": dense(m + ("v_proj",), h, kv_dim),
          "o_proj": dense(m + ("o_proj",), attn_dim, h),
      }
    else:
      m = at + ("moe",)
      layer["moe"] = {
          "experts_up": matrix(m, 1, (held, h, width)),
          "experts_down": matrix(m, 2, (held, width, h)),
          "router": dense(m + ("router",), h, sizes["router_width"]),
          "shared_up_proj": dense(m + ("shared_up_proj",), h, shared),
          "shared_down_proj": dense(m + ("shared_down_proj",), shared, h),
      }
    params[f"layer_{i}"] = layer
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
  return _rms(x, eps) * p["weight"]


def state_space_scan(x, dt, a_log, b, c, d, q, span: int):
  """The recurrence, token by token: x [B, T, H, P], dt [B, T, H] after its
  softplus, a_log and d [H], b and c [B, T, H, N] (already one a head).
  `span` steps at a time are made again in the backward pass."""
  batch, t, heads, p = x.shape
  decay = jnp.exp(-jnp.exp(a_log) * dt)

  def step(state, inputs):
    x_t, dt_t, a_t, b_t, c_t = inputs
    write = _product("bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t, q)
    state = a_t[..., None, None] * state + write
    return state, _product("bhpn,bhn->bhp", state, c_t, q)

  @jax.checkpoint
  def steps(state, inputs):
    return jax.lax.scan(step, state, inputs)

  span = max(s for s in range(1, min(span, t) + 1) if t % s == 0)
  spans = [jnp.moveaxis(v, 1, 0).reshape((t // span, span) + v.shape[:1]
                                         + v.shape[2:])
           for v in (x, dt, decay, b, c)]
  state0 = jnp.zeros((batch, heads, p, b.shape[-1]), jnp.float32)
  _, y = jax.lax.scan(steps, state0, spans)
  return jnp.moveaxis(y.reshape((t,) + y.shape[2:]), 0, 1) + d[:, None] * x


def _mamba(p, u, sizes, q):
  batch, t, _ = u.shape
  heads, head_dim = sizes["mamba_num_heads"], sizes["mamba_head_dim"]
  groups, state = sizes["n_groups"], sizes["ssm_state_size"]
  inner, width = heads * head_dim, sizes["conv_kernel"]
  conv_dim = inner + 2 * groups * state
  zxbcdt = _dense(p["in_proj"], u, q)
  z = zxbcdt[..., :inner]
  mixed = jnp.pad(q(zxbcdt[..., inner:inner + conv_dim]),
                  ((0, 0), (width - 1, 0), (0, 0)))
  taps = q(p["conv_kernel"])
  mixed = jax.nn.silu(q(sum(mixed[:, j:j + t] * taps[j]
                            for j in range(width))) + p["conv_bias"])
  dt = jax.nn.softplus(zxbcdt[..., inner + conv_dim:] + p["dt_bias"])
  x = mixed[..., :inner].reshape(batch, t, heads, head_dim)
  per_head = lambda v: jnp.repeat(  # noqa: E731
      v.reshape(batch, t, groups, state), heads // groups, axis=2)
  b = per_head(mixed[..., inner:inner + groups * state])
  c = per_head(mixed[..., inner + groups * state:])
  y = state_space_scan(x, dt, p["A_log"], b, c, p["D"], q,
                       sizes["reference_span"])
  y = (y.reshape(batch, t, inner) * jax.nn.silu(z)).reshape(
      batch, t, groups, inner // groups)
  y = _rms(y, sizes["norm_eps"]).reshape(batch, t, inner) * p["norm_weight"]
  return _dense(p["out_proj"], y, q)


def _attention(p, x, sizes, q):
  b, t, _ = x.shape
  heads, kv_heads, d = (sizes["num_attention_heads"],
                        sizes["num_key_value_heads"], sizes["head_dim"])
  group = heads // kv_heads
  query = _dense(p["q_proj"], x, q).reshape(b, t, kv_heads, group, d)
  key = _dense(p["k_proj"], x, q).reshape(b, t, kv_heads, d)
  value = _dense(p["v_proj"], x, q).reshape(b, t, kv_heads, d)
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
  return _dense(p["o_proj"], out.reshape(b, t, heads * d), q)


def _relu2(x):
  return jnp.square(jax.nn.relu(x))


def router_picks(p, tokens, sizes, q, selection_bias=None):
  """(weights [N, k], experts [N, k]): the picks from s + b, the weights
  from s."""
  scores = jax.nn.sigmoid(_dense(p["router"], tokens, q))
  biased = scores if selection_bias is None else scores + selection_bias
  _, top_idx = jax.lax.top_k(biased, sizes["num_experts_per_tok"])
  picked = jnp.take_along_axis(scores, top_idx, axis=-1)
  weights = sizes["routed_scaling_factor"] * picked / (
      jnp.sum(picked, axis=-1, keepdims=True) + ROUTER_EPS)
  return weights, top_idx


def moe_parts(p, x, sizes, q, selection_bias=None):
  """(shared expert's part, held experts' part) of the layer, [N, hidden]."""
  tokens = x.reshape(-1, x.shape[-1])
  weights, top_idx = router_picks(p, tokens, sizes, q, selection_bias)

  @jax.checkpoint
  def term(w_up, w_down, expert):
    weight = jnp.sum(jnp.where(top_idx == expert, weights, 0.0), axis=-1)
    hidden = _relu2(_product("ni,io->no", tokens, w_up, q))
    return _product("ni,io->no", hidden, w_down, q) * weight[:, None]

  def add(total, expert_weights):
    return total + term(*expert_weights), None

  experts = sizes["first_expert"] + jnp.arange(sizes["num_experts"])
  routed, _ = jax.lax.scan(add, jnp.zeros_like(tokens), (
      p["experts_up"], p["experts_down"], experts))
  shared = _dense(p["shared_down_proj"],
                  _relu2(_dense(p["shared_up_proj"], tokens, q)), q)
  return shared, routed


def _experts(p, x, sizes, q):
  shared, routed = moe_parts(p, x, sizes, q)
  return (shared + routed).reshape(x.shape)


_SLOT = {"mamba": ("mixer", _mamba), "attention": ("mixer", _attention),
         "experts": ("moe", _experts)}


def _layer(p, x, kind, sizes, q):
  name, fn = _SLOT[kind]
  return x + fn(p[name], _norm(p["norm"], x, sizes["norm_eps"]), sizes, q)


def hidden_states(params, tokens, sizes, q):
  """[B, T] ids -> the normed hidden states the head reads, [B, T, hidden]."""
  x = params["embed"]["embedding"][tokens]
  for i, kind in enumerate(layer_kinds(sizes)):
    layer = jax.checkpoint(functools.partial(_layer, kind=kind, sizes=sizes,
                                             q=q))
    x = layer(params[f"layer_{i}"], x)
  return _norm(params["norm_final"], x, sizes["norm_eps"])


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
  out, the mean taken over the rest". A step takes the whole batch at once.
  Returns `losses`, `params0`, `first_gradient` and `params`; the first
  gradient on the host, the two sets of parameters left on the device
  (`params0` drawn again from the seed once the steps are done), because the
  one-chip machine's host does not hold three more copies of 667 M
  parameters beside the trainer's own (`drivers/trainer_streamed.py`).
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
