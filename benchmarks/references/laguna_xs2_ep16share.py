"""Plain reference of one chip's share of poolside's Laguna-XS.2 on the
training path, and the count of the model's FLOPs.

Source: https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json
(`model_type` `laguna`). Where the config leaves a form open, the form taken
is the one the configuration file states under `assumed`. With
norm(x) = w * x / sqrt(mean(x^2) + eps), w from 1, eps `rms_norm_eps`:

  layer i:   x = x + attention_i(norm(x)); x = x + ffn_i(norm(x)). After the
             last layer norm, then the untied head onto the ids held here.
  attention: H_i query heads (`num_attention_heads_per_layer`), 8 key/value
             heads, d 128: [q, gate] = x W_q (H_i d each), k = x W_k,
             v = x W_v; q, k through norm over the head; the rotary of the
             layer's kind; softmax(q k^T / sqrt(d)) v, each key/value head
             serving H_i / 8 query heads, causal on `full_attention` layers
             and over keys i - 511 .. i on `sliding_attention` layers
             (`sliding_window` 512); (attn * sigmoid(gate)) W_o.
  rotary:    half-split pairing (dimension j with j + r/2) on the leading r
             dimensions of each head, the rest passed through.
             `sliding_attention`: r = d, inv_freq = 10000^(-2j/r).
             `full_attention`: YaRN on r = d / 2 at base 500000: inv_freq_e =
             base^(-2j/r), inv_freq_i = inv_freq_e / 64, [low, high] = [floor,
             ceil] of r ln(4096 / (beta 2 pi)) / (2 ln base) at beta 64 and 1,
             clamped to [0, r - 1], ramp = clip((j - low) / (high - low), 0,
             1), inv_freq = inv_freq_i ramp + inv_freq_e (1 - ramp); cos and
             sin times `attention_factor`.
  ffn:       `dense` (`mlp_layer_types`): (silu(x W_g) * (x W_u)) W_d at
             `intermediate_size`. `sparse`: s = sigmoid(x W_r) over all 256;
             the 8 largest of s + b (b zero and fixed); weights 2.5 s_i /
             (sum of the 8 picks' s + 1e-20); expert e (silu(x W_g) *
             (x W_u)) W_d; shared expert of the same form, not gated;
             ffn = shared + the picked experts' weighted terms.
  loss:      mean over rows of weight x mean over positions of the
             cross-entropy of the next token; Adam, learning rate 1e-4.

The share (PERF.md section 4): layers 0-4 of 40, experts 0-15 of each sparse
layer's 256 (the router keeps its 256 outputs and its 8 a token; what the 240
absent experts would add is left out), ids 0-12,543 of the vocabulary.

Straightforward jax.numpy in float32 at `highest` matmul precision. It
imports nothing of the program, has no kernel, no sort and no buffer:
attention is a masked softmax over the keys a block of query rows may see,
the band written out as a mask; the experts are a loop over the 16 held,
every token through each, masked by its weight. Departures from the source
are the program's (the configuration file lists them): q and gate lie one
after the other in W_q.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness import refmath

LEARNING_RATE = 1e-4
INIT_STDDEV = 0.02
ROUTER_EPS = 1e-20
# A layer type of the config -> what its attention is.
KINDS = {"full_attention": "global", "sliding_attention": "sliding",
         "global": "global", "sliding": "sliding"}
ROPE_KEYS = ("rope_type", "rope_theta", "partial_rotary_factor", "factor",
             "original_max_position_embeddings", "beta_fast", "beta_slow",
             "attention_factor")


def _rope(entry: dict) -> tuple:
  return tuple((key, entry[key]) for key in ROPE_KEYS if key in entry)


def sizes_from_bindings(values: dict) -> dict:
  rope = values["rope_parameters"]
  return {
      "sequence_length": int(values["sequence_length"]),
      "vocab_size": int(values["vocab_size"]),
      "hidden_size": int(values["hidden_size"]),
      # one entry a layer held here
      "layer_types": tuple(KINDS[k] for k in values["layer_types"]),
      "mlp_layer_types": tuple(values["mlp_layer_types"]),
      "num_attention_heads_per_layer": tuple(
          int(h) for h in values["num_attention_heads_per_layer"]),
      "num_key_value_heads": int(values["num_key_value_heads"]),
      "head_dim": int(values["head_dim"]),
      "sliding_window": int(values["sliding_window"]),
      "rms_norm_eps": float(values["rms_norm_eps"]),
      "rope_global": _rope(rope["full_attention"]),
      "rope_sliding": _rope(rope["sliding_attention"]),
      "intermediate_size": int(values["intermediate_size"]),
      "router_width": int(values["router_width"]),
      "first_expert": int(values.get("first_expert", 0)),
      "num_experts": int(values["num_experts"]),   # held here
      "num_experts_per_tok": int(values["num_experts_per_tok"]),
      "moe_intermediate_size": int(values["moe_intermediate_size"]),
      "shared_expert_intermediate_size": int(
          values["shared_expert_intermediate_size"]),
      "routed_scaling_factor": float(values["routed_scaling_factor"]),
      # the program's buffer, for the readers of its trace; not used here
      "expert_buffer_factor": float(values.get("expert_buffer_factor", 2.0)),
      "reference_query_rows": int(values.get("reference_query_rows", 512)),
  }


# -- FLOPs --------------------------------------------------------------------


def band_keys(t: int, window) -> float:
  """Score entries a head: sum over rows i of the keys row i sees,
  min(i + 1, window), or T^2 / 2 (half the square) where there is no
  window."""
  if window is None:
    return t * t / 2.0
  w = min(int(window), t)
  return w * (w + 1) / 2.0 + (t - w) * float(w)


def macs_per_token(sizes: dict) -> dict:
  """Forward multiply-adds a token, by part; the attention parts a layer,
  at each kind's head count."""
  h, d = sizes["hidden_size"], sizes["head_dim"]
  t = sizes["sequence_length"]
  kv_dim = sizes["num_key_value_heads"] * d
  width = sizes["moe_intermediate_size"]
  out = {
      "router": h * sizes["router_width"],
      "shared_expert": 3 * h * sizes["shared_expert_intermediate_size"],
      # balanced load: top_k x held / router_width experts a token
      "routed_experts": (sizes["num_experts_per_tok"] * sizes["num_experts"]
                         / sizes["router_width"]) * 3 * h * width,
      "dense_mlp": 3 * h * sizes["intermediate_size"],
      "head": h * sizes["vocab_size"],
  }
  for i, kind in enumerate(sizes["layer_types"]):
    attn_dim = sizes["num_attention_heads_per_layer"][i] * d
    window = sizes["sliding_window"] if kind == "sliding" else None
    out[f"projections/layer_{i}"] = (h * 2 * attn_dim + 2 * h * kv_dim
                                     + attn_dim * h)
    # scores and weighted sum over the keys each row sees
    out[f"attention/layer_{i}"] = 2 * band_keys(t, window) / t * attn_dim
  return out


def model_flops(sizes: dict, batch_size: int) -> float:
  """FLOPs one training step needs: 2 x the forward multiply-adds x 3, the
  routed experts at the balanced load, causal attention as half the square,
  windowed attention as the band's sum of min(i + 1, W) keys a row, nothing
  for recomputation, for tiles the kernels visit outside the band or for
  buffer rows that hold no pair."""
  m = macs_per_token(sizes)
  per_token = m["head"]
  for i, ffn in enumerate(sizes["mlp_layer_types"]):
    per_token += m[f"projections/layer_{i}"] + m[f"attention/layer_{i}"]
    per_token += m["dense_mlp"] if ffn == "dense" else (
        m["router"] + m["shared_expert"] + m["routed_experts"])
  return 2.0 * per_token * 3.0 * sizes["sequence_length"] * batch_size


# -- weights from the seed ----------------------------------------------------


def init_state(seed: int, sizes: dict):
  """(params, {}) as the trainer's seeded init draws them: normal(0.02) for
  every matrix and the embedding, every norm's weight 1."""
  rng = refmath.trainer_init_rng(seed)
  # Jitted, as the trainer's init is: compiled, the scaling of a draw rounds
  # in another place than op by op (one float32 ulp).
  normal = jax.jit(jax.nn.initializers.normal(INIT_STDDEV),
                   static_argnums=(1, 2))
  h, d = sizes["hidden_size"], sizes["head_dim"]

  def matrix(path, counter, shape):
    return normal(refmath.param_key(rng, path, counter), shape, jnp.float32)

  def dense(path, fan_in, fan_out):
    return {"kernel": matrix(path, 1, (fan_in, fan_out))}

  ones = lambda n: {"weight": jnp.ones((n,), jnp.float32)}  # noqa: E731
  kv_dim = sizes["num_key_value_heads"] * d
  width, held = sizes["moe_intermediate_size"], sizes["num_experts"]
  shared, dense_width = (sizes["shared_expert_intermediate_size"],
                         sizes["intermediate_size"])
  params = {"embed": {"embedding": matrix(("embed",), 1,
                                          (sizes["vocab_size"], h))},
            "head": matrix((), 1, (h, sizes["vocab_size"])),
            "norm_final": ones(h)}
  for i, ffn in enumerate(sizes["mlp_layer_types"]):
    at = (f"layer_{i}",)
    attn_dim = sizes["num_attention_heads_per_layer"][i] * d
    layer = {"norm_mixer": ones(h), "mixer": {
        "q_proj": dense(at + ("mixer", "q_proj"), h, 2 * attn_dim),
        "k_proj": dense(at + ("mixer", "k_proj"), h, kv_dim),
        "v_proj": dense(at + ("mixer", "v_proj"), h, kv_dim),
        "q_norm": ones(d), "k_norm": ones(d),
        "o_proj": dense(at + ("mixer", "o_proj"), attn_dim, h)}}
    if ffn == "dense":
      m = at + ("mlp",)
      layer["norm_mlp"] = ones(h)
      layer["mlp"] = {
          "gate_proj": dense(m + ("gate_proj",), h, dense_width),
          "up_proj": dense(m + ("up_proj",), h, dense_width),
          "down_proj": dense(m + ("down_proj",), dense_width, h)}
    else:
      m = at + ("moe",)
      layer["norm_moe"] = ones(h)
      layer["moe"] = {
          "experts_gate_up": matrix(m, 1, (held, h, 2 * width)),
          "experts_down": matrix(m, 2, (held, width, h)),
          "router": dense(m + ("router",), h, sizes["router_width"]),
          "shared_gate_proj": dense(m + ("shared_gate_proj",), h, shared),
          "shared_up_proj": dense(m + ("shared_up_proj",), h, shared),
          "shared_down_proj": dense(m + ("shared_down_proj",), shared, h)}
    params[f"layer_{i}"] = layer
  return params, {}


# -- forward, loss, step ------------------------------------------------------


def _product(subscripts, a, b, q):
  """Both operands and the result rounded to the precision asked for."""
  return q(jnp.einsum(subscripts, q(a), q(b), precision=refmath.HIGHEST))


def _dense(p, x, q):
  return _product("...i,io->...o", x, p["kernel"], q)


def _norm(p, x, eps):
  return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                           ) * p["weight"]


def inverse_frequencies(rope: dict, head_dim: int):
  """(inv_freq [r / 2], scale of cos and sin) of one rotary entry."""
  r = int(head_dim * rope.get("partial_rotary_factor", 1))
  base = float(rope["rope_theta"])
  extra = base ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
  if rope.get("rope_type", "default") != "yarn":
    return extra, 1.0

  def correction_dim(rotations):
    return (r * math.log(rope["original_max_position_embeddings"]
                         / (rotations * 2 * math.pi)) / (2 * math.log(base)))

  low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
  high = min(math.ceil(correction_dim(rope["beta_slow"])), r - 1)
  if low == high:
    high += 0.001
  ramp = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - low)
                  / (high - low), 0.0, 1.0)
  inter = extra / rope["factor"]
  return inter * ramp + extra * (1.0 - ramp), float(rope["attention_factor"])


def _rotary(x, rope: dict):
  """x [B, T, ..., D], its leading r dimensions rotated."""
  t, d = x.shape[1], x.shape[-1]
  inv_freq, scale = inverse_frequencies(rope, d)
  half = inv_freq.shape[0]
  angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None]
  shape = (1, t) + (1,) * (x.ndim - 3) + (half,)
  cos = (jnp.cos(angles) * scale).reshape(shape)
  sin = (jnp.sin(angles) * scale).reshape(shape)
  x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
  return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                         axis=-1)


def _attention(p, x, layer: int, sizes, q):
  b, t, _ = x.shape
  kind = sizes["layer_types"][layer]
  heads = sizes["num_attention_heads_per_layer"][layer]
  kv_heads, d = sizes["num_key_value_heads"], sizes["head_dim"]
  group = heads // kv_heads
  eps = sizes["rms_norm_eps"]
  window = sizes["sliding_window"] if kind == "sliding" else None
  rope = dict(sizes["rope_sliding" if kind == "sliding" else "rope_global"])
  query, gate = jnp.split(_dense(p["q_proj"], x, q), 2, axis=-1)
  query = _norm(p["q_norm"], query.reshape(b, t, kv_heads, group, d), eps)
  key = _norm(p["k_norm"], _dense(p["k_proj"], x, q).reshape(
      b, t, kv_heads, d), eps)
  value = _dense(p["v_proj"], x, q).reshape(b, t, kv_heads, d)
  query, key = _rotary(query, rope), _rotary(key, rope)
  block = min(sizes["reference_query_rows"], t)
  if t % block:
    raise ValueError(f"{t} query rows do not divide into blocks of {block}")

  def rows(start):
    """The block of query rows from `start` over the keys it may see: from
    the band's lower edge (0 where there is no window) to its last row."""
    lo = 0 if window is None else max(0, start - window + 1)
    hi = start + block

    @jax.checkpoint
    def part(query_rows, keys, values):
      scores = _product("bqgrd,bkgd->bgrqk", query_rows, keys, q) * d ** -0.5
      i = (start + jnp.arange(block))[:, None]
      j = (lo + jnp.arange(hi - lo))[None, :]
      allowed = i >= j
      if window is not None:
        allowed &= i - j < window
      weights = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), axis=-1)
      return _product("bgrqk,bkgd->bqgrd", weights, values, q)

    return part(query[:, start:hi], key[:, lo:hi], value[:, lo:hi])

  out = jnp.concatenate([rows(s) for s in range(0, t, block)], axis=1)
  out = out.reshape(b, t, heads * d) * jax.nn.sigmoid(gate)
  return _dense(p["o_proj"], out, q)


def _dense_mlp(p, x, q):
  hidden = jax.nn.silu(_dense(p["gate_proj"], x, q)) * _dense(
      p["up_proj"], x, q)
  return _dense(p["down_proj"], hidden, q)


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
  width = sizes["moe_intermediate_size"]
  weights, top_idx = router_picks(p, tokens, sizes, q, selection_bias)

  @jax.checkpoint
  def term(w_gate_up, w_down, expert):
    weight = jnp.sum(jnp.where(top_idx == expert, weights, 0.0), axis=-1)
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
  return _dense(p["shared_down_proj"], hidden, q), routed


def _layer(p, x, layer: int, sizes, q):
  eps = sizes["rms_norm_eps"]
  x = x + _attention(p["mixer"], _norm(p["norm_mixer"], x, eps), layer,
                     sizes, q)
  if sizes["mlp_layer_types"][layer] == "dense":
    return x + _dense_mlp(p["mlp"], _norm(p["norm_mlp"], x, eps), q)
  shared, routed = moe_parts(p["moe"], _norm(p["norm_moe"], x, eps), sizes, q)
  return x + (shared + routed).reshape(x.shape)


def hidden_states(params, tokens, sizes, q):
  """[B, T] ids -> the normed hidden states the head reads, [B, T, hidden]."""
  x = params["embed"]["embedding"][tokens]
  for i in range(len(sizes["layer_types"])):
    layer = jax.checkpoint(functools.partial(_layer, layer=i, sizes=sizes,
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
  out, the mean taken over the rest". A step takes the whole batch at once.
  Returns `losses`, `params0`, `first_gradient` and `params`; the first
  gradient on the host, the two sets of parameters left on the device
  (`params0` drawn again from the seed once the steps are done), because the
  one-chip machine's host does not hold three more copies of 565 M
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
