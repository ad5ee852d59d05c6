"""Plain reference of the repository's sequence policy trunk and its training
step, and the count of the model's FLOPs.

The trunk is this repository's own (it is no published model): observations
[B, T, obs] through a Dense embedding, N pre-LayerNorm blocks of causal
multi-head self-attention and a two-layer tanh-GELU MLP of twice the hidden
width, each added to the residual stream, and a Dense head to [B, T, action].
LayerNorm eps 1e-6; Dense kernels LeCun-normal, biases 0. Loss: mean squared
error against the action labels. Optimizer: Adam, learning rate 1e-4.

Straightforward jax.numpy in float32 at `highest` matmul precision, with the
whole [T, T] score matrix and a softmax: no kernel, no blocks along T. It
imports nothing of the program; the weights are drawn here from the seed
(`harness/refmath.py`). Rows of the batch do not meet before the loss, so a
step is taken in blocks of rows whose gradients are averaged: that is what
lets 128 sequences of 2,048 fit in float32 (`reference_rows` a block).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.harness import refmath

LEARNING_RATE = 1e-4
LN_EPS = 1e-6


def sizes_from_bindings(values: dict) -> dict:
  return {
      "obs_size": int(values["obs_size"]),
      "action_size": int(values["action_size"]),
      "sequence_length": int(values["sequence_length"]),
      "hidden_size": int(values["hidden_size"]),
      "num_blocks": int(values["num_blocks"]),
      "num_heads": int(values["num_heads"]),
      "reference_rows": int(values.get("reference_rows", 2)),
  }


# -- FLOPs --------------------------------------------------------------------


def attention_flops_forward(sizes: dict) -> float:
  """FLOPs of the causal attention core (scores and weighted sum) of one
  sequence in all blocks, forward: 2 products x 2 FLOPs x T*T/2 x width."""
  t, h = sizes["sequence_length"], sizes["hidden_size"]
  return sizes["num_blocks"] * 2 * 2.0 * (t * t / 2.0) * h


def model_flops(sizes: dict, batch_size: int) -> float:
  """FLOPs one training step needs: 2 x the multiply-adds of every matrix
  product of the forward pass, causal attention counted as half the square,
  x 3 for forward and backward, x sequences. Recomputation (the flash
  backward recomputes the scores) counts nothing."""
  t, h = sizes["sequence_length"], sizes["hidden_size"]
  per_token_macs = (sizes["obs_size"] * h
                    + sizes["num_blocks"] * (4 * h * h + 2 * h * 2 * h)
                    + h * sizes["action_size"])
  forward = 2.0 * per_token_macs * t + attention_flops_forward(sizes)
  return forward * 3.0 * batch_size


# -- weights from the seed ----------------------------------------------------


def init_state(seed: int, sizes: dict):
  """(params, {}) as the trainer's seeded init draws them."""
  rng = refmath.trainer_init_rng(seed)
  init = jax.nn.initializers.lecun_normal()
  h = sizes["hidden_size"]

  def dense(path, fan_in, fan_out):
    return {"kernel": init(refmath.param_key(rng, path, 1), (fan_in, fan_out),
                           jnp.float32),
            "bias": jnp.zeros((fan_out,), jnp.float32)}

  def layer_norm():
    return {"scale": jnp.ones((h,), jnp.float32),
            "bias": jnp.zeros((h,), jnp.float32)}

  params = {"embed": dense(("embed",), sizes["obs_size"], h),
            "head": dense(("head",), h, sizes["action_size"])}
  for i in range(sizes["num_blocks"]):
    params[f"ln_attn_{i}"] = layer_norm()
    params[f"attn_{i}"] = {
        name: dense((f"attn_{i}", name), h, h)
        for name in ("q_proj", "k_proj", "v_proj", "out_proj")}
    params[f"ln_mlp_{i}"] = layer_norm()
    params[f"mlp_in_{i}"] = dense((f"mlp_in_{i}",), h, 2 * h)
    params[f"mlp_out_{i}"] = dense((f"mlp_out_{i}",), 2 * h, h)
  return params, {}


# -- forward, loss, step ------------------------------------------------------


def _dense(p, x, q):
  return q(jnp.dot(q(x), q(p["kernel"]), precision=refmath.HIGHEST)
           + p["bias"])


def _layer_norm(p, x):
  mean = jnp.mean(x, -1, keepdims=True)
  var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
  return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _attention(p, x, num_heads, q):
  b, t, h = x.shape
  d = h // num_heads

  def heads(y):
    return y.reshape(b, t, num_heads, d).transpose(0, 2, 1, 3)

  query = heads(_dense(p["q_proj"], x, q))
  key = heads(_dense(p["k_proj"], x, q))
  value = heads(_dense(p["v_proj"], x, q))
  scores = q(jnp.einsum("bhqd,bhkd->bhqk", q(query), q(key),
                        precision=refmath.HIGHEST)) / math.sqrt(d)
  mask = jnp.tril(jnp.ones((t, t), bool))
  scores = jnp.where(mask, scores, -jnp.inf)
  weights = jax.nn.softmax(scores, axis=-1)
  out = q(jnp.einsum("bhqk,bhkd->bhqd", q(weights), q(value),
                     precision=refmath.HIGHEST))
  out = out.transpose(0, 2, 1, 3).reshape(b, t, h)
  return _dense(p["out_proj"], out, q)


def forward(params, observation, sizes, q):
  x = _dense(params["embed"], observation.astype(jnp.float32), q)
  for i in range(sizes["num_blocks"]):
    y = _layer_norm(params[f"ln_attn_{i}"], x)
    x = x + _attention(params[f"attn_{i}"], y, sizes["num_heads"], q)
    y = _layer_norm(params[f"ln_mlp_{i}"], x)
    y = jax.nn.gelu(_dense(params[f"mlp_in_{i}"], y, q), approximate=True)
    x = x + _dense(params[f"mlp_out_{i}"], y, q)
  return _dense(params["head"], x, q)


def loss_fn(params, batch, sizes, q):
  action = forward(params, batch["features/observation"], sizes, q)
  return jnp.mean(jnp.square(
      action - batch["labels/action"].astype(jnp.float32)))


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision"))
def _block_grad(params, batch, sizes_key, precision):
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
  flat dicts of numpy arrays (`features/observation`, `labels/action`).
  `rows`, a slice, plants the fault "part of the batch left out, the mean
  taken over the rest". Returns host numpy: `losses`, `params0`,
  `first_gradient` and `params`.
  """
  import numpy as np

  params, _ = init_state(seed, sizes)
  params0 = jax.device_get(params)
  mu = jax.tree_util.tree_map(jnp.zeros_like, params)
  nu = jax.tree_util.tree_map(jnp.zeros_like, params)
  sizes_key = tuple(sorted(sizes.items()))
  losses, first = [], None
  for count, batch in enumerate(batches, start=1):
    if rows is not None:
      batch = {k: v[rows] for k, v in batch.items()}
    n = len(next(iter(batch.values())))
    block = min(sizes["reference_rows"], n)
    if n % block:
      raise ValueError(f"{n} rows do not divide into blocks of {block}")
    loss_sum, grads = 0.0, None
    for start in range(0, n, block):
      part = {k: jnp.asarray(v[start:start + block])
              for k, v in batch.items()}
      loss, g = _block_grad(params, part, sizes_key, precision)
      loss_sum += float(loss)
      grads = g if grads is None else jax.tree_util.tree_map(
          jnp.add, grads, g)
    blocks = n // block
    grads = jax.tree_util.tree_map(lambda g: g / blocks, grads)
    if first is None:
      first = jax.device_get(grads)
    params, mu, nu = _adam(params, mu, nu, grads, count)
    losses.append(loss_sum / blocks)
  out = {"losses": np.asarray(losses, np.float64), "params0": params0,
         "first_gradient": first, "params": jax.device_get(params)}
  del params, mu, nu, grads
  return out
