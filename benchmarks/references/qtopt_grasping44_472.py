"""Plain reference of the QT-Opt grasping critic (Grasping44) and its
training step, and the count of the model's FLOPs.

Follows google-research/tensor2robot `research/qtopt/networks.py:299-615`
(`Grasping44FlexibleGraspParams`) and `t2r_models.py:78-91`: a 6x6/2 stem
convolution with bias and an unscaled batch norm, 3x3/3 max pool, six 5x5
convolutions, 3x3/3 pool, the grasp parameters in named blocks through
Dense(256) each, summed, unscaled batch norm, Dense(64), batch norm, added onto
every position of the image embedding, six 3x3 convolutions, 2x2/2 pool, three
VALID 3x3 convolutions, two Dense(64) and a sigmoid head. Every convolution and
Dense after the stem has no bias and a batch norm (decay 0.9997, eps 1e-3)
before its ReLU. Weights: truncated normal, stddev 0.01. Loss: mean squared
error of the sigmoid against the grasp-success label. Optimizer: momentum 0.9,
learning rate 1e-4 (its decay starts after 10,000 steps), L2 7e-5 on kernels
added to the gradient.

Straightforward jax.numpy in float32 at `highest` matmul precision. It imports
nothing of the program; the weights are drawn here from the seed
(`harness/refmath.py` says how the keys are derived). Batch norm takes its
moments over all rows of the batch, so a step cannot be cut into row blocks;
`jax.checkpoint` around each convolution block keeps the float32 step inside
the chip's memory instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import refmath

BN_DECAY = 0.9997
BN_EPS = 1e-3
INIT_STDDEV = 0.01
LEARNING_RATE = 1e-4
MOMENTUM = 0.9
L2 = 7e-5
FILTERS = 64


def sizes_from_bindings(values: dict) -> dict:
  """The sizes this reference needs, from the configuration's `model` block
  (or a traffic mix's `tiny` block laid over it)."""
  return {
      "image_size": int(values["image_size"]),
      "action_size": int(values["action_size"]),
      "num_convs": tuple(values.get("num_convs", (6, 6, 3))),
      "grasp_param_names": {k: tuple(v) for k, v in
                            values["grasp_param_names"].items()},
  }


# -- shapes and FLOPs ---------------------------------------------------------


def _same(n: int, stride: int) -> int:
  return -(-n // stride)


def layer_shapes(sizes: dict):
  """[(name, kind, output positions per row, MACs per output position)] of
  every convolution and matrix product of one forward pass of one row."""
  layers = []
  n = _same(sizes["image_size"], 2)
  layers.append(("conv1_1", "conv", n * n * FILTERS, 6 * 6 * 3))
  n = _same(n, 3)
  conv_id = 2
  for _ in range(sizes["num_convs"][0]):
    layers.append((f"conv{conv_id}", "conv", n * n * FILTERS,
                   5 * 5 * FILTERS))
    conv_id += 1
  n = _same(n, 3)
  for name, (_, size) in sorted(sizes["grasp_param_names"].items()):
    layers.append((name, "dense", 256, size))
  layers.append(("fcgrasp2", "dense", FILTERS, 256))
  for _ in range(sizes["num_convs"][1]):
    layers.append((f"conv{conv_id}", "conv", n * n * FILTERS,
                   3 * 3 * FILTERS))
    conv_id += 1
  n = _same(n, 2)
  for _ in range(sizes["num_convs"][2]):
    n = n - 2
    layers.append((f"conv{conv_id}", "conv", n * n * FILTERS,
                   3 * 3 * FILTERS))
    conv_id += 1
  flat = n * n * FILTERS
  layers.append(("fc0", "dense", FILTERS, flat))
  layers.append(("fc1", "dense", FILTERS, FILTERS))
  layers.append(("logit", "dense", 1, FILTERS))
  return layers, n


def model_flops(sizes: dict, batch_size: int) -> float:
  """FLOPs one training step needs: 2 x the multiply-adds of every
  convolution and matrix product of the forward pass, x 3 for forward and
  backward, x rows. Nothing is counted for recomputation, batch norm, pooling
  or the optimizer."""
  layers, _ = layer_shapes(sizes)
  macs = sum(outputs * per_output for _, _, outputs, per_output in layers)
  return 2.0 * macs * 3.0 * batch_size


# -- weights from the seed ----------------------------------------------------


def init_state(seed: int, sizes: dict):
  """(params, batch_stats) as the trainer's seeded init draws them: every
  kernel truncated normal(0.01) from its own key, biases 0, scales 1, running
  mean 0 and variance 1."""
  rng = refmath.trainer_init_rng(seed)
  init = jax.nn.initializers.truncated_normal(stddev=INIT_STDDEV)
  layers, _ = layer_shapes(sizes)
  params, stats = {}, {}

  def bn(name, features, scale=True):
    params[name] = {"bias": jnp.zeros((features,), jnp.float32)}
    if scale:
      params[name]["scale"] = jnp.ones((features,), jnp.float32)
    stats[name] = {"mean": jnp.zeros((features,), jnp.float32),
                   "var": jnp.ones((features,), jnp.float32)}

  kernel_shapes = {"conv1_1": (6, 6, 3, FILTERS)}
  for name, kind, outputs, per_output in layers:
    if name == "conv1_1":
      continue
    if kind == "conv":
      k = 5 if per_output == 25 * FILTERS else 3
      kernel_shapes[name] = (k, k, FILTERS, FILTERS)
    else:
      kernel_shapes[name] = (per_output, outputs)
  for name, shape in kernel_shapes.items():
    params[name] = {"kernel": init(refmath.param_key(rng, (name,), 1), shape,
                                   jnp.float32)}
  with_bias = ["conv1_1", "logit"] + sorted(sizes["grasp_param_names"])
  for name in with_bias:
    params[name]["bias"] = jnp.zeros((kernel_shapes[name][-1],), jnp.float32)
  bn("conv1_bn", FILTERS, scale=False)
  bn("fcgrasp_bn", 256, scale=False)
  bn("fcgrasp2_bn", FILTERS)
  for name, kind, _, _ in layers:
    if name.startswith("conv") and name != "conv1_1":
      bn(f"{name}_bn", FILTERS)
  bn("fc0_bn", FILTERS)
  bn("fc1_bn", FILTERS)
  return params, stats


# -- forward, loss, step ------------------------------------------------------


def _conv(x, w, stride, padding, q):
  return q(jax.lax.conv_general_dilated(
      q(x), q(w), (stride, stride), padding,
      dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=refmath.HIGHEST))


def _dense(x, w, q):
  return q(jnp.dot(q(x), q(w), precision=refmath.HIGHEST))


def _max_pool(x, window, stride):
  return jax.lax.reduce_window(
      x, -jnp.inf, jax.lax.max, (1, window, window, 1),
      (1, stride, stride, 1), "SAME")


def _batch_norm(x, p, running, new_stats, name):
  axes = tuple(range(x.ndim - 1))
  mean = jnp.mean(x, axes)
  var = jnp.mean(jnp.square(x - mean), axes)
  y = (x - mean) * jax.lax.rsqrt(var + BN_EPS)
  if "scale" in p:
    y = y * p["scale"]
  new_stats[name] = {
      "mean": BN_DECAY * running["mean"] + (1 - BN_DECAY) * mean,
      "var": BN_DECAY * running["var"] + (1 - BN_DECAY) * var}
  return y + p["bias"]


def forward(params, stats, image, action, sizes, q):
  """Training-mode forward: (logits [B, 1], new batch_stats)."""
  new_stats = {}

  def block(x, conv_name, padding="SAME"):
    # One convolution, its batch norm and ReLU; recomputed in the backward
    # pass so that the float32 step fits beside nothing else on the chip.
    def f(x, w, bn_params):
      local = {}
      y = _conv(x, w, 1, padding, q)
      y = _batch_norm(y, bn_params, stats[f"{conv_name}_bn"], local,
                      f"{conv_name}_bn")
      return jax.nn.relu(y), local
    y, local = jax.checkpoint(f)(x, params[conv_name]["kernel"],
                                 params[f"{conv_name}_bn"])
    new_stats.update(local)
    return y

  def stem(image, w, b, bn_params):
    local = {}
    x = image.astype(jnp.float32) / 255.0
    y = _conv(x, w, 2, "SAME", q) + b
    y = _batch_norm(y, bn_params, stats["conv1_bn"], local, "conv1_bn")
    return _max_pool(jax.nn.relu(y), 3, 3), local

  net, local = jax.checkpoint(stem)(
      image, params["conv1_1"]["kernel"], params["conv1_1"]["bias"],
      params["conv1_bn"])
  new_stats.update(local)
  conv_id = 2
  for _ in range(sizes["num_convs"][0]):
    net = block(net, f"conv{conv_id}")
    conv_id += 1
  net = _max_pool(net, 3, 3)

  fcgrasp = 0.0
  for name, (offset, size) in sorted(sizes["grasp_param_names"].items()):
    part = action[:, offset:offset + size]
    fcgrasp = fcgrasp + _dense(part, params[name]["kernel"], q) \
        + params[name]["bias"]
  fcgrasp = jax.nn.relu(_batch_norm(fcgrasp, params["fcgrasp_bn"],
                                    stats["fcgrasp_bn"], new_stats,
                                    "fcgrasp_bn"))
  fcgrasp = _dense(fcgrasp, params["fcgrasp2"]["kernel"], q)
  fcgrasp = jax.nn.relu(_batch_norm(fcgrasp, params["fcgrasp2_bn"],
                                    stats["fcgrasp2_bn"], new_stats,
                                    "fcgrasp2_bn"))
  net = net + fcgrasp[:, None, None, :]

  for _ in range(sizes["num_convs"][1]):
    net = block(net, f"conv{conv_id}")
    conv_id += 1
  net = _max_pool(net, 2, 2)
  for _ in range(sizes["num_convs"][2]):
    net = block(net, f"conv{conv_id}", padding="VALID")
    conv_id += 1

  net = net.reshape(net.shape[0], -1)
  for name in ("fc0", "fc1"):
    net = _dense(net, params[name]["kernel"], q)
    net = jax.nn.relu(_batch_norm(net, params[f"{name}_bn"],
                                  stats[f"{name}_bn"], new_stats,
                                  f"{name}_bn"))
  logits = _dense(net, params["logit"]["kernel"], q) + params["logit"]["bias"]
  return logits, new_stats


def loss_fn(params, stats, batch, sizes, q):
  logits, new_stats = forward(params, stats, batch["features/state/image"],
                              batch["features/action/action"], sizes, q)
  target = batch["labels/reward"].astype(jnp.float32)
  loss = jnp.mean(jnp.square(jax.nn.sigmoid(logits) - target))
  return loss, new_stats


@functools.partial(jax.jit, static_argnames=("sizes_key", "precision"),
                   donate_argnums=(0, 1, 2))
def _step(params, stats, trace, batch, sizes_key, precision):
  sizes = dict(sizes_key)
  sizes["grasp_param_names"] = dict(sizes["grasp_param_names"])
  q = refmath.quantizer(precision)
  (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
      params, stats, batch, sizes, q)
  params, trace, seen = refmath.sgd_momentum_step(
      params, trace, grads, learning_rate=LEARNING_RATE, momentum=MOMENTUM,
      weight_decay=L2)
  return params, new_stats, trace, loss, seen


def _hashable(sizes: dict):
  items = dict(sizes)
  items["grasp_param_names"] = tuple(sorted(
      (k, tuple(v)) for k, v in sizes["grasp_param_names"].items()))
  items["num_convs"] = tuple(items["num_convs"])
  return tuple(sorted(items.items()))


def train_steps(seed: int, sizes: dict, batches, precision: str = "float32",
                rows=None):
  """Follows the trainer's first `len(batches)` steps from its seeded init.

  `batches` are the pool's host batches in the order the trainer is fed
  them, as flat dicts of numpy arrays (`features/state/image`, uint8;
  `features/action/action`; `labels/reward`). `rows`, a slice, plants the
  fault "part of the batch left out, the mean taken over the rest".

  Returns host numpy: `losses`, `params0`, `first_gradient` (as the
  optimizer's accumulator gets it: L2 term included), `params`, and
  `first_batch_stats` (the running moments after step 1).
  """
  import numpy as np

  params, stats = init_state(seed, sizes)
  params0 = jax.device_get(params)
  trace = jax.tree_util.tree_map(jnp.zeros_like, params)
  losses, first = [], None
  for batch in batches:
    if rows is not None:
      batch = {k: v[rows] for k, v in batch.items()}
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    params, stats, trace, loss, seen = _step(
        params, stats, trace, batch, _hashable(sizes), precision)
    losses.append(float(loss))
    if first is None:
      first = jax.device_get(seen)
      first_stats = jax.device_get(stats)
    del seen
  out = {"losses": np.asarray(losses, np.float64), "params0": params0,
         "first_gradient": first, "params": jax.device_get(params),
         "first_batch_stats": first_stats}
  del params, stats, trace
  return out
