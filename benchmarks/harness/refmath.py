"""What the plain references share: the precision a reference computes in,
the keys that seeded initializers are drawn with, and two optimizers written
out by hand.

Nothing here imports the program. The key derivation repeats what
`flax.linen.Module.init` does with the `params` stream (flax.core.scope:
`LazyRng.create(rng, *module_path, counter)`), through flax's own `LazyRng`,
so a reference can draw the weights the trainer's seeded init draws without
taking them from the trainer. tests/benchmark/test_references.py holds both
references to bit-equal initial weights at tiny sizes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax.core.scope import LazyRng

PRECISIONS = ("float32", "bfloat16", "fp8")
HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _straight_through(round_fn):
  """Rounds on the way forward and passes the cotangent through unrounded
  (differentiating the cast itself would round the cotangent to the low
  type, unscaled, and fp8 would flush it to zero)."""
  @jax.custom_vjp
  def q(x):
    return round_fn(x)

  q.defvjp(lambda x: (round_fn(x), None), lambda _, g: (g,))
  return q


def _round_bf16(x):
  return x.astype(jnp.bfloat16).astype(jnp.float32)


def _round_fp8(x):
  scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
  return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def quantizer(precision: str):
  """The rounding a reference applies to both operands and to the result of
  every convolution and matrix product: what the program's dtype policy does
  when its compute dtype is set to that type (operands cast down, the product
  returned in the type), with the arithmetic in between left in float32. The
  backward's products take the rounded operands the forward saved; cotangents
  stay float32. `float32` is the reference proper; `fp8` (e4m3, one scale per
  tensor) is the control for a bfloat16 configuration, the step that would
  tempt a later PR; `bfloat16` is the control for a float32 one."""
  if precision == "float32":
    return lambda x: x
  if precision == "bfloat16":
    return _straight_through(_round_bf16)
  if precision == "fp8":
    return _straight_through(_round_fp8)
  raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def trainer_init_rng(seed: int):
  """The `params` key the trainer's init hands to flax for `seed`:
  PRNGKey(seed) -> split (init, state) -> split (params, dropout)."""
  init_rng, _ = jax.random.split(jax.random.PRNGKey(seed))
  params_rng, _ = jax.random.split(init_rng)
  return params_rng


def param_key(params_rng, path, counter: int):
  """Key of the `counter`-th parameter (from 1) created in the module at
  `path` (a tuple of names from the root)."""
  return LazyRng.create(params_rng, *path, counter).as_jax_rng()


def tree_paths(tree, prefix=()):
  """[(path tuple, leaf)] of a nested dict, sorted by key at each level."""
  out = []
  for key in sorted(tree):
    value = tree[key]
    if isinstance(value, dict):
      out.extend(tree_paths(value, prefix + (key,)))
    else:
      out.append((prefix + (key,), value))
  return out


def sgd_momentum_step(params, trace, grads, *, learning_rate, momentum,
                      weight_decay):
  """g' = g + wd * p on leaves of rank > 1; trace = g' + m * trace;
  p = p - lr * trace. Returns (params, trace, g') — g' is the gradient as
  the momentum accumulator gets it."""
  def decayed(g, p):
    return g + weight_decay * p if p.ndim > 1 else g
  grads = jax.tree_util.tree_map(decayed, grads, params)
  trace = jax.tree_util.tree_map(lambda g, t: g + momentum * t, grads, trace)
  params = jax.tree_util.tree_map(lambda p, t: p - learning_rate * t,
                                  params, trace)
  return params, trace, grads


def adam_step(params, mu, nu, grads, count, *, learning_rate, b1=0.9,
              b2=0.999, eps=1e-8):
  """Adam as optax.adam writes it (no eps_root, bias-corrected moments).
  `count` is the number of the step being taken, from 1."""
  mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
  nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu,
                              grads)
  c1 = 1 - b1 ** count
  c2 = 1 - b2 ** count
  params = jax.tree_util.tree_map(
      lambda p, m, v: p - learning_rate * (m / c1) / (jnp.sqrt(v / c2) + eps),
      params, mu, nu)
  return params, mu, nu
