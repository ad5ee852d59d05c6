"""`ops/short_conv.py` against the XLA expression it replaces
(`_conv_silu_xla`): forward and all three cotangents, kernels interpreted,
operands read in place at a column offset as the mixers hand them over."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.ops import short_conv as sc

# T at one row tile, two, and a tile + 8 (520 = 5 tiles of 104).
LENGTHS = {"one_tile": sc._MAX_ROWS, "two_tiles": 2 * sc._MAX_ROWS,
           "a_tile_and_8": sc._MAX_ROWS + 8}
# The operand's width and the columns read: two 128-lane channel blocks
# at an offset of one.
WIDTH, START, CHANNELS = 512, 128, 256


def _operands(t, width, bias, dtype=jnp.float32, batch=2, seed=0):
  keys = jax.random.split(jax.random.PRNGKey(seed), 4)
  x = jax.random.normal(keys[0], (batch, t, WIDTH), jnp.float32).astype(dtype)
  kernel = jax.random.uniform(keys[1], (width, CHANNELS), jnp.float32,
                              -0.5, 0.5)
  b = (jax.random.uniform(keys[2], (CHANNELS,), jnp.float32, -0.5, 0.5)
       if bias else None)
  dy = jax.random.normal(keys[3], (batch, t, CHANNELS),
                         jnp.float32).astype(dtype)
  return x, kernel, b, dy


def _reference(x, kernel, bias, start=START):
  return sc._conv_silu_xla(x[..., start:start + kernel.shape[1]], kernel,
                           bias)


def _op(x, kernel, bias, start=START):
  return sc.causal_conv_silu(x, kernel, bias, start, interpret=True)


@jax.jit
def _both(x, kernel, bias, dy):
  """(y, dx, dk, db) of the op and of the reference."""
  out = []
  for f in (_op, _reference):
    y, vjp = jax.vjp(f, x, kernel, bias)
    out.append((y,) + vjp(dy))
  return out


def _kernels(fn, *args):
  """Names of the op's Pallas calls in fn's jaxpr."""
  text = str(jax.make_jaxpr(fn)(*args))
  return re.findall(r"^\s*name=(short_conv\w*)\s*$", text, re.M)


@pytest.mark.parametrize("width", [4, 2], ids=["taps_4", "taps_2"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("length", list(LENGTHS))
def test_kernels_match_the_xla_expression(length, bias, width):
  """y within one float32 ulp of the expression's; dx, dk, db within 1e-5
  of the largest entry (float32 sums in another order)."""
  x, kernel, b, dy = _operands(LENGTHS[length], width, bias)
  (y, dx, dk, db), (y_ref, dx_ref, dk_ref, db_ref) = _both(x, kernel, b, dy)
  y, y_ref = np.asarray(y), np.asarray(y_ref)
  assert np.all(np.abs(y - y_ref) <= np.spacing(np.abs(y_ref))), (
      np.max(np.abs(y - y_ref)))
  grads = [(dx, dx_ref), (dk, dk_ref)] + ([(db, db_ref)] if bias else [])
  for got, want in grads:
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.max(np.abs(got - want)) <= 1e-5 * np.max(np.abs(want))
  if not bias:
    assert db is None
  # nothing outside the columns read
  assert not np.any(np.asarray(dx)[..., :START])
  assert not np.any(np.asarray(dx)[..., START + CHANNELS:])


def test_bfloat16_operands_round_once():
  """bfloat16 in, bfloat16 out, each result the float32 sum rounded once:
  y within a bfloat16 ulp of the expression's, the cotangents within one
  of their largest entry."""
  x, kernel, b, dy = _operands(2 * sc._MAX_ROWS, 4, True, jnp.bfloat16)
  kernel, b = kernel.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
  (y, dx, dk, db), (y_ref, dx_ref, dk_ref, db_ref) = _both(x, kernel, b, dy)
  y, y_ref = np.asarray(y, np.float32), np.asarray(y_ref, np.float32)
  assert np.all(np.abs(y - y_ref) <= 2.0 ** -7 * np.abs(y_ref))
  for got, want in ((dx, dx_ref), (dk, dk_ref), (db, db_ref)):
    assert got.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.max(np.abs(got - want)) <= 2.0 ** -7 * np.max(np.abs(want))


@pytest.mark.parametrize("row", [0, 103, 104, 300, 519])
def test_causal(row):
  """A change of x at one row changes no output before it, and that row's."""
  x, kernel, b, _ = _operands(sc._MAX_ROWS + 8, 4, True, batch=1)
  y = _op(x, kernel, b)
  moved = _op(x.at[:, row, START:START + CHANNELS].add(1.0), kernel, b)
  changed = np.any(np.asarray(moved != y), axis=(0, 2))
  assert not changed[:row].any()
  assert changed[row]


@pytest.mark.parametrize("t,channels,width", [(64, 96, 4), (20, 128, 4),
                                              (64, 128, 12)],
                         ids=["96_channels", "T_20", "12_taps"])
def test_other_shapes_take_the_xla_expression(t, channels, width):
  """No kernel where `_kernel_takes` says no: the expression itself, to
  the bit, forward and backward."""
  keys = jax.random.split(jax.random.PRNGKey(1), 3)
  x = jax.random.normal(keys[0], (1, t, 256))
  kernel = jax.random.normal(keys[1], (width, channels))
  b = jax.random.normal(keys[2], (channels,))
  assert not sc._kernel_takes(x.shape, x.dtype, width, channels)
  assert _kernels(_op, x, kernel, b) == []
  loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)  # noqa: E731
  got = jax.grad(loss(_op), argnums=(0, 1, 2))(x, kernel, b)
  want = jax.grad(loss(_reference), argnums=(0, 1, 2))(x, kernel, b)
  for g, w in zip(got, want):
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("start", [0, 64, 128], ids=["at_0", "unaligned",
                                                    "aligned"])
def test_kernels_engage_by_shape(start):
  """The two kernels wherever the channels are whole 128-lane tiles; an
  offset that is no multiple of 128 hands them the slice."""
  x, kernel, b, dy = _operands(64, 4, True, batch=1)
  assert _kernels(lambda *a: _op(*a, start=start), x, kernel, b) == [
      "short_conv"]
  grad = jax.grad(lambda *a: jnp.sum(_op(*a, start=start)),
                  argnums=(0, 1, 2))
  assert sorted(_kernels(grad, x, kernel, b)) == [
      "short_conv", "short_conv_bwd"]
  got = jax.grad(lambda *a: jnp.sum(_op(*a, start=start) * dy),
                 argnums=(0, 1, 2))(x, kernel, b)
  want = jax.grad(lambda *a: jnp.sum(_reference(*a, start=start) * dy),
                  argnums=(0, 1, 2))(x, kernel, b)
  for g, w in zip(got, want):
    assert np.max(np.abs(np.asarray(g - w))) <= 1e-5 * np.max(np.abs(w))


def test_interpret_follows_the_platform():
  """interpret=None: the kernels interpreted off the TPU, the same values."""
  x, kernel, b, _ = _operands(64, 4, True, batch=1)
  np.testing.assert_array_equal(
      np.asarray(sc.causal_conv_silu(x, kernel, b, START)),
      np.asarray(_op(x, kernel, b)))
