"""The chunked Mamba-2 scan against the token-by-token recurrence: outputs,
the last state and every gradient, in float32, at chunk sizes that do and do
not divide the length and with several heads sharing a group's B and C.

Tolerances: both forms are float32 sums of the same terms in another order
(CPU products are float32 whatever the precision asked for), so they agree
to some float32 ulps of the largest entry: 2e-5 of it for the values (a
state decays, so the largest entry bounds what any sum held), 2e-4 for the
gradients, whose sums run over the whole length twice."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.ops import state_space as ss


def _inputs(seed=0, b=2, t=48, h=6, p=8, g=2, n=16):
  keys = jax.random.split(jax.random.PRNGKey(seed), 6)
  x = jax.random.normal(keys[0], (b, t, h, p))
  dt = jax.nn.softplus(jax.random.normal(keys[1], (b, t, h)) - 1.0)
  a_log = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32))
  bb = jax.random.normal(keys[2], (b, t, g, n))
  cc = jax.random.normal(keys[3], (b, t, g, n))
  d = jax.random.normal(keys[4], (h,))
  return x, dt, a_log, bb, cc, d


def _close(got, want, share, name=""):
  scale = float(jnp.max(jnp.abs(want)))
  np.testing.assert_allclose(got, want, atol=share * scale, err_msg=name)


@pytest.mark.parametrize("chunk", [16, 8, 128, 20, 7])
@pytest.mark.parametrize("groups", [1, 2, 6])   # H / G = 6, 3, 1
def test_chunked_scan_matches_the_recurrence(chunk, groups):
  args = _inputs(g=groups)
  y_ref, s_ref = ss.ssd_recurrent(*args)
  y, s = ss.ssd_chunked(*args, chunk_size=chunk)
  assert y.shape == y_ref.shape == (2, 48, 6, 8)
  assert s.shape == s_ref.shape == (2, 6, 8, 16)
  _close(y, y_ref, 2e-5)
  _close(s, s_ref, 2e-5)


@pytest.mark.parametrize("chunk,groups", [(16, 2), (20, 2), (128, 3),
                                          (32, 6)])
def test_chunked_scan_gradients_match_the_recurrence(chunk, groups):
  # Two chunks at least: 128, the size the benchmark's cell runs, at T 256.
  args = _inputs(seed=3, t=max(48, 2 * chunk), g=groups)
  probe = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

  def loss(fn, *xs):
    y, s = fn(*xs)
    return jnp.sum(y * probe) + jnp.sum(s * s)

  every = (0, 1, 2, 3, 4, 5)
  ref = jax.grad(lambda *xs: loss(ss.ssd_recurrent, *xs), argnums=every)(
      *args)
  got = jax.grad(lambda *xs: loss(
      lambda *ys: ss.ssd_chunked(*ys, chunk_size=chunk), *xs),
                 argnums=every)(*args)
  for name, a, b in zip("x dt a_log b c d".split(), got, ref):
    _close(a, b, 2e-4, name)


def test_the_scan_is_the_lines_of_its_docstring():
  x, dt, a_log, b, c, d = _inputs(seed=5, b=1, t=5, h=2, p=3, g=1, n=4)
  y, last = ss.ssd_recurrent(x, dt, a_log, b, c, d)
  for head in range(2):
    state = np.zeros((3, 4))
    for t in range(5):
      step = float(dt[0, t, head])
      a = np.exp(-np.exp(float(a_log[head])) * step)
      state = a * state + step * np.outer(np.asarray(x)[0, t, head],
                                          np.asarray(b)[0, t, 0])
      want = state @ np.asarray(c)[0, t, 0] + float(d[head]) * np.asarray(
          x)[0, t, head]
      np.testing.assert_allclose(y[0, t, head], want, atol=1e-5)
    np.testing.assert_allclose(last[0, head], state, atol=1e-5)


def test_a_head_reads_its_own_groups_b_and_c():
  """Head h reads group h // (H / G): swapping the two groups' B and C and
  the two halves of the heads gives the same result, heads swapped."""
  x, dt, a_log, b, c, d = _inputs(seed=7, h=4, g=2)
  a_log = jnp.zeros((4,))   # one decay for every head, so heads can swap
  d = jnp.ones((4,))
  y, _ = ss.ssd_chunked(x, dt, a_log, b, c, d, chunk_size=16)
  swap = lambda v: jnp.concatenate(  # noqa: E731
      [v[:, :, 2:], v[:, :, :2]], axis=2)
  y_swapped, _ = ss.ssd_chunked(swap(x), swap(dt), a_log, b[:, :, ::-1],
                                c[:, :, ::-1], d, chunk_size=16)
  np.testing.assert_allclose(swap(y_swapped), y, atol=1e-5)
  # and the groups differ: the swap of B and C alone changes the result
  y_other, _ = ss.ssd_chunked(x, dt, a_log, b[:, :, ::-1], c[:, :, ::-1], d,
                              chunk_size=16)
  assert float(jnp.max(jnp.abs(y_other - y))) > 0.1


def test_padding_tokens_write_nothing_and_decay_nothing():
  args = _inputs(seed=11, t=40)
  _, state = ss.ssd_chunked(*args, chunk_size=16)    # 8 tokens of padding
  _, want = ss.ssd_recurrent(*args)
  _close(state, want, 2e-5)


def test_bfloat16_operands_round_the_products_only():
  """`matmul_dtype` rounds what the products read; decays, dt and the state
  stay float32, so the result stays within bfloat16's rounding of the
  float32 one (8 bits of mantissa, some hundred terms a sum: 3e-2 of the
  largest entry) and is not equal to it."""
  args = _inputs(seed=13, t=64)
  y32, s32 = ss.ssd_chunked(*args, chunk_size=16)
  y16, s16 = ss.ssd_chunked(*args, chunk_size=16, matmul_dtype=jnp.bfloat16)
  assert y16.dtype == jnp.float32 and s16.dtype == jnp.float32
  _close(y16, y32, 3e-2)
  _close(s16, s32, 3e-2)
  assert float(jnp.max(jnp.abs(y16 - y32))) > 1e-4
