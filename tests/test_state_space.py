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


def _mixed(seed, b, t, h, p, g, n, dtype):
  """The mixer's convolution output [B, T, H P + 2 G N] (x, B, C one after
  another), dt, a_log and D."""
  keys = jax.random.split(jax.random.PRNGKey(seed), 4)
  mixed = jax.random.normal(keys[0], (b, t, h * p + 2 * g * n)).astype(dtype)
  dt = jax.nn.softplus(jax.random.normal(keys[1], (b, t, h)) - 2.0)
  a_log = jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)) - 2.0
  return mixed, dt, a_log, jax.random.normal(keys[2], (h,))


def _xla_form(groups, n, matmul_dtype, chunk=128):
  """`ssd_chunked` on slices of the mixer's operand: the kernels'
  yardstick."""
  def scan(mixed, dt, a_log, d):
    b, t, h = dt.shape
    x_end = mixed.shape[2] - 2 * groups * n
    y, last = ss.ssd_chunked(
        mixed[..., :x_end].reshape(b, t, h, -1), dt, a_log,
        mixed[..., x_end:x_end + groups * n].reshape(b, t, groups, n),
        mixed[..., x_end + groups * n:].reshape(b, t, groups, n), d,
        chunk_size=chunk, matmul_dtype=matmul_dtype)
    return y.reshape(b, t, -1), last
  return scan


def _value_and_cotangents(scan, args, seed):
  """(y, last state) and the cotangents of `args` for random ones of
  those, in one jitted program."""
  def run(*xs):
    out, vjp = jax.vjp(scan, *xs)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return out + vjp(tuple(jax.random.normal(k, v.shape)
                           for k, v in zip(keys, out)))
  return jax.jit(run)(*args)


# Tolerances, relative to the largest entry. float32 operands: the same
# terms summed in another order, some float32 ulps (2e-5); a state or a dS
# held in bfloat16 (2^-9 of each entry) fails every one of them. bfloat16
# operands, as the training path holds them: both sides round the same
# products' operands, but one computed in float32 in another order (dt x,
# its decays) can round the other way and move its term by 2^-8 of itself:
# 2e-3 for y and the last state (4e-4 and 7e-5 read at most); D's cotangent
# takes no product (2e-5); the other cotangents 1e-2: the cotangent of the
# convolution's result leaves in bfloat16 (one rounding, up to 2^-7 of the
# largest entry), and the CPU's autodiff keeps float32 cotangents as
# operands of its transposed products where the kernels round them, as the
# chip's default precision does.
_FLOAT32_TOL = 2e-5
_BFLOAT16_TOL = {"y": 2e-3, "state": 2e-3, "d": 2e-5}


@pytest.mark.parametrize("b,t,h,groups,operand", [
    (1, 256, 2, 1, "float32"),      # one group, two chunks
    (2, 512, 4, 2, "float32"),      # two groups, four chunks, two sequences
    (1, 512, 2, 1, "bfloat16"),
    (1, 256, 4, 2, "bfloat16"),
])
def test_kernels_match_autodiff_of_the_xla_form(b, t, h, groups, operand):
  """`ssd_scan`'s kernels, interpreted, against `ssd_chunked` and its
  autodiff: y, the last state, and the cotangents of x, B, C (one operand),
  dt, a_log and D. Heads of 64, two to a 128-lane block, N 128."""
  p, n = 64, 128
  dtype = jnp.dtype(operand)
  matmul_dtype = None if operand == "float32" else jnp.bfloat16
  args = _mixed(17, b, t, h, p, groups, n, dtype)
  got = _value_and_cotangents(lambda *a: ss.ssd_scan(
      *a, groups, n, matmul_dtype=matmul_dtype, interpret=True), args, 3)
  want = _value_and_cotangents(_xla_form(groups, n, matmul_dtype), args, 3)
  assert [v.dtype for v in got] == [v.dtype for v in want]
  x_end, b_end = h * p, h * p + groups * n
  names = dict(y=0, state=1, dt=3, a_log=4, d=5)
  parts = {k: (got[i], want[i]) for k, i in names.items()}
  for name, cols in (("x", slice(0, x_end)), ("b", slice(x_end, b_end)),
                     ("c", slice(b_end, None))):
    parts[name] = (got[2][..., cols].astype(jnp.float32),
                   want[2][..., cols].astype(jnp.float32))
  for name, (a, w) in parts.items():
    share = _FLOAT32_TOL if operand == "float32" else _BFLOAT16_TOL.get(
        name, 1e-2)
    _close(a, w, share, name)


def test_a_value_is_laid_over_its_heads_lanes_to_the_bit():
  """`_lay`: the three bfloat16 parts of a float32 value, against a 0/1
  matrix, give the value itself, over decays from 1e-30 to 1e30 and their
  logarithms of either sign."""
  r, size, p = 8, 128, 64
  keys = jax.random.split(jax.random.PRNGKey(23), 2)
  v_t = (jax.random.normal(keys[0], (r, size))
         * 10.0 ** jax.random.randint(keys[1], (r, size), -30, 31))
  head = jnp.arange(r)[:, None]
  whose = jnp.arange(r * p)[None, :] // p == head
  got = ss._lay(ss._parts(v_t), whose)
  np.testing.assert_array_equal(got, jnp.repeat(v_t.T, p, axis=1))
  column = ss._lay(ss._parts(v_t), jnp.broadcast_to(head == 3, (r, size)))
  np.testing.assert_array_equal(column, jnp.broadcast_to(v_t[3][:, None],
                                                         (size, size)))


def test_other_shapes_take_the_xla_form():
  """A state of 16 and chunks of 32 (the rehearsal's sizes) are no kernel's
  shape: the op is `ssd_chunked` on the operand's slices, to the bit, and
  no Pallas call is traced."""
  args = _mixed(19, 1, 96, 4, 16, 2, 16, jnp.float32)
  op = lambda *a: ss.ssd_scan(*a, 2, 16, chunk_size=32)  # noqa: E731
  assert not ss._kernel_takes(96, 4, 16, 2, 16, 32)
  assert "pallas_call" not in str(jax.make_jaxpr(op)(*args))
  for a, w in zip(_value_and_cotangents(op, args, 5), _value_and_cotangents(
      _xla_form(2, 16, None, chunk=32), args, 5)):
    np.testing.assert_array_equal(a, w)


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
