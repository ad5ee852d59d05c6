"""The chunked gated delta rule against the token-by-token recurrence:
outputs, the last state and every gradient, in float32, at chunk sizes
that do and do not divide the length."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.ops import linear_attention as la


def _inputs(seed=0, b=2, t=48, h=3, d_k=16, d_v=8):
  keys = jax.random.split(jax.random.PRNGKey(seed), 5)
  q = jax.random.normal(keys[0], (b, t, h, d_k))
  k = jax.random.normal(keys[1], (b, t, h, d_k))
  v = jax.random.normal(keys[2], (b, t, h, d_v))
  g = -jax.nn.softplus(jax.random.normal(keys[3], (b, t, h))) * 0.5
  beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
  return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [16, 8, 64, 20, 7])
def test_chunked_rule_matches_the_recurrence(chunk):
  args = _inputs()
  o_ref, s_ref = la.gated_delta_rule_recurrent(*args)
  o, s = la.gated_delta_rule_chunked(*args, chunk_size=chunk)
  assert o.shape == o_ref.shape == (2, 48, 3, 8)
  np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)
  np.testing.assert_allclose(s, s_ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("chunk", [16, 20, 64])
def test_chunked_rule_gradients_match_the_recurrence(chunk):
  # Two chunks at least: 64, the one size the benchmark's cell runs, at T 128.
  args = _inputs(seed=3, t=max(48, 2 * chunk))
  probe = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

  def loss(fn, *xs):
    o, s = fn(*xs)
    return jnp.sum(o * probe) + jnp.sum(s * s)

  ref = jax.grad(lambda *xs: loss(la.gated_delta_rule_recurrent, *xs),
                 argnums=(0, 1, 2, 3, 4))(*args)
  got = jax.grad(
      lambda *xs: loss(lambda *ys: la.gated_delta_rule_chunked(
          *ys, chunk_size=chunk), *xs), argnums=(0, 1, 2, 3, 4))(*args)
  for name, a, b in zip("q k v g beta".split(), got, ref):
    scale = float(jnp.max(jnp.abs(b)))
    np.testing.assert_allclose(a, b, atol=2e-4 * scale, err_msg=name)


def test_the_rule_is_the_four_lines_of_its_docstring():
  q, k, v, g, beta = _inputs(seed=5, b=1, t=5, h=1, d_k=4, d_v=3)
  o, _ = la.gated_delta_rule_recurrent(q, k, v, g, beta)
  unit = lambda x: x / np.sqrt(np.sum(x * x, -1, keepdims=True) + 1e-6)
  qn = unit(np.asarray(q))[0, :, 0] * 4 ** -0.5
  kn = unit(np.asarray(k))[0, :, 0]
  state = np.zeros((4, 3))
  for t in range(5):
    state = np.exp(float(g[0, t, 0])) * state
    u = float(beta[0, t, 0]) * (np.asarray(v)[0, t, 0] - state.T @ kn[t])
    state = state + np.outer(kn[t], u)
    np.testing.assert_allclose(o[0, t, 0], state.T @ qn[t], atol=1e-5)


def test_inverse_of_unit_lower():
  a = np.tril(np.random.default_rng(0).normal(size=(2, 24, 24)), -1)
  got = la._inverse_of_unit_lower(jnp.asarray(a, jnp.float32))
  np.testing.assert_allclose(got, np.linalg.inv(np.eye(24) + a), atol=1e-3,
                             rtol=1e-3)


def _unit_lower(c, lead=(2, 1, 4), seed=0):
  a = np.random.default_rng(seed).normal(size=lead + (c, c)) * 0.25
  return jnp.asarray(np.tril(a, -1), jnp.float32)


@pytest.mark.parametrize("c", [8, 16, 64])
def test_inverse_kernel_is_the_doubling_product(c):
  """`gdn_inverse`, interpreted, on the chunked layout [N, B, H, C, C]:
  the XLA product it replaces, and the inverse itself."""
  a = _unit_lower(c)
  assert la._kernel_takes(a.shape)
  got = la._inverse_of_unit_lower(a, interpret=True)
  np.testing.assert_allclose(got, la._doubling_inverse(a), atol=1e-5,
                             rtol=1e-5)
  exact = np.linalg.inv(np.eye(c) + np.asarray(a, np.float64))
  np.testing.assert_allclose(got, exact, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shape", [(2, 1, 4, 7, 7), (2, 1, 4, 20, 20),
                                   (3, 16, 16)])
def test_shapes_the_kernel_leaves_to_xla(shape):
  a = _unit_lower(shape[-1], lead=shape[:-2])
  assert not la._kernel_takes(a.shape)
  exact = np.linalg.inv(np.eye(shape[-1]) + np.asarray(a, np.float64))
  np.testing.assert_allclose(la._inverse_of_unit_lower(a), exact, atol=1e-5,
                             rtol=1e-5)


@pytest.mark.parametrize("c", [16, 64])
def test_closed_form_backward_is_the_doubling_products_gradient(c):
  a = _unit_lower(c, seed=1)
  probe = jnp.asarray(np.random.default_rng(2).normal(size=a.shape),
                      jnp.float32)
  # On the operand's strict lower triangle, as the rule calls it.
  strictly_lower = np.tril(np.ones((c, c), bool), -1)
  loss = lambda fn: lambda x: jnp.sum(  # noqa: E731
      fn(jnp.where(strictly_lower, x, 0.0)) * probe)
  want = jax.grad(loss(la._doubling_inverse))(a)
  got = jax.grad(loss(lambda x: la._inverse_of_unit_lower(x, True)))(a)
  np.testing.assert_allclose(got, want,
                             atol=1e-5 * float(jnp.max(jnp.abs(want))))


def _count(jaxpr, primitive):
  """Equations of `primitive` in a jaxpr and everything it calls."""
  total = 0
  for eqn in jaxpr.eqns:
    total += eqn.primitive.name == primitive
    for sub in jax.core.jaxprs_in_params(eqn.params):
      total += _count(sub, primitive)
  return total


@pytest.mark.parametrize("fn,products,residuals", [
    (la._doubling_inverse, 20, 11),  # autodiff through the ten products
    (lambda a: la._inverse_of_unit_lower(a, True), 2, 1),
    (lambda a: la._inverse_of_unit_lower(a[0, 0]), 2, 1),  # XLA's forward
], ids=["autodiff", "kernel", "xla_forward"])
def test_backward_of_the_inverse_holds_two_products_and_one_residual(
    fn, products, residuals):
  a = _unit_lower(64)
  out, backward = jax.vjp(fn, a)
  # Distinct by value: autodiff holds some of its eleven twice, one of
  # the two with the batch of 1 squeezed out.
  held = {np.asarray(x).tobytes()
          for x in jax.tree_util.tree_leaves(backward)
          if getattr(x, "shape", ())[-2:] == (64, 64)}
  assert len(held) == residuals
  assert _count(jax.make_jaxpr(backward)(out).jaxpr,
                "dot_general") == products


def _mixed(seed, b, t, k_heads, v_heads, d, dtype):
  """The convolution's result [B, T, 2 H_k d + H_v d] (q, k, v one after
  another) and the decoder's g and beta."""
  keys = jax.random.split(jax.random.PRNGKey(seed), 3)
  mixed = jax.random.normal(
      keys[0], (b, t, (2 * k_heads + v_heads) * d)).astype(dtype)
  g = -jax.nn.softplus(jax.random.normal(keys[1], (b, t, v_heads))) * 0.5
  beta = jax.nn.sigmoid(jax.random.normal(keys[2], (b, t, v_heads)))
  return mixed, g, beta


def _xla_form(k_heads, d, matmul_dtype, chunk=64):
  """`gated_delta_rule_chunked` on slices of `mixed`, the key heads
  repeated to the value heads, as the decoder called it before the op: the
  kernels' yardstick."""
  def rule(mixed, g, beta):
    b, t, _ = mixed.shape
    v_heads, key_dim = g.shape[-1], k_heads * d
    q, k = (jnp.repeat(mixed[..., i * key_dim:(i + 1) * key_dim].reshape(
        b, t, k_heads, d), v_heads // k_heads, axis=2) for i in (0, 1))
    v = mixed[..., 2 * key_dim:].reshape(b, t, v_heads, d)
    return la.gated_delta_rule_chunked(q, k, v, g, beta, chunk_size=chunk,
                                       matmul_dtype=matmul_dtype)
  return rule


def _value_and_cotangents(rule, args, seed):
  """(o, last state) and the cotangents of `args` for random ones of
  those, in one jitted program."""
  def run(*xs):
    out, vjp = jax.vjp(rule, *xs)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return out + vjp(tuple(jax.random.normal(k, v.shape)
                           for k, v in zip(keys, out)))
  return jax.jit(run)(*args)


# Tolerances, relative to the largest entry. float32 operands: the same
# terms summed in another order (2e-5). bfloat16 operands, as the training
# path holds them: both sides round the same products' operands, o and the
# last state to 2e-3 (5e-4 read at most); the cotangents 1e-2: the
# cotangent of the convolution's result leaves in bfloat16 (one rounding,
# up to 2^-7 of the largest entry), and the CPU's autodiff keeps float32
# cotangents as operands of its transposed products where the kernels
# round them, as the chip's default precision does.
_FLOAT32_TOL = 2e-5
_BFLOAT16_TOL = {"o": 2e-3, "state": 2e-3}


@pytest.mark.parametrize("b,t,k_heads,v_heads,operand", [
    (1, 64, 1, 2, "float32"),      # one chunk, two value heads a key head
    (1, 192, 1, 2, "bfloat16"),    # three chunks
    (2, 128, 2, 2, "float32"),     # a key head a value head, two sequences
], ids=["one_chunk", "three_chunks_bf16", "two_sequences"])
def test_op_matches_autodiff_of_the_xla_form(b, t, k_heads, v_heads,
                                             operand):
  """`gated_delta_rule`'s kernels, interpreted, against
  `gated_delta_rule_chunked` and its autodiff: o, the last state, and the
  cotangents of the q, k and v columns of `mixed`, of g and of beta.
  Heads of 128, chunks of 64."""
  d = 128
  matmul_dtype = None if operand == "float32" else jnp.bfloat16
  args = _mixed(17, b, t, k_heads, v_heads, d, jnp.dtype(operand))
  got = _value_and_cotangents(lambda *a: la.gated_delta_rule(
      *a, k_heads, d, matmul_dtype=matmul_dtype, interpret=True), args, 3)
  want = _value_and_cotangents(_xla_form(k_heads, d, matmul_dtype), args, 3)
  assert [v.shape for v in got] == [v.shape for v in want]
  assert [v.dtype for v in got] == [v.dtype for v in want]
  parts = {"o": (got[0], want[0]), "state": (got[1], want[1]),
           "g": (got[3], want[3]), "beta": (got[4], want[4])}
  key_dim = k_heads * d
  for i, name in enumerate("qkv"):
    cols = slice(i * key_dim, (i + 1) * key_dim if i < 2 else None)
    parts[name] = (got[2][..., cols].astype(jnp.float32),
                   want[2][..., cols].astype(jnp.float32))
  for name, (a, w) in parts.items():
    share = _FLOAT32_TOL if operand == "float32" else _BFLOAT16_TOL.get(
        name, 1e-2)
    scale = float(jnp.max(jnp.abs(w)))
    np.testing.assert_allclose(a, w, atol=share * scale, err_msg=name)


@pytest.mark.parametrize("t,k_heads,v_heads,d_k,d_v,chunk,heads", [
    (4096, 16, 32, 128, 128, 64, 16),  # the hybrid cell: 2 groups of 16
    (128, 1, 2, 128, 128, 64, 2),      # every head in one program
    (128, 2, 4, 128, 128, 32, 4),
    (128, 2, 4, 16, 16, 64, None),     # heads of 16: no whole lane block
    (96, 1, 2, 128, 128, 64, None),    # the chunk does not divide T
    (128, 1, 2, 128, 128, 12, None),   # a chunk of no whole sublane tile
    (128, 2, 3, 128, 128, 64, None),   # value heads not in whole groups
])
def test_the_shapes_the_kernels_take(t, k_heads, v_heads, d_k, d_v, chunk,
                                     heads):
  assert la._scan_heads(t, k_heads, v_heads, d_k, d_v, chunk) == heads


def test_other_shapes_take_the_xla_form():
  """Heads of 16 (the rehearsal's sizes) are no kernel's shape: the op is
  `gated_delta_rule_chunked` on the operand's slices, to the bit, and no
  `gdn_scan` kernel is traced."""
  args = _mixed(19, 1, 128, 2, 4, 16, jnp.float32)
  op = lambda *a: la.gated_delta_rule(*a, 2, 16)  # noqa: E731
  assert "gdn_scan" not in str(jax.make_jaxpr(op)(*args))
  for a, w in zip(_value_and_cotangents(op, args, 5),
                  _value_and_cotangents(_xla_form(2, 16, None), args, 5)):
    np.testing.assert_array_equal(a, w)


def test_refined_inverse_is_the_inverse_to_fourteen_bits():
  """The kernels' (I + A)^-1: one bfloat16 pass of the doubling product and
  one Newton step from a three-pass residual is the inverse to 2^-14 of
  its largest entry on a harder A than the rule's (entries of 0.25, an
  inverse of 5), and to 2^-18 on the rule's own (unit keys, beta < 1,
  decays <= 1): under the bfloat16 rounding (2^-9) of the operand it
  becomes. The doubling product alone is not."""
  rng = np.random.default_rng(4)
  hard = np.tril(rng.normal(size=(8, 64, 64)) * 0.25, -1)
  k = rng.normal(size=(8, 64, 128))
  k /= np.linalg.norm(k, axis=-1, keepdims=True)
  beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(8, 64, 1))))
  gate = np.cumsum(-np.log1p(np.exp(rng.normal(size=(8, 64)) - 2.0)), -1)
  decay = np.exp(np.minimum(gate[:, :, None] - gate[:, None, :], 0.0))
  rule = np.tril(beta * np.einsum("hik,hjk->hij", k, k) * decay, -1)
  for a, bits in ((hard, 14), (rule, 18)):
    exact = np.linalg.inv(np.eye(64) + a)
    got = np.stack(la._refined_inverses(
        [jnp.asarray(x, jnp.float32) for x in a], 2))
    error = np.max(np.abs(got - exact)) / np.max(np.abs(exact))
    assert error < 2.0 ** -bits, (bits, error)
  power = -jnp.asarray(hard, jnp.bfloat16)
  one_pass = jnp.eye(64) + power.astype(jnp.float32)
  for _ in range(5):
    power = jnp.matmul(power, power, preferred_element_type=jnp.float32)
    one_pass = one_pass + jnp.matmul(one_pass.astype(jnp.bfloat16), power,
                                     preferred_element_type=jnp.float32)
    power = power.astype(jnp.bfloat16)
  exact = np.linalg.inv(np.eye(64) + hard)
  assert np.max(np.abs(one_pass - exact)) / np.max(np.abs(exact)) > 2.0 ** -12
