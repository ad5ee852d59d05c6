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
