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


@pytest.mark.parametrize("chunk", [16, 20])
def test_chunked_rule_gradients_match_the_recurrence(chunk):
  args = _inputs(seed=3)
  probe = jax.random.normal(jax.random.PRNGKey(9), (2, 48, 3, 8))

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
