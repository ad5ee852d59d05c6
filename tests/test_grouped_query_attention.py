"""Grouped-query heads in `ops/attention.flash_attention`: k and v at H_kv
heads, H_kv dividing H, query head h reading key/value head h // (H / H_kv).

The interpreted kernels are held to the causal / band reference with k and v
repeated to the query heads here, outputs and all three gradients, at the
bounds the window's kernels meet in `test_window_attention.py`. Where a
program holds one head (D 128, D 256) the kernels take k and v at their own
width and return dK and dV there; at heads of 64 `flash_attention` repeats
them ahead of the kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.ops import attention as attn


def _reference(q, k, v, h, window):
  b, t, hd = q.shape
  d = hd // h
  group = h // (k.shape[-1] // d)
  heads = lambda x: x.reshape(b, t, -1, d).transpose(0, 2, 1, 3)  # noqa
  out = attn.attention(heads(q), jnp.repeat(heads(k), group, axis=1),
                       jnp.repeat(heads(v), group, axis=1), causal=True,
                       window=window)
  return out.transpose(0, 2, 1, 3).reshape(b, t, hd)


def _kernel_kv_widths(jaxpr):
  """(name, k's width, v's width) of every pallas_call in `jaxpr`."""
  out = []

  def walk(jaxpr):
    for eqn in jaxpr.eqns:
      if eqn.primitive.name == "pallas_call":
        out.append((eqn.params["name"],) + tuple(
            x.aval.shape[-1] for x in eqn.invars[1:3]))
      for value in eqn.params.values():
        inner = getattr(value, "jaxpr", value)
        if hasattr(inner, "eqns"):
          walk(inner)

  walk(jaxpr)
  return out


# (T, heads, key/value heads, head_dim, window, block_q, block_k): groups of
# 2, 6 and 8, causal and windowed, a T that pads (40 -> 48, and a window no
# block divides), heads of 128 and 256 (one a program: the grouped kernels),
# H_kv == H, and heads of 64 (two a program: k and v repeated ahead).
CASES = [
    (32, 4, 2, 128, None, 16, 32),
    (40, 6, 1, 128, 9, 16, 16),
    (32, 8, 1, 128, 8, 16, 16),
    (32, 8, 1, 256, None, 16, 16),
    (32, 2, 2, 128, 8, 16, 16),
    (32, 4, 2, 64, None, 16, 16),
]


@pytest.mark.parametrize("t,h,h_kv,d,window,bq,bk", CASES)
def test_grouped_heads_match_the_repeated_reference(t, h, h_kv, d, window,
                                                    bq, bk):
  keys = jax.random.split(jax.random.PRNGKey(t + h + d), 4)
  q, probe = (jax.random.normal(key, (1, t, h * d)) for key in keys[:2])
  k, v = (jax.random.normal(key, (1, t, h_kv * d)) for key in keys[2:])
  flash = lambda q, k, v: attn.flash_attention(  # noqa: E731
      q, k, v, h, causal=True, window=window, block_q=bq, block_k=bk,
      interpret=True)
  ref = lambda q, k, v: _reference(q, k, v, h, window)  # noqa: E731

  def out_and_grads(f):
    def run(q, k, v):
      out, pull = jax.vjp(f, q, k, v)
      return out, pull(probe)
    return run

  traced = jax.jit(out_and_grads(flash)).trace(q, k, v)
  got, want = traced.lower().compile()(q, k, v), out_and_grads(ref)(q, k, v)
  np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                             atol=2e-5, rtol=2e-5)
  for got_grad, want_grad in zip(got[1], want[1]):
    assert got_grad.shape == want_grad.shape
    np.testing.assert_allclose(np.asarray(got_grad), np.asarray(want_grad),
                               atol=5e-5, rtol=5e-4)
  # Which kernels ran, and at what width k and v reached them.
  width = (h_kv if d % 128 == 0 else h) * d
  fwd, bwd = (("flash_fwd", "flash_bwd") if window is None
              else ("flash_window_fwd", "flash_window_bwd"))
  assert _kernel_kv_widths(traced.jaxpr.jaxpr) == [
      (fwd, width, width), (bwd, width, width)]


def test_key_value_heads_must_divide_the_query_heads():
  q = jnp.zeros((1, 16, 6 * 128))
  for kv_width in (4 * 128, 100):
    kv = jnp.zeros((1, 16, kv_width))
    with pytest.raises(ValueError):
      attn.flash_attention(q, kv, kv, 6, causal=True, interpret=True)
