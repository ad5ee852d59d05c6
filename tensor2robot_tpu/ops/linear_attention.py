"""The gated delta rule: linear attention with a decaying, error-correcting
state, token by token and in chunks.

Per head, with keys and queries of size d_k, values of size d_v, a log
decay g_t <= 0 and a write strength beta_t in (0, 1), the state S in
R^{d_k x d_v} starts at 0 and follows

    S'_t = exp(g_t) S_{t-1}
    u_t  = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t u_t^T
    o_t  = S_t^T q_t

(Gated DeltaNet, Yang et al. 2024, arXiv:2412.06464; the public
`qwen3_next` modelling code's `torch_recurrent_gated_delta_rule` and
`torch_chunk_gated_delta_rule`). Keys and queries are L2-normalised over
d_k here and the query scaled by d_k^-0.5, as the source does inside its
kernel.

`gated_delta_rule_recurrent` is those four lines under `lax.scan`: what
the tests hold the chunked form to. `gated_delta_rule_chunked` is the
training path. Inside a chunk of C tokens the cumulative gate G_i and the
strictly lower-triangular A_ij = beta_i (k_i . k_j) exp(G_i - G_j) give
the chunk's writes in closed form through (I + A)^-1; only one
[d_k, d_v] state travels from chunk to chunk, under `lax.scan` over the
T / C chunks, with the backward pass by autodiff. Everything that does
not need the travelling state (A, its inverse, the in-chunk scores) is
computed for all chunks at once, outside the scan.

(I + A)^-1: A is nilpotent (A^C = 0), so with N = -A the inverse is the
finite product (I + N)(I + N^2)(I + N^4)... of log2(C) factors: ten
[C, C] products at C 64, at `highest` precision, where the source runs C
sequential row updates. The state, the gates and every sum are float32;
the other products take the backend's default precision, which on the
TPU feeds the MXU bfloat16 operands and accumulates in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["gated_delta_rule_recurrent", "gated_delta_rule_chunked"]

_L2_EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST


def _normalised(q, k):
  """q, k [..., d_k] in float32: both of unit length, q scaled by
  d_k^-0.5."""
  def unit(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)

  return unit(q) * (q.shape[-1] ** -0.5), unit(k)


def gated_delta_rule_recurrent(q, k, v, g, beta):
  """The rule, one token at a time. q, k [B, T, H, d_k], v [B, T, H, d_v],
  g and beta [B, T, H]; returns (o [B, T, H, d_v], the last state
  [B, H, d_k, d_v]), float32."""
  q, k = _normalised(q, k)
  v, g, beta = (x.astype(jnp.float32) for x in (v, g, beta))
  b, _, h, d_k = q.shape

  def step(state, inputs):
    q_t, k_t, v_t, g_t, beta_t = inputs
    state = state * jnp.exp(g_t)[..., None, None]
    read = jnp.einsum("bhk,bhkv->bhv", k_t, state, precision=_HIGHEST)
    u_t = beta_t[..., None] * (v_t - read)
    state = state + k_t[..., :, None] * u_t[..., None, :]
    return state, jnp.einsum("bhk,bhkv->bhv", q_t, state, precision=_HIGHEST)

  state0 = jnp.zeros((b, h, d_k, v.shape[-1]), jnp.float32)
  time_major = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)]
  state, o = jax.lax.scan(step, state0, time_major)
  return jnp.moveaxis(o, 0, 1), state


def _inverse_of_unit_lower(a):
  """(I + a)^-1 for strictly lower-triangular a [..., C, C]."""
  c = a.shape[-1]
  eye = jnp.eye(c, dtype=a.dtype)
  power = -a
  inverse = eye + power
  covered = 2  # `inverse` holds the sum of N^0 .. N^(covered - 1)
  while covered < c:
    power = jnp.matmul(power, power, precision=_HIGHEST)
    inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
    covered *= 2
  return inverse


def gated_delta_rule_chunked(q, k, v, g, beta, chunk_size: int = 64,
                             matmul_dtype=None):
  """The same rule in chunks of `chunk_size` tokens; shapes and results as
  `gated_delta_rule_recurrent`. A length that the chunk does not divide is
  padded with tokens that write nothing (beta 0, g 0). `matmul_dtype`
  (bfloat16 on the training path) is the type the products' operands are
  held in; they accumulate in float32, and the inverse stays float32."""
  q, k = _normalised(q, k)
  v, g, beta = (x.astype(jnp.float32) for x in (v, g, beta))
  b, t, h, d_k = q.shape
  d_v = v.shape[-1]
  c = int(chunk_size)
  n = -(-t // c)
  if n * c != t:
    pad = lambda x: jnp.pad(  # noqa: E731
        x, ((0, 0), (0, n * c - t)) + ((0, 0),) * (x.ndim - 2))
    q, k, v, g, beta = (pad(x) for x in (q, k, v, g, beta))
  operand = (lambda x: x) if matmul_dtype is None else (
      lambda x: x.astype(matmul_dtype))

  def product(subscripts, x, y):
    return jnp.einsum(subscripts, operand(x), operand(y),
                      preferred_element_type=jnp.float32)

  def chunks(x):  # [B, N x C, H, ...] -> [N, B, H, C, ...]
    x = x.reshape((b, n, c, h) + x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

  q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
  gate = jnp.cumsum(g, axis=-1)                         # G_i, [N, B, H, C]
  rows = jnp.arange(c)
  lower = rows[:, None] >= rows[None, :]
  # exp(G_i - G_j) for i >= j; masked before the exp so that nothing
  # overflows above the diagonal.
  decay = jnp.exp(jnp.where(lower, gate[..., :, None] - gate[..., None, :],
                            -jnp.inf))
  k_beta = k * beta[..., None]
  a = product("...ik,...jk->...ij", k_beta, k) * decay
  a = jnp.where(rows[:, None] > rows[None, :], a, 0.0)
  inverse = _inverse_of_unit_lower(a)
  # The chunk's writes before the incoming state corrects them, and what
  # the incoming state has to be read with for that correction.
  writes = product("...ij,...jv->...iv", inverse, v * beta[..., None])
  reads = operand(product("...ij,...jk->...ik", inverse,
                          k_beta * jnp.exp(gate)[..., None]))
  scores = operand(product("...ik,...jk->...ij", q, k) * decay)  # i >= j
  q_in = operand(q * jnp.exp(gate)[..., None])
  gate_last = gate[..., -1]                                    # [N, B, H]
  k_out = operand(k * jnp.exp(gate_last[..., None] - gate)[..., None])

  def step(state, inputs):
    writes_i, reads_i, scores_i, q_i, k_i, last_i = inputs
    u_i = writes_i - product("...ck,...kv->...cv", reads_i, state)
    o_i = product("...ck,...kv->...cv", q_i, state) + product(
        "...ij,...jv->...iv", scores_i, u_i)
    state = state * jnp.exp(last_i)[..., None, None] + product(
        "...ck,...cv->...kv", k_i, u_i)
    return state, o_i

  state0 = jnp.zeros((b, h, d_k, d_v), jnp.float32)
  state, o = jax.lax.scan(
      step, state0, (writes, reads, scores, q_in, k_out, gate_last))
  o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)  # [B, N, C, H, d_v]
  return o.reshape(b, n * c, h, d_v)[:, :t], state
