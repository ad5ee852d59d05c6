"""The Mamba-2 state-space scan: a scalar decay a head, token by token and
in chunks.

Per head h of size P, with a step dt_t > 0, A = -exp(a_log_h) < 0, and
vectors B_t, C_t of the state size N that the H / G heads of a group
share, the state S in R^{P x N} starts at 0 and follows

    a_t = exp(A dt_t)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

(Mamba-2, Dao & Gu 2024, arXiv:2405.21060; the public `nemotron_h`
modelling code's `torch_forward`). There is no correction of the state by
what it already holds, so no triangular system: that is what sets it apart
from `ops/linear_attention.py`'s gated delta rule.

`ssd_recurrent` is those lines under `lax.scan`: what the tests hold the
chunked form to. `ssd_chunked` is the training path, the source's
"state-space duality". Inside a chunk of C tokens, with the cumulative log
decay c_i = sum_{k <= i} A dt_k and L_ij = exp(c_i - c_j) for i >= j,

    Y_diag = ((C B^T) * L) (dt x)            the chunk's own writes, read
    S_out  = exp(c_last) S_in + sum_j exp(c_last - c_j) dt_j x_j B_j^T
    Y_off  = exp(c_i) (C_i . S_in)           what came before the chunk

Every term but the recurrence over S_in is computed for all chunks at
once; the scan over the T / C chunks carries one [P, N] state a head and
does one multiply-add a step, and Y_off is then one product over all the
states it left. B and C stay at their G groups: C B^T is computed once a
group, and a group's R = H / G heads meet B and C as one [R x P, N]
operand. State, decays, dt and every sum are float32; the products take
`matmul_dtype` operands and accumulate in float32. All of it is XLA's; the
backward pass is autodiff's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssd_recurrent", "ssd_chunked"]

_HIGHEST = jax.lax.Precision.HIGHEST


def ssd_recurrent(x, dt, a_log, b, c, d):
  """The scan, one token at a time. x [B, T, H, P], dt [B, T, H] (after its
  softplus), a_log and d [H], b and c [B, T, G, N] with G dividing H; head h
  reads group h // (H / G). Returns (y [B, T, H, P], the last state
  [B, H, P, N]), float32."""
  x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
  batch, _, heads, p = x.shape
  repeat = heads // b.shape[2]
  b, c = (jnp.repeat(v, repeat, axis=2) for v in (b, c))
  decay = jnp.exp(-jnp.exp(a_log.astype(jnp.float32)) * dt)

  def step(state, inputs):
    x_t, dt_t, a_t, b_t, c_t = inputs
    write = (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
    state = a_t[..., None, None] * state + write
    return state, jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=_HIGHEST)

  state0 = jnp.zeros((batch, heads, p, b.shape[-1]), jnp.float32)
  time_major = [jnp.moveaxis(v, 1, 0) for v in (x, dt, decay, b, c)]
  state, y = jax.lax.scan(step, state0, time_major)
  return jnp.moveaxis(y, 0, 1) + d.astype(jnp.float32)[:, None] * x, state


def ssd_chunked(x, dt, a_log, b, c, d, chunk_size: int = 128,
                matmul_dtype=None):
  """The same scan in chunks of `chunk_size` tokens; shapes and results as
  `ssd_recurrent`. A length that the chunk does not divide is padded with
  tokens that write nothing and decay nothing (dt 0). `matmul_dtype`
  (bfloat16 on the training path) is the type the products' operands are
  held in; they accumulate in float32."""
  x, dt, b, c = (v.astype(jnp.float32) for v in (x, dt, b, c))
  batch, t, heads, p = x.shape
  groups, state_size = b.shape[2], b.shape[3]
  r = heads // groups
  size = int(chunk_size)
  n = -(-t // size)
  if n * size != t:
    pad = lambda v: jnp.pad(  # noqa: E731
        v, ((0, 0), (0, n * size - t)) + ((0, 0),) * (v.ndim - 2))
    x, dt, b, c = (pad(v) for v in (x, dt, b, c))
  operand = (lambda v: v) if matmul_dtype is None else (
      lambda v: v.astype(matmul_dtype))

  def product(subscripts, lhs, rhs):
    return jnp.einsum(subscripts, operand(lhs), operand(rhs),
                      preferred_element_type=jnp.float32)

  def chunks(v, split):  # [B, N x C, split..., ...] -> [N, B, split..., C, ...]
    v = v.reshape((batch, n, size) + split + v.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(v, 1, 0), 2, 2 + len(split))

  skip = d.astype(jnp.float32).reshape(groups, r)[:, :, None, None]
  x = chunks(x, (groups, r))                     # [N, B, G, R, C, P]
  dt = chunks(dt, (groups, r))                   # [N, B, G, R, C]
  b, c = chunks(b, (groups,)), chunks(c, (groups,))   # [N, B, G, C, S]
  log_decay = -jnp.exp(a_log.astype(jnp.float32)).reshape(
      groups, r)[:, :, None] * dt
  cum = jnp.cumsum(log_decay, axis=-1)           # c_i, [N, B, G, R, C]
  rows = jnp.arange(size)
  lower = rows[:, None] >= rows[None, :]
  # exp(c_i - c_j) for i >= j; masked before the exp so that nothing
  # overflows above the diagonal.
  within = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                             -jnp.inf))
  written = x * dt[..., None]                    # dt x
  scores = product("nbgis,nbgjs->nbgij", c, b)[:, :, :, None] * within
  y = product("nbgrij,nbgrjp->nbgrip", scores, written)
  last = cum[..., -1]                            # [N, B, G, R]
  to_end = jnp.exp(last[..., None] - cum)
  chunk_states = product("nbgrjp,nbgjs->nbgrps", written * to_end[..., None],
                         b)

  def step(state, inputs):
    decay_i, state_i = inputs
    return state * decay_i[..., None, None] + state_i, state

  state0 = jnp.zeros((batch, groups, r, p, state_size), jnp.float32)
  state, entering = jax.lax.scan(step, state0, (jnp.exp(last), chunk_states))
  y = y + product("nbgis,nbgrps->nbgrip", c, entering) * jnp.exp(
      cum)[..., None]
  y = y + skip * x
  y = jnp.moveaxis(jnp.moveaxis(y, 4, 2), 0, 1)  # [B, N, C, G, R, P]
  return (y.reshape(batch, n * size, heads, p)[:, :t],
          state.reshape(batch, heads, p, state_size))
