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
chunked form to. `ssd_chunked` is the source's "state-space duality" in
XLA. Inside a chunk of C tokens, with X_j = dt_j x_j, the cumulative log
decay c_i = sum_{k <= i} A dt_k and L_ij = exp(c_i - c_j) for i >= j,

    Y      = ((C B^T) * L) X + exp(c) * (C S_in^T) + D x
    S_out  = exp(c_last) S_in + sum_j exp(c_last - c_j) X_j B_j^T

Every term but the recurrence over S_in is computed for all chunks at
once; the scan over the T / C chunks carries one [P, N] state a head. B and
C stay at their G groups: C B^T is computed once a group, and a group's
R = H / G heads meet B and C as one [R x P, N] operand. State, decays, dt
and every sum are float32; the products take `matmul_dtype` operands and
accumulate in float32. Its backward pass is autodiff's.

`ssd_scan` is the training path: the same lines as one op with a
`custom_vjp` over two Pallas kernels, where `_kernel_takes` the shape, and
`ssd_chunked` elsewhere. It reads x, B and C in place from the mixer's
convolution output `mixed` [B, T, H P + 2 G N] (x, then B, then C: at
nemotron's sizes x at column 0, group g's B at 4096 + 128 g and C at
5120 + 128 g), so no slice of them is laid out again on either side.

* `ssd_scan` (forward): grid (B, G, chunks), the chunks in order. A
  program is one chunk of one group: x as column block g of width R P,
  B and C as 128-column blocks, dt as [R, C]. The group's state
  [R P, N] float32 stays in VMEM from chunk to chunk (it is the resident
  block of the last state), C B^T is one product a program, and each
  128-lane block of heads (two heads of 64) meets it in one product a
  head, its operand zero outside the head's lanes (as `ops/attention.py`
  does), so y is written once, [B, T, H P] float32, in the layout the gate
  and the grouped norm read. Under differentiation it also keeps the
  state each chunk entered with, [B, G, chunks, R P, N] float32 (67 MB a
  layer at nemotron's sizes), for the backward.
* `ssd_scan_bwd` (backward): grid (B, chunks, G), the chunks in reverse,
  dS carried in VMEM a group. For one head, with M_ij = (C_i . B_j) L_ij
  and Q_ij = M_ij (dy_i . X_j) for i >= j and w_j = exp(c_last - c_j):

      dX_j  = sum_{i>=j} M_ij dy_i + w_j dS_out B_j
      dS_in = exp(c_last) dS_out + sum_i exp(c_i) dy_i C_i^T
      dC_i += sum_{j<=i} L_ij (dy_i . X_j) B_j + exp(c_i) S_in^T dy_i
      dB_j += sum_{i>=j} L_ij (dy_i . X_j) C_i + w_j dS_out^T X_j
      dc_i  = sum_j Q_ij - sum_k Q_ki + exp(c_i) dy_i . (S_in C_i)
              - w_i <dS_out, X_i B_i^T>
              + [i = last] (exp(c_last) <dS_out, S_in>
                            + sum_j w_j <dS_out, X_j B_j^T>)
      r_k   = sum_{i>=k} dc_i,  ddt_k = A r_k + x_k . dX_k,
      dA    = sum_k dt_k r_k (d a_log = A dA),
      dx_k  = dt_k dX_k + D dy_k,  dD = sum_k x_k . dy_k

  Q is never formed: sum_j Q_ij = dy_i . (M X)_i, where (M X)_i is y_i
  less D x_i and the state's term (the forward's y is a residual), and
  sum_k Q_ki = X_i . (M^T dy)_i, the product dX takes anyway; each reads
  the operands its product rounded, so that the two cancel over the chunk
  as autodiff's do. dB and dC are summed over the group's heads inside the
  program (the [C, C] cotangent of C B^T once, then two products). A
  program's dx, dB and dC land in one [C, H P + 2 G N] block of the
  cotangent of `mixed`, in its dtype, that stays in VMEM while the G
  groups of a chunk pass: one array, written once, no concatenation. ddt
  leaves [B, T, H] float32; dA and dD are summed in resident blocks over
  the chunks.

Inside a program nothing is broadcast or summed across lanes head by head:
a per-head value (dt, exp(c_i), w_i, the column c_i of L) is laid over its
lanes by one bfloat16 product with the 0/1 matrix of whose lanes are whose,
the float32 value split exactly into three bfloat16 parts (`_lay`), and a
sum over a head's lanes runs down the sublanes of a transpose. Both kernels
are bound by their instruction count, not by bytes or the MXU:
the bundles of one program, as the compiler packs them for a v5e, track
their times on the chip.

Rounding points are `ssd_chunked(matmul_dtype=...)`'s: the products'
operands (cotangents included) in that type, sums in float32; the state,
the decays, dt, the cumulative sums (products with 0/1 triangles at
`highest`) and the masks in float32. `autodiff` of `ssd_chunked` is what
the tests hold both kernels to.

What the op costs alone (one v5e chip, nemotron's shape: T 4096, 64 heads
of 64, 8 groups, N 128, chunks of 128, bfloat16 operands; PERF.md section
6): forward 0.40 ms a call where XLA's form took 1.39 (0.47 keeping the
entering states), the backward kernel 0.75 ms, forward + backward 1.13 ms
where XLA's forward and autodiff took 4.63. Their least time, from bytes,
is 0.145 and 0.207 ms: they run at 36 % and 28 % of it. The packed
bundles (forward 2,012 a program, backward 3,743) predicted both times to
7 %.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_recurrent", "ssd_chunked", "ssd_scan"]

_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
# dot_general contractions: a . b, a . b^T, a^T . b.
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


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


# --------------------------------------------------------------------------
# The Pallas kernels (the module's docstring).


def _kernel_takes(t: int, heads: int, p: int, groups: int, state_size: int,
                  chunk: int) -> bool:
  """Whole chunks of whole 128-row tiles (C in the lanes of the [C, C]
  tiles), B and C of whole 128-lane tiles at whole blocks of N columns,
  and a group's heads in whole 128-lane blocks of x (two heads of 64, one
  of 128)."""
  lanes = math.lcm(p, _LANES)
  return (chunk % _LANES == 0 and t % chunk == 0 and heads % groups == 0
          and state_size % _LANES == 0 and heads * p % state_size == 0
          and (heads // groups * p) % lanes == 0)


def _mm(a, b, operand, contract=_NN):
  """a . b over `contract` with `operand` operands and float32 sums (at
  `highest` where the operands are float32, as `ssd_chunked` is on the
  chip under that precision)."""
  precision = _HIGHEST if operand == np.dtype(np.float32) else None
  return jax.lax.dot_general(a.astype(operand), b.astype(operand),
                             (contract, ((), ())), precision=precision,
                             preferred_element_type=jnp.float32)


def _exact(a, b, contract=_NN):
  """a . b of float32 operands at `highest`: the cumulative sums, as
  products with a 0/1 triangle, float32 all through."""
  return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                             preferred_element_type=jnp.float32)


def _triangle(size: int, lower: bool):
  """[size, size] float32: 1 where row >= column (lower) or <= (upper)."""
  row = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
  col = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
  return ((row >= col) if lower else (row <= col)).astype(jnp.float32)


def _parts(v_t):
  """v_t [R, C] float32 as [4 R, C] bfloat16: three parts that sum to it
  exactly (and a zero fourth, for whole tiles)."""
  hi = v_t.astype(jnp.bfloat16).astype(jnp.float32)
  rest = v_t - hi
  mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
  return jnp.concatenate([hi, mid, rest - mid, jnp.zeros_like(v_t)],
                         axis=0).astype(jnp.bfloat16)


def _lay(parts, where):
  """v^T w [C, n] for v_t's `_parts` and w [R, n] 0/1 (`where`): each row
  i of the result holds v_i of the heads w selects, to the float32 bit
  (one product, the parts summed in its float32 accumulator)."""
  w = where.astype(jnp.bfloat16)
  return jax.lax.dot_general(parts, jnp.concatenate([w, w, w, w], axis=0),
                             (_TN, ((), ())),
                             preferred_element_type=jnp.float32)


class _Chunk:
  """A program's decays from dt [R, C] and A [R, 1], by head, and laid
  over the heads' lanes of x [C, R P] by products with the 0/1 matrix of
  which lanes are whose (`_lay`), so that no column is broadcast across
  lanes."""

  def __init__(self, dt_ref, a_ref, p: int):
    self.p = p
    self.dt_t = dt_ref[...]
    r, size = self.dt_t.shape
    # c^T = (A dt) U, U the upper 0/1 triangle: c_i by head, [R, C]
    self.cum_t = _exact(self.dt_t * a_ref[...], _triangle(size, lower=False))
    last = self.cum_t[:, size - 1:]                        # c_last, [R, 1]
    self.last = jnp.exp(last)
    head = jax.lax.broadcasted_iota(jnp.int32, (r, r * p), 0) * p
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, r * p), 1)
    whose = (lane >= head) & (lane < head + p)             # [R, R P]
    self.dt = _lay(_parts(self.dt_t), whose)               # [C, R P]
    self.decayed = _lay(_parts(jnp.exp(self.cum_t)), whose)         # exp(c_i)
    self.to_end = _lay(_parts(jnp.exp(last - self.cum_t)), whose)   # w_i
    self._cum = _parts(self.cum_t)
    self._row = jax.lax.broadcasted_iota(jnp.int32, (r, size), 0)

  def within(self, h: int, causal):
    """L_ij = exp(c_i - c_j) for i >= j, 0 above the diagonal (masked
    before the exp)."""
    return jnp.exp(jnp.where(causal, _lay(self._cum, self._row == h)
                             - self.cum_t[h:h + 1, :], -jnp.inf))

  def blocks(self, rp: int):
    """Per 128-lane block of the group's heads: its lanes and its heads."""
    lanes = math.lcm(self.p, _LANES)
    per = lanes // self.p
    for block in range(rp // lanes):
      yield (slice(block * lanes, (block + 1) * lanes),
             list(range(block * per, (block + 1) * per)))

  def rows(self, heads):
    """exp(c_last) over the state's rows of `heads`, [len(heads) P, 1]."""
    shape = (len(heads) * self.p, 1)
    out = jnp.broadcast_to(self.last[heads[-1]:heads[-1] + 1], shape)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    for i in reversed(range(len(heads) - 1)):
      out = jnp.where(row < (i + 1) * self.p,
                      self.last[heads[i]:heads[i] + 1], out)
    return out

  def mask(self, heads, h):
    """[1, len(heads) P]: the lanes of head h among `heads`' (None where
    it is the only one)."""
    if len(heads) == 1:
      return None
    i = heads.index(h)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, len(heads) * self.p), 1)
    return (lane >= i * self.p) & (lane < (i + 1) * self.p)

  def sums(self, z, heads, total):
    """total [R, n] plus, in row h, the sum of z's [len(heads) P, n] rows
    of head h (its lanes, for z transposed)."""
    row = jax.lax.broadcasted_iota(jnp.int32, total.shape, 0)
    for i, h in enumerate(heads):
      total = total + jnp.where(row == h, jnp.sum(
          z[i * self.p:(i + 1) * self.p], axis=0, keepdims=True), 0.0)
    return total


def _only(mask, v):
  return v if mask is None else jnp.where(mask, v, 0.0)


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref, state_ref,
                *entering_ref, p: int, operand):
  chunk, rp = x_ref.shape

  @pl.when(pl.program_id(2) == 0)
  def _():
    state_ref[...] = jnp.zeros_like(state_ref)

  if entering_ref:
    entering_ref[0][...] = state_ref[...]
  k = _Chunk(dt_ref, a_ref, p)
  bm, cm = b_ref[...], c_ref[...]
  scores = _mm(cm, bm, operand, _NT)                      # C_i . B_j
  causal = _triangle(chunk, lower=True) > 0
  x = x_ref[...].astype(jnp.float32)
  written = x * k.dt
  state = state_ref[...]
  y = _mm(cm, state, operand, _NT) * k.decayed + d_ref[...] * x
  for cols, heads in k.blocks(rp):
    y_block = y[:, cols]
    for h in heads:
      y_block = y_block + _mm(scores * k.within(h, causal),
                              _only(k.mask(heads, h), written[:, cols]),
                              operand)
    y_ref[:, cols] = y_block
  state_ref[...] = state * jnp.concatenate(
      [k.rows(heads) for _, heads in k.blocks(rp)]) + _mm(
          written * k.to_end, bm, operand, _TN)


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref, dy_ref,
                entering_ref, dlast_ref, dmixed_ref, ddt_ref, da_ref, dd_ref,
                ds_ref, *, p: int, operand, b_at: int, c_at: int):
  chunk, rp = x_ref.shape
  state_size = b_ref.shape[1]
  step, g = pl.program_id(1), pl.program_id(2)

  @pl.when(step == 0)
  def _():
    ds_ref[g] = dlast_ref[...]

  @pl.when((step == 0) & (g == 0))
  def _():
    da_ref[...] = jnp.zeros_like(da_ref)
    dd_ref[...] = jnp.zeros_like(dd_ref)

  k = _Chunk(dt_ref, a_ref, p)
  r = k.dt_t.shape[0]
  bm, cm = b_ref[...], c_ref[...]
  scores = _mm(cm, bm, operand, _NT)
  causal = _triangle(chunk, lower=True) > 0
  d_scores = jnp.zeros((chunk, chunk), jnp.float32)      # of C B^T, summed
  dc_acc = jnp.zeros((chunk, state_size), jnp.float32)
  db_acc = jnp.zeros((chunk, state_size), jnp.float32)
  dc_t = jnp.zeros((r, chunk), jnp.float32)              # dc_i by head
  x_dx = jnp.zeros((r, chunk), jnp.float32)              # x_i . dX_i
  s_ds = jnp.zeros((r, state_size), jnp.float32)         # <dS_out, S_in>
  ends = jnp.zeros((r, 1), jnp.float32)  # sum_j w_j <dS_out, X_j B_j^T>
  rounded = lambda v: v.astype(operand).astype(jnp.float32)  # noqa: E731
  for cols, heads in k.blocks(rp):
    x = x_ref[:, cols].astype(jnp.float32)
    dy = dy_ref[:, cols]
    dt = k.dt[:, cols]
    written = x * dt
    state, ds = entering_ref[cols, :], ds_ref[g, cols, :]
    # Through S_out = exp(c_last) S_in + sum_j w_j X_j B_j^T.
    through_end = k.to_end[:, cols] * _mm(bm, ds, operand, _NT)  # w_j dS B_j
    db_acc = db_acc + _mm(written * k.to_end[:, cols], ds, operand)
    s_ds = k.sums(ds * state, heads, s_ds)
    # Through exp(c_i) C_i S_in: C's cotangent and dS_in.
    decayed = k.decayed[:, cols]
    dy_decayed = dy * decayed
    dc_acc = dc_acc + _mm(dy_decayed, state, operand)
    from_state = _mm(cm, state, operand, _NT) * decayed
    ds_ref[g, cols, :] = ds * k.rows(heads) + _mm(
        dy_decayed, cm, operand, _TN)
    d_within = jnp.zeros_like(x)
    for h in heads:
      within = k.within(h, causal)
      dy_h = _only(k.mask(heads, h), dy)
      d_within = d_within + _mm(scores * within, dy_h, operand, _TN)
      d_scores = d_scores + within * _mm(
          dy_h, _only(k.mask(heads, h), written), operand, _NT)
    d_written = d_within + through_end                   # dX
    dmixed_ref[:, pl.ds(pl.multiple_of(g * rp + cols.start, _LANES),
                        cols.stop - cols.start)] = (
        d_written * dt + d_ref[:, cols] * dy).astype(dmixed_ref.dtype)
    # dc_i: sum_j Q_ij = dy_i . (M X)_i and sum_k Q_ki = X_i . (M^T dy)_i,
    # each from the operands its product rounded, so that they cancel as
    # autodiff's do; (M X)_i + exp(c_i) C_i S_in = y_i - D x_i.
    end_terms = written * through_end            # w_i <dS_out, X_i B_i^T>
    dy_r = rounded(dy)
    dc_t = k.sums((dy_r * (y_ref[:, cols] - d_ref[:, cols] * x)
                   + (dy - dy_r) * from_state - rounded(written) * d_within
                   - end_terms).T, heads, dc_t)
    ends = k.sums(jnp.sum(end_terms, axis=0, keepdims=True).T, heads, ends)
    x_dx = k.sums((x * d_written).T, heads, x_dx)
    dd_ref[g, :, cols] += jnp.sum(x * dy, axis=0, keepdims=True)
  dc = dc_acc + _mm(d_scores, bm, operand)
  db = db_acc + _mm(d_scores, cm, operand, _TN)
  at = lambda start: pl.ds(  # noqa: E731
      pl.multiple_of(start + g * state_size, _LANES), state_size)
  dmixed_ref[:, at(b_at)] = db.astype(dmixed_ref.dtype)
  dmixed_ref[:, at(c_at)] = dc.astype(dmixed_ref.dtype)
  # dc_i by head; r_k = sum_{i >= k} dc_i (r^T = dc^T L, L the lower 0/1
  # triangle); ddt_k = A r_k + x_k . dX_k; dA = sum_k dt_k r_k.
  lane = jax.lax.broadcasted_iota(jnp.int32, (r, chunk), 1)
  dc_t = dc_t + jnp.where(lane == chunk - 1, ends + k.last * jnp.sum(
      s_ds, axis=1, keepdims=True), 0.0)
  back = _exact(dc_t, _triangle(chunk, lower=True))
  ddt_ref[...] = a_ref[...] * back + x_dx
  da_ref[g] += jnp.sum(k.dt_t * back, axis=1, keepdims=True)


def _layout(mixed, dt, a_log, d, plan):
  """(sizes, dt as [B, G, R, T], A as [G, R, 1], D over x's lanes
  [G, 1, R P]) for the kernels: T in the lanes, so that a program reads
  its heads' dt as one [R, C] tile."""
  groups, state_size, chunk, _ = plan
  batch, t, width = mixed.shape
  heads = dt.shape[-1]
  r = heads // groups
  p = (width - 2 * groups * state_size) // heads
  dt_t = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(
      batch, groups, r, t)
  a = -jnp.exp(a_log.astype(jnp.float32)).reshape(groups, r, 1)
  d_lanes = jnp.repeat(d.astype(jnp.float32), p).reshape(groups, 1, r * p)
  return (batch, t, heads, p, r, t // chunk), dt_t, a, d_lanes


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _forward(mixed, dt, a_log, d, plan, keep: bool, interpret: bool):
  """(y [B, T, H P] float32, the last state [B, H, P, N], and where
  `keep` the states the chunks entered with [B, G, chunks, R P, N]). Under
  `jit` so that a step's many calls of one shape trace the kernel once."""
  groups, state_size, chunk, operand = plan
  (batch, t, heads, p, r, n), dt_t, a, d_lanes = _layout(
      mixed, dt, a_log, d, plan)
  rp = r * p
  b_block, c_block = heads * p // state_size, heads * p // state_size + groups
  out_specs = [pl.BlockSpec((None, chunk, rp), lambda b, g, k: (b, k, g)),
               pl.BlockSpec((None, None, rp, state_size),
                            lambda b, g, k: (b, g, 0, 0))]
  out_shape = [jax.ShapeDtypeStruct((batch, t, heads * p), jnp.float32),
               jax.ShapeDtypeStruct((batch, groups, rp, state_size),
                                    jnp.float32)]
  if keep:
    out_specs.append(pl.BlockSpec((None, None, None, rp, state_size),
                                  lambda b, g, k: (b, g, k, 0, 0)))
    out_shape.append(jax.ShapeDtypeStruct((batch, groups, n, rp, state_size),
                                          jnp.float32))
  outs = pl.pallas_call(
      functools.partial(_fwd_kernel, p=p, operand=operand),
      grid=(batch, groups, n),
      in_specs=[
          pl.BlockSpec((None, chunk, rp), lambda b, g, k: (b, k, g)),
          pl.BlockSpec((None, chunk, state_size),
                       lambda b, g, k: (b, k, b_block + g)),
          pl.BlockSpec((None, chunk, state_size),
                       lambda b, g, k: (b, k, c_block + g)),
          pl.BlockSpec((None, None, r, chunk), lambda b, g, k: (b, g, 0, k)),
          pl.BlockSpec((None, r, 1), lambda b, g, k: (g, 0, 0)),
          pl.BlockSpec((None, 1, rp), lambda b, g, k: (g, 0, 0)),
      ],
      out_specs=out_specs,
      out_shape=out_shape,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "arbitrary")),
      interpret=interpret,
      name="ssd_scan",
  )(mixed, mixed, mixed, dt_t, a, d_lanes)
  return (outs[0], outs[1].reshape(batch, heads, p, state_size)) + tuple(
      outs[2:])


@functools.partial(jax.jit, static_argnums=(8, 9))
def _backward(mixed, dt, a_log, d, y, entering, dy, dlast, plan,
              interpret: bool):
  """(d mixed [B, T, H P + 2 G N] in its dtype, ddt [B, T, H], d a_log,
  dD) for y and the last state of the forward (y its output)."""
  groups, state_size, chunk, operand = plan
  (batch, t, heads, p, r, n), dt_t, a, d_lanes = _layout(
      mixed, dt, a_log, d, plan)
  rp, width = r * p, mixed.shape[2]
  b_at = heads * p
  b_block, c_block = b_at // state_size, b_at // state_size + groups
  back = lambda k: n - 1 - k  # noqa: E731
  dmixed, ddt, da, dd = pl.pallas_call(
      functools.partial(_bwd_kernel, p=p, operand=operand, b_at=b_at,
                        c_at=b_at + groups * state_size),
      grid=(batch, n, groups),
      in_specs=[
          pl.BlockSpec((None, chunk, rp), lambda b, k, g: (b, back(k), g)),
          pl.BlockSpec((None, chunk, state_size),
                       lambda b, k, g: (b, back(k), b_block + g)),
          pl.BlockSpec((None, chunk, state_size),
                       lambda b, k, g: (b, back(k), c_block + g)),
          pl.BlockSpec((None, None, r, chunk),
                       lambda b, k, g: (b, g, 0, back(k))),
          pl.BlockSpec((None, r, 1), lambda b, k, g: (g, 0, 0)),
          pl.BlockSpec((None, 1, rp), lambda b, k, g: (g, 0, 0)),
          pl.BlockSpec((None, chunk, rp), lambda b, k, g: (b, back(k), g)),
          pl.BlockSpec((None, chunk, rp), lambda b, k, g: (b, back(k), g)),
          pl.BlockSpec((None, None, None, rp, state_size),
                       lambda b, k, g: (b, g, back(k), 0, 0)),
          pl.BlockSpec((None, None, rp, state_size),
                       lambda b, k, g: (b, g, 0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((None, chunk, width), lambda b, k, g: (b, back(k), 0)),
          pl.BlockSpec((None, None, r, chunk),
                       lambda b, k, g: (b, g, 0, back(k))),
          pl.BlockSpec((None, groups, r, 1), lambda b, k, g: (b, 0, 0, 0)),
          pl.BlockSpec((None, groups, 1, rp), lambda b, k, g: (b, 0, 0, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct(mixed.shape, mixed.dtype),
          jax.ShapeDtypeStruct((batch, groups, r, t), jnp.float32),
          jax.ShapeDtypeStruct((batch, groups, r, 1), jnp.float32),
          jax.ShapeDtypeStruct((batch, groups, 1, rp), jnp.float32),
      ],
      scratch_shapes=[pltpu.VMEM((groups, rp, state_size), jnp.float32)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary", "arbitrary")),
      interpret=interpret,
      name="ssd_scan_bwd",
  )(mixed, mixed, mixed, dt_t, a, d_lanes, y, dy.astype(jnp.float32),
    entering, dlast.astype(jnp.float32).reshape(batch, groups, rp,
                                                  state_size))
  ddt = ddt.reshape(batch, heads, t).transpose(0, 2, 1)
  da = da.sum(axis=0).reshape(heads)
  dd = dd.reshape(batch, heads, p).sum(axis=(0, 2))
  return (dmixed, ddt.astype(dt.dtype),
          (da * -jnp.exp(a_log.astype(jnp.float32))).astype(a_log.dtype),
          dd.astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _scan(mixed, dt, a_log, d, plan, interpret):
  return _forward(mixed, dt, a_log, d, plan, False, interpret)


def _scan_fwd(mixed, dt, a_log, d, plan, interpret):
  y, last, entering = _forward(mixed, dt, a_log, d, plan, True, interpret)
  return (y, last), (mixed, dt, a_log, d, y, entering)


def _scan_bwd(plan, interpret, residuals, cotangents):
  dy, dlast = cotangents
  return _backward(*residuals, dy, dlast, plan, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(mixed, dt, a_log, d, groups: int, state_size: int,
             chunk_size: int = 128, matmul_dtype=None,
             interpret: Optional[bool] = None):
  """The scan of x, B and C as they lie in `mixed` [B, T, H P + 2 G N]: x
  (head h at columns h P..) at column 0, group g's B at H P + g N and its C
  at H P + G N + g N; dt [B, T, H] (after its softplus), a_log and d [H].
  Returns (y [B, T, H P], the last state [B, H, P, N]), float32,
  differentiable in all four. The Pallas kernels where `_kernel_takes` the
  shape, else `ssd_chunked` on slices of `mixed`; `chunk_size` and
  `matmul_dtype` as `ssd_chunked`'s. `interpret`: whether the kernels run
  interpreted (off the TPU) or as Mosaic kernels; None follows the
  lowering platform."""
  batch, t, width = mixed.shape
  heads = dt.shape[-1]
  p = (width - 2 * groups * state_size) // heads
  if not _kernel_takes(t, heads, p, groups, state_size, int(chunk_size)):
    x_end, b_end = heads * p, heads * p + groups * state_size
    y, last = ssd_chunked(
        mixed[..., :x_end].reshape(batch, t, heads, p), dt, a_log,
        mixed[..., x_end:b_end].reshape(batch, t, groups, state_size),
        mixed[..., b_end:].reshape(batch, t, groups, state_size), d,
        chunk_size=chunk_size, matmul_dtype=matmul_dtype)
    return y.reshape(batch, t, heads * p), last
  plan = (groups, state_size, int(chunk_size),
          np.dtype(matmul_dtype or jnp.float32))
  if interpret is None:
    return jax.lax.platform_dependent(
        mixed, dt, a_log, d,
        tpu=lambda *a: _scan(*a, plan, False),
        default=lambda *a: _scan(*a, plan, True))
  return _scan(mixed, dt, a_log, d, plan, bool(interpret))
