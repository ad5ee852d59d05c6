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
T / C chunks. Everything that does not need the travelling state (A, its
inverse, the in-chunk scores) is computed for all chunks at once, outside
the scan. The backward pass is autodiff's, but for the inverse, which is
one op with its own.

(I + A)^-1: A is nilpotent (A^C = 0), so with N = -A the inverse is the
finite product (I + N)(I + N^2)(I + N^4)... of log2(C) factors: ten
[C, C] products at C 64, at float32 accuracy (`highest`), where the
source runs C sequential row updates. On the chunked layout
[N, B, H, C, C] with a C that Mosaic tiles they run in one Pallas kernel,
`gdn_inverse`, on tiles that stay in VMEM: the operand is read once and
the inverse written once (`_inverse_kernel`); other shapes keep the same
products in XLA (`_doubling_inverse`), which the tests hold the kernel
to. Its backward is closed form, dA = -T^T G T^T with T the inverse and
G its cotangent: two products and one residual where autodiff through
the ten ran twenty and kept eleven. The state, the gates and every sum
are float32; the other products take the backend's default precision,
which on the TPU feeds the MXU bfloat16 operands and accumulates in
float32.

`gated_delta_rule` is the training path: the chunked rule as one op with a
`custom_vjp` over two Pallas kernels, where `_scan_heads` takes the shape,
and `gated_delta_rule_chunked` elsewhere. It reads q, k and v in place from
the convolution's result `mixed` [B, T, 2 H_k d_k + H_v d_v] (at the hybrid
cell's sizes q of key head j at column block j, its k at 16 + j, v of value
head h at 32 + h, blocks of 128), and value head h reads key head
h // (H_v / H_k) through the blocks' index maps, so neither a slice nor the
repeat is laid out. Per value head and chunk, with q and k normalised (q
scaled by d_k^-0.5), G_i the cumulative gate, g = G_last, D_ij =
exp(G_i - G_j) for i >= j (0 above), and S the state the chunk enters with:

    A = ((k beta) k^T) o D, strictly lower;   T = (I + A)^-1
    W = T (v beta),   R = T (k beta e^G),   P = (q k^T) o D
    u = W - R S
    o = (q e^G) S + P u
    S_out = e^g S + (k e^(g - G))^T u

* `gdn_scan` (forward): grid (B, head groups, chunks), the chunks in
  order; 16 value heads (8 key heads) a program, their states [d_k, d_v]
  float32 in VMEM from chunk to chunk (the resident block of the last
  state). T comes from `_refined_inverses`: the doubling product in one
  bfloat16 pass, two heads side by side in 128 lanes against their powers
  laid block-diagonally, then one Newton step from a residual made in
  three passes of bfloat16 parts: 2^-16 of the largest entry or better,
  for an inverse that only ever becomes a bfloat16 operand. o is written
  once, [B, T, H_v d_v] float32, the layout the gated norm reads. It also keeps, for the backward, the state each chunk
  entered with, [B, H_v, chunks, d_k, d_v] float32 (134 MB a layer at the
  cell's sizes), and T as the products read it, [B, H_v, chunks, C, C] in
  the operands' dtype (17 MB): in the primal too, so that one forward
  kernel is traced, not two.
* `gdn_scan_bwd` (backward): grid (B, chunks, head groups), the chunks in
  reverse, dS carried in VMEM a group. With dO and dS_out:

      du     = P^T dO + (k e^(g-G)) dS_out
      dP     = dO u^T,   d(q e^G) = dO S^T,   d(k e^(g-G)) = u dS_out^T
      dS_in  = e^g dS_out + (q e^G)^T dO - R^T du
      dW     = du,       dR = -du S^T
      d(v beta), d(k beta e^G) = T^T dW, T^T dR
      dA     = -T^T dT T^T = -(d(v beta) W^T + d(k beta e^G) R^T), strictly
               lower
      E = dA o D,  F = dP o D:  d(k beta) = E k + d(k beta e^G) e^G,
      dq     = F k + d(q e^G) e^G,
      dk     = E^T (k beta) + F^T q + d(k beta) beta + d(k e^(g-G)) e^(g-G)
      M      = dA o A + dP o (q k^T o D)              (dD o D)
      dG_i   = sum_j M_ij - sum_j M_ji + k_i beta_i e^G_i . d(k beta e^G)_i
               + q_i e^G_i . d(q e^G)_i - k_i e^(g-G_i) . d(k e^(g-G))_i
               + [i = last] (e^g <dS_out, S> + sum_i k_i e^(g-G_i) .
                             d(k e^(g-G))_i)
      dg_j   = sum_{i >= j} dG_i
      d beta = k . d(k beta) + v . d(v beta),   dv = d(v beta) beta

  and dq, dk through the normalisation (dx = s / |x| (dq - x (x . dq) /
  |x|^2)), summed over the value heads that share a key head. A program's
  dq, dk and dv land in one [C, 2 H_k d_k + H_v d_v] block of the cotangent
  of `mixed`, in its dtype, that stays in VMEM while the head groups of a
  chunk pass: one array, written once, no concatenation. dg and d beta
  leave [B, T, H_v] float32.

Both kernels write their products stage by stage over a program's heads:
the MXU takes products in the order they are written, and each head's
chain of dependent products would otherwise run one head after another.
They are bound by the rows they stream through the MXU (a [64, 128] LHS
against a weight tile is 64 of its cycles), not by HBM. Rounding points
are `gated_delta_rule_chunked(matmul_dtype=...)`'s: every product's
operands in that type, sums in float32; the state, the gates, the decays
and T in float32 until T becomes an operand. Cotangents stay float32 until
they become a product's operand, where autodiff of the XLA form rounds
each to the operands' type as it leaves a product: a rounding fewer, never
one more. Autodiff of `gated_delta_rule_chunked` is what the tests hold
both kernels to.
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

from tensor2robot_tpu.ops import state_space as ss

__all__ = ["gated_delta_rule_recurrent", "gated_delta_rule_chunked",
           "gated_delta_rule"]

_L2_EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST
# `gdn_inverse`: heads of one chunk a program (1 MB a buffer at C 64),
# tiles a batched product, lanes a tile. ms a call at f32[64, 1, 32, 64, 64]
# on the v5e (PERF.md section 6, PR 34): XLA's ten products 2.82; one
# [64, 64] product a head 1.42-1.44 at 4-16 heads a program; two heads a
# tile 0.79 at 32 heads and 4 tiles (0.80 at 16 and 4, 0.85 at 32 and 8,
# 0.96 at 8 and 2).
_HEADS_A_PROGRAM = 32
_TILES_A_PRODUCT = 4
_LANES = 128


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


def _doubling_inverse(a):
  """(I + a)^-1 for strictly lower-triangular a [..., C, C], as the
  finite product of the module's docstring, in XLA: the shapes the kernel
  does not take, and what the tests hold the kernel to."""
  c = a.shape[-1]
  power = -a
  inverse = jnp.eye(c, dtype=a.dtype) + power
  covered = 2  # `inverse` holds the sum of N^0 .. N^(covered - 1)
  while covered < c:
    power = jnp.matmul(power, power, precision=_HIGHEST)
    inverse = inverse + jnp.matmul(inverse, power, precision=_HIGHEST)
    covered *= 2
  return inverse


def _inverse_kernel(a_ref, t_ref, *, side: int, together: int):
  """One program of `gdn_inverse`: a chunk's group of heads, [G, C, C]."""
  inverses = _unit_lower_inverses([a_ref[i] for i in range(a_ref.shape[0])],
                                  side, together)
  for i, inverse in enumerate(inverses):
    t_ref[i] = inverse


def _highest(x, y):
  """The batched product [T, I, W] x [T, W, V] at float32 accuracy."""
  return jnp.einsum("tiw,twv->tiv", x, y, precision=_HIGHEST,
                    preferred_element_type=jnp.float32)


def _split(v):
  """v float32 as (hi, lo) bfloat16, hi + lo = v to 16 bits."""
  hi = v.astype(jnp.bfloat16)
  return hi, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _refined_inverses(blocks, side: int):
  """`_unit_lower_inverses` for a caller that reads the inverse as a
  bfloat16 operand: the doubling product in one bfloat16 pass gives T0,
  good to about 2^-10 of its largest entry, and one Newton step,
  T0 + T0 (I - (I + A) T0), with (I + A) T0 to 2^-16 from bfloat16 parts
  (a_hi T_hi + a_lo T_hi + a_hi T_lo), takes it to the square of that: 2^-16
  or better (2^-20 where A is the rule's, unit keys, beta < 1, decays <= 1;
  the tests hold it to 2^-14 on harder ones). Two heads lie side by side in
  128 lanes against their matrices laid block-diagonally, as in
  `_unit_lower_inverses`; each stage runs over every pair before the next
  (the MXU takes its products in the order they are written)."""
  c = blocks[0].shape[-1]
  width = side * c
  lane = jax.lax.broadcasted_iota(jnp.int32, (c, width), 1)
  row = jax.lax.broadcasted_iota(jnp.int32, (c, width), 0)
  eye = (row == lane % c).astype(jnp.float32)
  levels = max(math.ceil(math.log2(c)) - 1, 0)
  masks = [((lane >= head * c) & (lane < (head + 1) * c)).astype(jnp.float32)
           for head in range(side)]

  def block_diagonal(x):  # [C, side x C] -> [side x C, side x C]
    return jnp.concatenate([x * mask for mask in masks])

  def mm(x, y):
    return ss._mm(x, y, jnp.bfloat16)

  a_s = [jnp.concatenate([blocks[first + head] for head in range(side)],
                         axis=1) for first in range(0, len(blocks), side)]
  powers = [-a for a in a_s]
  inverses = [eye + power for power in powers]   # the sums of N^0 .. N^1
  for level in range(1, levels + 2):
    # With N^(2^(level-1)): its square (while a power is still wanted) and,
    # from level 2, the inverse times it, as one operand of stacked rows.
    for i, power in enumerate(powers):
      lhs = [power] if level <= levels else []
      lhs += [inverses[i]] if level > 1 else []
      n = len(lhs) * c
      both = mm(jnp.concatenate(lhs), block_diagonal(power))
      if level > 1:
        inverses[i] = inverses[i] + both[n - c:]
      if level <= levels:
        powers[i] = both[:c]
  residuals = []
  for a, t in zip(a_s, inverses):           # I - (I + A) T0
    a_hi, a_lo = _split(a)
    t_hi, t_lo = _split(block_diagonal(t))
    high = mm(jnp.concatenate([a_hi, a_lo]), t_hi)
    residuals.append(eye - t - (high[:c] + high[c:] + mm(a_hi, t_lo)))
  refined = [t + mm(t, block_diagonal(residual))
             for t, residual in zip(inverses, residuals)]
  return [t[:, head * c:(head + 1) * c] for t in refined
          for head in range(side)]


def _unit_lower_inverses(blocks, side: int, together: int):
  """(I + a)^-1 for each strictly lower-triangular [C, C] of `blocks`.

  The same doubling product as `_doubling_inverse`, laid out for the MXU:
  `side` heads lie side by side in the lanes of one [C, side x C] tile,
  and the tile meets the heads' powers laid block-diagonally,
  [side x C, side x C] (the tile's own rows, masked to one head's lanes at
  a time, one below the other: selects and a sublane concatenation, no
  lane moves). The blocks off the diagonal are exact zeros, so every
  product is the [C, C] product to the bit, and at C 64 it streams 64
  rows through a whole 128 x 128 MXU tile for two heads where two
  [64, 64] products stream 128 through a quarter of it. `together` tiles
  make one batched product. The power that squares itself and the
  inverse it multiplies meet it as one operand of twice the rows, so that
  each power is loaded into the MXU once: log2(C) products deep."""
  group = len(blocks)
  c = blocks[0].shape[-1]
  width = side * c
  lane = jax.lax.broadcasted_iota(jnp.int32, (c, width), 1)
  row = jax.lax.broadcasted_iota(jnp.int32, (c, width), 0)
  eye = (row == lane % c).astype(jnp.float32)
  levels = max(math.ceil(math.log2(c)) - 1, 0)  # powers N^2 .. N^(2^levels)

  def block_diagonal(x):  # [together, C, side x C] -> [.., side x C, side x C]
    return jnp.concatenate(
        [jnp.where(lane // c == head, x, 0.0) for head in range(side)],
        axis=1)

  out = []
  for first in range(0, group, side * together):
    power = jnp.stack([
        jnp.concatenate([-blocks[first + tile * side + head]
                         for head in range(side)], axis=1)
        for tile in range(together)])
    inverse = eye + power            # the sum of N^0 .. N^1
    for level in range(1, levels + 1):
      # With N^(2^(level-1)): its square, and (level > 1) the inverse
      # times it (the sum of N^0 .. N^(2^level - 1)).
      lhs = [power] + ([inverse] if level > 1 else [])
      both = _highest(jnp.concatenate(lhs, axis=1), block_diagonal(power))
      if level > 1:
        inverse = inverse + both[:, c:]
      power = both[:, :c]
    if levels:
      inverse = inverse + _highest(inverse, block_diagonal(power))
    out += [inverse[tile, :, head * c:(head + 1) * c]
            for tile in range(together) for head in range(side)]
  return out


def _kernel_takes(shape) -> bool:
  """The chunked layout [N, B, H, C, C] with a C that Mosaic tiles
  (float32 sublanes come in eights)."""
  return len(shape) == 5 and shape[-1] % 8 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _inverse(a, interpret):
  """(I + a)^-1 with its own backward; `gdn_inverse` where
  `_kernel_takes` the shape."""
  if not _kernel_takes(a.shape):
    return _doubling_inverse(a)
  n, b, h, c, _ = a.shape
  group = math.gcd(h, _HEADS_A_PROGRAM)
  side = math.gcd(_LANES // c, group) if _LANES % c == 0 else 1
  block = pl.BlockSpec((None, None, group, c, c),
                       lambda i, j, k: (i, j, k, 0, 0))
  return pl.pallas_call(
      functools.partial(_inverse_kernel, side=side,
                        together=math.gcd(group // side, _TILES_A_PRODUCT)),
      grid=(n, b, h // group),
      in_specs=[block],
      out_specs=block,
      out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "parallel")),
      interpret=interpret,
      name="gdn_inverse",
  )(a)


def _inverse_fwd(a, interpret):
  inverse = _inverse(a, interpret)
  return inverse, inverse


def _inverse_bwd(interpret, inverse, cotangent):
  """d/dA of T = (I + A)^-1 is -T^T G T^T: T is the only residual. (The
  doubling product equals the inverse wherever A is nilpotent, and a
  perturbation inside the strict lower triangle leaves it so; the
  caller's mask drops the rest.)"""
  del interpret
  transposed = jnp.swapaxes(inverse, -1, -2)
  return (-jnp.matmul(
      jnp.matmul(transposed, cotangent, precision=_HIGHEST), transposed,
      precision=_HIGHEST),)


_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _inverse_of_unit_lower(a, interpret: Optional[bool] = None):
  """(I + a)^-1 for strictly lower-triangular a [..., C, C]: one op with
  its own backward. `interpret=None` follows the lowering platform, as
  `attention.flash_attention` does."""
  if interpret is None and _kernel_takes(a.shape):
    return jax.lax.platform_dependent(
        a, tpu=lambda x: _inverse(x, False),
        default=lambda x: _inverse(x, True))
  return _inverse(a, bool(interpret))


def gated_delta_rule_chunked(q, k, v, g, beta, chunk_size: int = 64,
                             matmul_dtype=None,
                             interpret: Optional[bool] = None):
  """The same rule in chunks of `chunk_size` tokens; shapes and results as
  `gated_delta_rule_recurrent`. A length that the chunk does not divide is
  padded with tokens that write nothing (beta 0, g 0). `matmul_dtype`
  (bfloat16 on the training path) is the type the products' operands are
  held in; they accumulate in float32, and the inverse stays float32.
  `interpret`: whether `gdn_inverse` runs interpreted (off the TPU) or as
  a Mosaic kernel; None follows the lowering platform."""
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
  inverse = _inverse_of_unit_lower(a, interpret)
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


# --------------------------------------------------------------------------
# `gated_delta_rule`: the rule as one op over two Pallas kernels (the
# module's docstring).

# Value heads a program of `gdn_scan` / `gdn_scan_bwd`: 128 programs a call
# at the hybrid cell's 32 heads and 64 chunks. Packed bundles a call
# (described v5e), forward / backward: 8 heads 1.29 M / 1.25 M, 16 heads
# 1.09 M / 1.11 M; 32 heads do not fit the 16 MB of VMEM.
_HEADS_A_SCAN = 16
# Scoped VMEM of either kernel: at 16 heads the backward's live values need
# 20-22 MB on the chip, over the default 16 MB (the chip's own compiler; the
# described v5e does not see it, PERF.md section 7).
_VMEM_BYTES = 48 << 20


def _scan_heads(t: int, k_heads: int, v_heads: int, d_k: int, d_v: int,
                chunk: int) -> Optional[int]:
  """Value heads a program, or None where the kernels do not take the
  shape: heads of whole 128-lane blocks, whole chunks of a multiple of 8
  rows, and value heads in whole groups of their key head. A program
  takes whole key heads, its gates as one [heads, C] tile (eight rows or
  all of them), and its values as one block of `mixed`'s columns."""
  if (d_k % _LANES or d_v % _LANES or chunk % 8 or t % chunk
      or v_heads % k_heads):
    return None
  r = v_heads // k_heads
  for heads in range(min(max(_HEADS_A_SCAN, r), v_heads), 0, -1):
    if (v_heads % heads == 0 and heads % r == 0
        and (heads % 8 == 0 or heads == v_heads)
        and 2 * k_heads * d_k % (heads * d_v) == 0):
      return heads
  return None


def _unit(x):
  """(x over its length, the inverse length [C, 1]), float32."""
  x = x.astype(jnp.float32)
  inverse = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + _L2_EPS)
  return x * inverse, inverse


def _whose(heads: int, d: int):
  """[heads, heads x d] 0/1: which lanes are head h's."""
  lane = jax.lax.broadcasted_iota(jnp.int32, (heads, heads * d), 1)
  first = jax.lax.broadcasted_iota(jnp.int32, (heads, heads * d), 0) * d
  return (lane >= first) & (lane < first + d)


class _Gates:
  """A program's gates by value head: the cumulative gate G ([heads, C],
  the chunk in the lanes) and the per-row values the products need laid
  over each head's lanes ([C, heads x d], `state_space._lay`: one product
  with the 0/1 matrix of whose lanes are whose, so that no column is
  broadcast across lanes): beta, e^G and e^(g - G) over the key's lanes
  in one product, G over the chunk's lanes for D in another."""

  def __init__(self, g_t, beta_t, d_k: int, d_v: int):
    heads, c = g_t.shape
    self.c = c
    self.cum_t = ss._exact(g_t, ss._triangle(c, lower=False))
    last = self.cum_t[:, c - 1:]
    laid = ss._lay(jnp.concatenate(
        [ss._parts(v) for v in (beta_t, jnp.exp(self.cum_t),
                                jnp.exp(last - self.cum_t))], axis=1),
                   _whose(heads, d_k))
    self.beta_k, self.grow, self.to_end = laid[:c], laid[c:2 * c], laid[2 * c:]
    if d_v == d_k:
      self.beta_v, grow_v = self.beta_k, self.grow
    else:
      laid = ss._lay(jnp.concatenate(
          [ss._parts(beta_t), ss._parts(jnp.exp(self.cum_t))], axis=1),
                     _whose(heads, d_v))
      self.beta_v, grow_v = laid[:c], laid[c:]
    self.decay = grow_v[c - 1:]         # e^g over each head's d_v lanes
    self._cum = ss._lay(ss._parts(self.cum_t), _whose(heads, c))

  def within(self, h: int, causal):
    """D_ij = exp(G_i - G_j) for i >= j, 0 above the diagonal (masked
    before the exp)."""
    c = self.c
    return jnp.exp(jnp.where(causal, self._cum[:, h * c:(h + 1) * c]
                             - self.cum_t[h:h + 1, :], -jnp.inf))


class _Program:
  """What both kernels compute for one chunk of a group of value heads
  before the travelling state: q and k normalised by key head, and by value
  head D, k beta, A, q k^T o D and T = (I + A)^-1 (or T as the forward
  kept it, `t_ref`). One product a key head gives A of each of its value
  heads and q k^T (k^T is loaded into the MXU once)."""

  def __init__(self, q_ref, k_ref, g_ref, beta_ref, r: int, d_v: int,
               operand, t_ref=None):
    c = q_ref.shape[0]
    self.heads = heads = g_ref.shape[0]
    self.r, self.operand = r, operand
    self.d_k = d_k = q_ref.shape[1] * r // heads
    self.gates = gates = _Gates(g_ref[...], beta_ref[...], d_k, d_v)
    self.causal = ss._triangle(c, lower=True) > 0
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    self.strict = row > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    self.q, self.k, self.k_beta, self.a, self.scores = [], [], [], [], []
    self.within = []
    for j in range(heads // r):
      q, _ = _unit(q_ref[:, j * d_k:(j + 1) * d_k])
      k, _ = _unit(k_ref[:, j * d_k:(j + 1) * d_k])
      self.q.append(q * d_k ** -0.5)
      self.k.append(k)
      k_beta = [k * gates.beta_k[:, self.cols(h)]
                for h in range(j * r, (j + 1) * r)]
      self.k_beta += k_beta
      both = self.mm(jnp.concatenate(k_beta + [self.q[j]]), k, ss._NT)
      for i, h in enumerate(range(j * r, (j + 1) * r)):
        within = gates.within(h, self.causal)
        self.within.append(within)
        self.a.append(jnp.where(self.strict,
                                both[i * c:(i + 1) * c] * within, 0.0))
        self.scores.append(both[r * c:] * within)          # q k^T o D
    if t_ref is not None:
      self.t = [t_ref[h] for h in range(heads)]
      return
    side = math.gcd(_LANES // c, heads) if _LANES % c == 0 else 1
    if operand == np.dtype(np.float32):
      inverses = _unit_lower_inverses(
          self.a, side, math.gcd(heads // side, _TILES_A_PRODUCT))
    else:
      inverses = _refined_inverses(self.a, side)
    self.t = [t.astype(operand) for t in inverses]

  def cols(self, h: int):
    return slice(h * self.d_k, (h + 1) * self.d_k)

  def mm(self, a, b, contract=ss._NN):
    return ss._mm(a, b, self.operand, contract)

  def head(self, h: int, v):
    """The chunk's terms for value head h and its v [C, d_v]: (W, R as
    the products read it, q e^G and k e^(g - G) unrounded)."""
    j, cols, gates, d_v = h // self.r, self.cols(h), self.gates, v.shape[1]
    both = self.mm(self.t[h], jnp.concatenate(
        [v * gates.beta_v[:, h * d_v:(h + 1) * d_v],
         self.k_beta[h] * gates.grow[:, cols]], axis=1))
    w, reads = both[:, :d_v], both[:, d_v:].astype(self.operand)
    q_in = self.q[j] * gates.grow[:, cols]
    k_out = self.k[j] * gates.to_end[:, cols]
    return w, reads, q_in, k_out


def _scan_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_ref,
                     entering_ref, t_ref, *, r: int, operand):
  @pl.when(pl.program_id(2) == 0)
  def _():
    state_ref[...] = jnp.zeros_like(state_ref)

  entering_ref[...] = state_ref[...]
  d_v = v_ref.shape[1] // g_ref.shape[0]
  p = _Program(q_ref, k_ref, g_ref, beta_ref, r, d_v, operand)
  c, heads = q_ref.shape[0], p.heads
  # Stage by stage over the heads: the MXU takes its products in the order
  # they are written, so each stage's products follow one another while
  # the previous stage's results drain. Every read of the state comes
  # before any write.
  states = [state_ref[h] for h in range(heads)]
  vcols = [slice(h * d_v, (h + 1) * d_v) for h in range(heads)]
  terms = [p.head(h, v_ref[:, vcols[h]].astype(jnp.float32))
           for h in range(heads)]
  # R S and (q e^G) S with S loaded once, then u; P u and (k e^(g-G))^T u
  # with u loaded once.
  read_s = [p.mm(jnp.concatenate([reads, q_in.astype(operand)]), states[h])
            for h, (_, reads, q_in, _) in enumerate(terms)]
  u = [w - read_s[h][:c] for h, (w, _, _, _) in enumerate(terms)]
  out = [p.mm(jnp.concatenate([p.scores[h], k_out.T]).astype(operand), u[h])
         for h, (_, _, _, k_out) in enumerate(terms)]
  for h in range(heads):
    t_ref[h] = p.t[h]
    o_ref[:, vcols[h]] = read_s[h][c:] + out[h][:c]
    state_ref[h] = states[h] * p.gates.decay[:, vcols[h]] + out[h][c:]


def _scan_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref,
                     entering_ref, t_ref, dlast_ref, dmixed_ref, dg_ref,
                     dbeta_ref, ds_ref, *, r: int, operand, k_heads: int):
  c = q_ref.shape[0]
  heads = g_ref.shape[0]
  step, group = pl.program_id(1), pl.program_id(2)

  @pl.when(step == 0)
  def _():
    ds_ref[group] = dlast_ref[...]

  d_v = v_ref.shape[1] // heads
  p = _Program(q_ref, k_ref, g_ref, beta_ref, r, d_v, operand, t_ref)
  gates, mm, d_k = p.gates, p.mm, p.d_k
  key, each = heads // r, range(heads)
  cols = [p.cols(h) for h in each]
  vcols = [slice(h * d_v, (h + 1) * d_v) for h in each]
  at = lambda start, width: pl.ds(  # noqa: E731
      pl.multiple_of(start, _LANES), width)
  lane_sum = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
  cat = jnp.concatenate
  # Stage by stage over the heads, as in the forward; dS is read for every
  # head before it is written for any.
  v = [v_ref[:, vcols[h]].astype(jnp.float32) for h in each]
  do = [do_ref[:, vcols[h]] for h in each]
  states = [entering_ref[h] for h in each]
  ds = [ds_ref[group, h] for h in each]
  last = [gates.decay[:, vcols[h]] for h in each]
  w, reads, q_in, k_out = zip(*[p.head(h, v[h]) for h in each])
  u = [w[h] - mm(reads[h], states[h]) for h in each]
  # Through o = (q e^G) S + P u and S_out = e^g S + (k e^(g - G))^T u.
  du = [(mm(p.scores[h], do[h], ss._TN) + mm(k_out[h], ds[h])).astype(
      operand) for h in each]
  d_scores = [mm(do[h], u[h], ss._NT) for h in each]
  d_k_out = [mm(u[h], ds[h], ss._NT) for h in each]
  # dS_in = e^g dS_out + (q e^G)^T dO - R^T du: one product over the
  # chunk's rows twice.
  ds_ref[group] = jnp.stack([ds[h] * last[h] + mm(
      cat([q_in[h].astype(operand), reads[h]]),
      cat([do[h].astype(operand), -du[h]]), ss._TN) for h in each])
  # (dO; du) S^T with S loaded once: d(q e^G) and -dR.
  through_s = [mm(cat([do[h].astype(operand), du[h]]), states[h], ss._NT)
               for h in each]
  # Through W = T (v beta), R = T (k beta e^G), T = (I + A)^-1:
  # d(v beta) and d(k beta e^G) = T^T (dW, dR), and dA = -T^T dT T^T =
  # -(d(v beta) W^T + d(k beta e^G) R^T).
  through_t = [mm(p.t[h], cat([du[h], (-through_s[h][c:]).astype(
      operand)], axis=1), ss._TN) for h in each]
  d_a = [jnp.where(p.strict, -mm(through_t[h], cat(
      [w[h], reads[h].astype(jnp.float32)], axis=1), ss._NT), 0.0)
         for h in each]
  ef = [cat([d_a[h] * p.within[h], d_scores[h] * p.within[h]])
        for h in each]
  through_k = [mm(ef[h], p.k[h // r]) for h in each]
  through_kq = [mm(ef[h], cat([p.k_beta[h], p.q[h // r]]), ss._TN)
                for h in each]
  dq = [jnp.zeros((c, d_k), jnp.float32) for _ in range(key)]
  dk = [jnp.zeros((c, d_k), jnp.float32) for _ in range(key)]
  # Sums over a row's lanes land as columns, head h in lane h, and are
  # turned into rows once; sums down the sublanes are rows already.
  lane = jax.lax.broadcasted_iota(jnp.int32, (c, _LANES), 1)
  first = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
  row = jax.lax.broadcasted_iota(jnp.int32, (heads, c), 0)
  gate_cols = jnp.zeros((c, _LANES), jnp.float32)
  beta_cols = jnp.zeros((c, _LANES), jnp.float32)
  gate_rows = jnp.zeros((heads, c), jnp.float32)
  for h in each:
    j, kc = h // r, cols[h]
    d_v_beta, d_k_grow = through_t[h][:, :d_v], through_t[h][:, d_v:]
    d_q_in = through_s[h][:c]
    d_k_beta = through_k[h][:c] + d_k_grow * gates.grow[:, kc]
    dq[j] = dq[j] + through_k[h][c:] + d_q_in * gates.grow[:, kc]
    dk[j] = (dk[j] + through_kq[h] + d_k_beta * gates.beta_k[:, kc]
             + d_k_out[h] * gates.to_end[:, kc])
    # The gate: dG_i = sum_j M_ij - sum_j M_ji with M = dD o D, and the
    # rows of the e^G and e^(g - G) factors; the last also takes dg.
    m = d_a[h] * p.a[h] + d_scores[h] * p.scores[h]
    ends = d_k_out[h] * k_out[h]
    d_last = jnp.sum(jnp.sum(ds[h] * states[h], axis=0, keepdims=True)
                     * last[h], axis=1, keepdims=True) + jnp.sum(
                         lane_sum(ends), axis=0, keepdims=True)
    gate_h = lane_sum(m) + lane_sum(
        d_k_grow * p.k_beta[h] * gates.grow[:, kc] + d_q_in * q_in[h]
        - ends) + jnp.where(first, d_last, 0.0)
    gate_cols = jnp.where(lane == h, gate_h, gate_cols)
    gate_rows = jnp.where(row == h, -jnp.sum(m, axis=0, keepdims=True),
                          gate_rows)
    beta_cols = jnp.where(lane == h, lane_sum(d_k_beta * p.k[j])
                          + lane_sum(d_v_beta * v[h]), beta_cols)
    v_at = 2 * k_heads * d_k + (group * heads + h) * d_v
    dmixed_ref[:, at(v_at, d_v)] = (
        d_v_beta * gates.beta_v[:, vcols[h]]).astype(dmixed_ref.dtype)
  # dg_j = sum_{i >= j} dG_i: dG L, L the lower 0/1 triangle.
  d_gate = gate_cols.T[:heads] + gate_rows
  dg_ref[...] = ss._exact(d_gate, ss._triangle(c, lower=True))
  dbeta_ref[...] = beta_cols.T[:heads]
  # Through the normalisation, q = s x / |x|: dx = s / |x| (dq - x (x.dq)
  # / |x|^2), the value heads of a key head summed.
  for j in range(key):
    for d_unit, x_ref, scale, start in (
        (dq[j], q_ref, d_k ** -0.5, 0), (dk[j], k_ref, 1.0, k_heads * d_k)):
      x = x_ref[:, j * d_k:(j + 1) * d_k].astype(jnp.float32)
      _, inverse = _unit(x)
      dx = scale * inverse * (d_unit - inverse * inverse * jnp.sum(
          d_unit * x, axis=1, keepdims=True) * x)
      dmixed_ref[:, at(start + (group * key + j) * d_k, d_k)] = dx.astype(
          dmixed_ref.dtype)


def _scan_sizes(mixed, g, plan):
  k_heads, d_k, chunk, heads, _ = plan
  batch, t, width = mixed.shape
  v_heads = g.shape[-1]
  d_v = (width - 2 * k_heads * d_k) // v_heads
  return batch, t, v_heads, d_v, t // chunk, v_heads // heads


def _gates_t(x, chunk):
  """[B, T, H_v] -> [B, T / C, H_v, C] float32: a program's gates as one
  [heads, C] tile."""
  batch, t, h = x.shape
  return x.astype(jnp.float32).reshape(batch, t // chunk, chunk, h).transpose(
      0, 1, 3, 2)


@functools.lru_cache(maxsize=None)
def _traced(fn, plan, interpret: bool, operands):
  """`fn`'s program for operands of these (shape, dtype), traced once and
  run under `jit`. A step calls each kernel under several transformations
  (the primal, its linearisation, the rematerialised forward), whose
  operands carry other abstract meshes, so a `jit` of `fn` itself traced
  the kernels anew for each: about 0.8 ms of the benchmark's set-up an
  equation of a kernel's body (PERF.md section 7). Re-tracing this `jit`
  binds the traced program's few equations; the `jit` keeps the kernels'
  instructions named after them (`%gdn_scan.3`), as the readers find
  them."""
  closed = jax.make_jaxpr(functools.partial(fn, plan=plan,
                                            interpret=interpret))(
      *(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in operands))
  return jax.jit(functools.partial(jax.core.eval_jaxpr, closed.jaxpr,
                                   closed.consts))


def _run(fn, plan, interpret: bool, *args):
  return _traced(fn, plan, interpret,
                 tuple((x.shape, np.dtype(x.dtype)) for x in args))(*args)


def _scan_forward(mixed, g, beta, *, plan, interpret: bool):
  """(o [B, T, H_v d_v] float32, the last state [B, H_v, d_k, d_v], the
  states the chunks entered with [B, H_v, chunks, d_k, d_v] and their T
  [B, H_v, chunks, C, C]). The primal keeps the backward's residuals too
  (0.18 ms a call at the hybrid cell's sizes), so that one forward kernel,
  not two, is traced."""
  k_heads, d_k, chunk, heads, operand = plan
  batch, t, v_heads, d_v, n, groups = _scan_sizes(mixed, g, plan)
  key = heads * k_heads // v_heads
  v_block = 2 * k_heads * d_k // (heads * d_v)
  gate = pl.BlockSpec((None, None, heads, chunk),
                      lambda b, i, s: (b, s, i, 0))
  out_specs = [pl.BlockSpec((None, chunk, heads * d_v),
                            lambda b, i, s: (b, s, i)),
               pl.BlockSpec((None, heads, d_k, d_v),
                            lambda b, i, s: (b, i, 0, 0)),
               pl.BlockSpec((None, heads, None, d_k, d_v),
                            lambda b, i, s: (b, i, s, 0, 0)),
               pl.BlockSpec((None, heads, None, chunk, chunk),
                            lambda b, i, s: (b, i, s, 0, 0))]
  out_shape = [jax.ShapeDtypeStruct((batch, t, v_heads * d_v), jnp.float32),
               jax.ShapeDtypeStruct((batch, v_heads, d_k, d_v), jnp.float32),
               jax.ShapeDtypeStruct((batch, v_heads, n, d_k, d_v),
                                    jnp.float32),
               jax.ShapeDtypeStruct((batch, v_heads, n, chunk, chunk),
                                    operand)]
  return tuple(pl.pallas_call(
      functools.partial(_scan_fwd_kernel, r=v_heads // k_heads,
                        operand=operand),
      grid=(batch, groups, n),
      in_specs=[
          pl.BlockSpec((None, chunk, key * d_k), lambda b, i, s: (b, s, i)),
          pl.BlockSpec((None, chunk, key * d_k),
                       lambda b, i, s: (b, s, groups + i)),
          pl.BlockSpec((None, chunk, heads * d_v),
                       lambda b, i, s: (b, s, v_block + i)),
          gate, gate],
      out_specs=out_specs,
      out_shape=out_shape,
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "arbitrary"),
          vmem_limit_bytes=_VMEM_BYTES),
      interpret=interpret,
      name="gdn_scan",
  )(mixed, mixed, mixed, _gates_t(g, chunk), _gates_t(beta, chunk)))


def _scan_backward(mixed, g, beta, entering, inverses, do, dlast, *, plan,
                   interpret: bool):
  """(d mixed in its dtype, dg, d beta) for o and the last state."""
  k_heads, d_k, chunk, heads, operand = plan
  batch, t, v_heads, d_v, n, groups = _scan_sizes(mixed, g, plan)
  key = heads * k_heads // v_heads
  v_block = 2 * k_heads * d_k // (heads * d_v)
  back = lambda s: n - 1 - s  # noqa: E731
  gate = pl.BlockSpec((None, None, heads, chunk),
                      lambda b, s, i: (b, back(s), i, 0))
  dmixed, dg, dbeta = pl.pallas_call(
      functools.partial(_scan_bwd_kernel, r=v_heads // k_heads,
                        operand=operand, k_heads=k_heads),
      grid=(batch, n, groups),
      in_specs=[
          pl.BlockSpec((None, chunk, key * d_k),
                       lambda b, s, i: (b, back(s), i)),
          pl.BlockSpec((None, chunk, key * d_k),
                       lambda b, s, i: (b, back(s), groups + i)),
          pl.BlockSpec((None, chunk, heads * d_v),
                       lambda b, s, i: (b, back(s), v_block + i)),
          gate, gate,
          pl.BlockSpec((None, chunk, heads * d_v),
                       lambda b, s, i: (b, back(s), i)),
          pl.BlockSpec((None, heads, None, d_k, d_v),
                       lambda b, s, i: (b, i, back(s), 0, 0)),
          pl.BlockSpec((None, heads, None, chunk, chunk),
                       lambda b, s, i: (b, i, back(s), 0, 0)),
          pl.BlockSpec((None, heads, d_k, d_v), lambda b, s, i: (b, i, 0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((None, chunk, mixed.shape[2]),
                       lambda b, s, i: (b, back(s), 0)),
          gate, gate],
      out_shape=[jax.ShapeDtypeStruct(mixed.shape, mixed.dtype)] + [
          jax.ShapeDtypeStruct((batch, n, v_heads, chunk), jnp.float32)] * 2,
      scratch_shapes=[pltpu.VMEM((groups, heads, d_k, d_v), jnp.float32)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary", "arbitrary"),
          vmem_limit_bytes=_VMEM_BYTES),
      interpret=interpret,
      name="gdn_scan_bwd",
  )(mixed, mixed, mixed, _gates_t(g, chunk), _gates_t(beta, chunk),
    do.reshape(batch, t, v_heads * d_v).astype(jnp.float32), entering,
    inverses, dlast.astype(jnp.float32))
  untile = lambda x, like: x.transpose(0, 1, 3, 2).reshape(  # noqa: E731
      batch, t, v_heads).astype(like.dtype)
  return dmixed, untile(dg, g), untile(dbeta, beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rule(mixed, g, beta, plan, interpret):
  return tuple(_run(_scan_forward, plan, interpret, mixed, g, beta)[:2])


def _rule_fwd(mixed, g, beta, plan, interpret):
  o, last, entering, inverses = _run(_scan_forward, plan, interpret, mixed,
                                     g, beta)
  return (o, last), (mixed, g, beta, entering, inverses)


def _rule_bwd(plan, interpret, residuals, cotangents):
  do, dlast = cotangents
  return tuple(_run(_scan_backward, plan, interpret, *residuals, do, dlast))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(mixed, g, beta, k_heads: int, d_k: int,
                     chunk_size: int = 64, matmul_dtype=None,
                     interpret: Optional[bool] = None):
  """The rule of q, k and v as they lie in the convolution's result `mixed`
  [B, T, 2 H_k d_k + H_v d_v]: q of key head j at columns j d_k, its k at
  H_k d_k + j d_k, v of value head h at 2 H_k d_k + h d_v; value head h
  reads key head h // (H_v / H_k). g and beta [B, T, H_v]. Returns (o
  [B, T, H_v, d_v], the last state [B, H_v, d_k, d_v]), float32,
  differentiable in `mixed`, g and beta. The Pallas kernels where
  `_scan_heads` takes the shape, else `gated_delta_rule_chunked` on slices
  of `mixed`, the key heads repeated; `chunk_size` and `matmul_dtype` as
  its. `interpret`: whether the kernels run interpreted (off the TPU) or as
  Mosaic kernels; None follows the lowering platform."""
  batch, t, width = mixed.shape
  v_heads = g.shape[-1]
  d_v = (width - 2 * k_heads * d_k) // v_heads
  heads = _scan_heads(t, k_heads, v_heads, d_k, d_v, int(chunk_size))
  if heads is None:
    key_dim = k_heads * d_k
    q, k = (jnp.repeat(mixed[..., i * key_dim:(i + 1) * key_dim].reshape(
        batch, t, k_heads, d_k), v_heads // k_heads, axis=2) for i in (0, 1))
    v = mixed[..., 2 * key_dim:].reshape(batch, t, v_heads, d_v)
    return gated_delta_rule_chunked(q, k, v, g, beta, chunk_size=chunk_size,
                                    matmul_dtype=matmul_dtype,
                                    interpret=interpret)
  plan = (k_heads, d_k, int(chunk_size), heads,
          np.dtype(matmul_dtype or jnp.float32))
  if interpret is None:
    o, last = jax.lax.platform_dependent(
        mixed, g, beta,
        tpu=lambda *a: _rule(*a, plan, False),
        default=lambda *a: _rule(*a, plan, True))
  else:
    o, last = _rule(mixed, g, beta, plan, bool(interpret))
  return o.reshape(batch, t, v_heads, d_v), last
