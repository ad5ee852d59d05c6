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
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta_rule_recurrent", "gated_delta_rule_chunked"]

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
  """One program of `gdn_inverse`: a chunk's group of heads, [G, C, C].

  The same doubling product as `_doubling_inverse`, laid out for the MXU:
  `side` heads lie side by side in the lanes of one [C, side x C] tile,
  and the tile meets the heads' powers laid block-diagonally,
  [side x C, side x C] (the tile's own rows, masked to one head's lanes at
  a time, one below the other: selects and a sublane concatenation, no
  lane moves). The blocks off the diagonal are exact zeros, so every
  product is the [C, C] product to the bit, and at C 64 it streams 64
  rows through a whole 128 x 128 MXU tile for two heads where two
  [64, 64] products stream 128 through a quarter of it. `together` tiles
  make one batched product: the chain is log2(C) levels deep, and the
  MXU interleaves independent products."""
  group, c, _ = a_ref.shape
  width = side * c
  lane = jax.lax.broadcasted_iota(jnp.int32, (c, width), 1)
  row = jax.lax.broadcasted_iota(jnp.int32, (c, width), 0)
  eye = (row == lane % c).astype(jnp.float32)

  def block_diagonal(x):  # [together, C, side x C] -> [.., side x C, side x C]
    return jnp.concatenate(
        [jnp.where(lane // c == head, x, 0.0) for head in range(side)],
        axis=1)

  def product(x, y):
    return jnp.einsum("tiw,twv->tiv", x, y, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)

  for first in range(0, group, side * together):
    power = jnp.stack([
        jnp.concatenate([-a_ref[first + tile * side + head]
                         for head in range(side)], axis=1)
        for tile in range(together)])
    inverse = eye + power
    diagonal = block_diagonal(power)
    covered = 2
    while covered < c:
      power = product(power, diagonal)
      diagonal = block_diagonal(power)
      inverse = inverse + product(inverse, diagonal)
      covered *= 2
    for tile in range(together):
      for head in range(side):
        t_ref[first + tile * side + head] = inverse[
            tile, :, head * c:(head + 1) * c]


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
