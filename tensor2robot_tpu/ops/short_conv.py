"""The two linear mixers' short convolution: a causal depthwise convolution
of width w over time, a bias and SiLU, as one op with its own backward.

With x the C channels [B, T, C] that start at column `start` of the
operand (the mixers' in-projection result: columns 0:8192 of
[1, 4096, 12288] in `GatedDeltaNet`, 4096:10240 of [1, 4096, 10304] in
`Mamba2Mixer`), taps k [w, C] and an optional bias b [C],

    m[t]  = sum_j x[t - (w-1) + j] k_j + b       (x before row 0 is 0)
    y[t]  = silu(m[t])
    dm    = dy silu'(m)
    dx[s] = sum_j dm[s + (w-1) - j] k_j          (dm past row T-1 is 0)
    dk_j  = sum_t x[t - (w-1) + j] dm[t],   db = sum_t dm[t]

(the public `qwen3_next` and `nemotron_h` modelling code's `conv1d` with
`groups=channels`, `padding=w-1`, cut to T rows). Everything is float32
from the operands as handed in; y and dx leave in x's dtype, dk and db in
the taps' and the bias's, each rounded once from its float32 sum.

`causal_conv_silu` is that op with a `custom_vjp` over two Pallas kernels,
where `_kernel_takes` the shape:

* `short_conv` (forward): grid (B, T tiles, channel blocks), all parallel.
  It reads its block of x in place, as a column block of the whole operand
  (no slice of the operand is ever written), and the w-1 rows before the
  tile from a second block of the same operand (the 8 rows before it;
  zeros before row 0), sums the taps in float32 in the XLA expression's
  order (tap 0 first, then the bias) and writes y once.
* `short_conv_bwd` (backward): grid (channel blocks, B, T tiles), the last
  two an accumulation. It reads x in place with the 8 rows before and
  after the tile, dy with the 8 rows after it, recomputes m and dm,
  writes dx once, and sums dk and db into float32 scratch that is
  written at the channel block's last tile. So x and dy are read once and
  dx written once. The operand's cotangent is dx padded to its width: in
  both steps XLA reads the pad inside the in-projection's two backward
  products (described v5e), as it did the slice's.

An offset that is no multiple of 128 (the nemotron rehearsal's 64) hands
the kernels the slice. Other shapes keep `_conv_silu_xla`, the expression
the mixers ran before (PR 38), which the tests hold the kernels to.

What follows the shapes, and on which readings (one v5e chip, PR 38: the op
alone at the two cells' shapes, device time of the kernels in a trace; XLA's
expression 1.14 / 0.35 ms forward and 3.38 / 2.98 ms recomputed forward +
backward a layer at qwen3next / nemotron, PERF.md section 6):

* blocks of 512 rows by 1024 channels (the largest that divide the shape;
  256 x 1024, 512 x 512, 1024 x 512 and 256 x 2048 read within 10 %),
  double-buffered in the default scoped VMEM;
* inside a block, a `fori_loop` over strips of 64 rows by 256 lanes, the
  w-1 rows before a strip carried from the last, so that every value of
  the loop's body stays a few vregs. The whole block as one value read
  0.40 / 0.93 ms (forward / backward kernel at qwen3next's shape), strips
  of 16 x 512 0.32 / 0.76, 32 x 512 0.26 / 0.66, 64 x 256 0.245 / 0.57
  (nemotron 0.195 / 0.43). Their bytes take 0.16 and 0.25 ms at 819
  GB/s; that no block size moved them and the loop's shape did says the
  vector unit is the bound (the taps, the sigmoid and, in the backward,
  dm, dx and the five column sums), which no counter reads.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["causal_conv_silu"]

_LANES = 128
# Rows of the neighbouring blocks read beside a tile (one float32 sublane
# tile): the kernels take widths up to _HALO + 1.
_HALO = 8
# The largest row tile and channel block.
_MAX_ROWS = 512
_MAX_CHANNELS = 1024
# The rows and lanes a loop step of the kernels works on (the module's
# docstring).
_STRIP_ROWS = 64
_STRIP_LANES = 256


def _conv_silu_xla(x, kernel, bias=None):
  """silu(causal depthwise convolution of x [B, T, C] with `kernel`
  [width, C], plus `bias` [C]), in float32, returned in x's dtype."""
  width, t = kernel.shape[0], x.shape[1]
  padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
  taps = kernel.astype(jnp.float32)
  mixed = sum(padded[:, j:j + t] * taps[j] for j in range(width))
  if bias is not None:
    mixed = mixed + bias.astype(jnp.float32)
  return jax.nn.silu(mixed).astype(x.dtype)


def _row_quantum(dtype) -> int:
  """A row tile's multiple: a whole sublane tile of x's dtype."""
  return _HALO * max(1, 4 // jnp.dtype(dtype).itemsize)


def _kernel_takes(shape, dtype, width: int, channels: int) -> bool:
  """[B, T, C] with whole 128-lane channel tiles, whole sublane tiles of
  rows, and the w-1 rows a tile needs inside the 8 beside it."""
  return (len(shape) == 3 and channels % _LANES == 0
          and shape[1] % _row_quantum(dtype) == 0 and width - 1 <= _HALO)


def _taps(shifted, w, width: int, bias=None):
  """sum_j shifted(width - 1 - j) k_j (+ b), tap 0 first."""
  acc = None
  for j in range(width):
    term = shifted(width - 1 - j) * w[j:j + 1]
    acc = term if acc is None else acc + term
  return acc if bias is None else acc + bias


def _plan(t: int, channels: int, start: int, dtype):
  """(row tile, channel block, strip rows, strip lanes): the largest
  multiple of the row quantum that divides T, up to _MAX_ROWS; the largest
  128-lane multiple that divides both the channel count and the offset,
  up to _MAX_CHANNELS; the largest multiple of the row quantum up to
  _STRIP_ROWS that divides the tile, by up to _STRIP_LANES lanes."""
  quantum = _row_quantum(dtype)
  rows = max(r for r in range(quantum, min(t, _MAX_ROWS) + 1, quantum)
             if t % r == 0)
  common = math.gcd(channels, start) if start else channels
  block = max(c for c in range(_LANES, min(common, _MAX_CHANNELS) + 1,
                               _LANES) if common % c == 0)
  strip = max(r for r in range(quantum, max(quantum, _STRIP_ROWS) + 1,
                               quantum) if rows % r == 0)
  return rows, block, strip, math.gcd(block, _STRIP_LANES)


def _fwd_kernel(x_ref, before_ref, w_ref, *rest, width: int, strip: int,
                chunk: int):
  b_ref, y_ref = rest if len(rest) == 2 else (None, rest[0])
  rows, lanes = x_ref.shape
  first_tile = pl.program_id(1) == 0

  def lane_chunk(c, _):
    cols = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    w = w_ref[:, cols].astype(jnp.float32)
    bias = None if b_ref is None else b_ref[:, cols].astype(jnp.float32)

    def row_strip(k, prev):
      at = pl.ds(pl.multiple_of(k * strip, strip), strip)
      cur = x_ref[at, cols].astype(jnp.float32)
      xx = jnp.concatenate([prev, cur], axis=0)
      mixed = _taps(lambda s: xx[_HALO - s:_HALO - s + strip], w, width,
                    bias)
      y_ref[at, cols] = jax.nn.silu(mixed).astype(y_ref.dtype)
      return cur[strip - _HALO:]

    before = jnp.where(first_tile, 0.0,
                       before_ref[:, cols].astype(jnp.float32))
    jax.lax.fori_loop(0, rows // strip, row_strip, before)
    return 0

  jax.lax.fori_loop(0, lanes // chunk, lane_chunk, 0)


def _fold(x):
  """[R, L] -> [8, L]: the sum of its 8-row tiles (no sublane reduce)."""
  out = x[:_HALO]
  for r in range(_HALO, x.shape[0], _HALO):
    out = out + x[r:r + _HALO]
  return out


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                *rest, width: int, strip: int, chunk: int):
  if len(rest) == 6:
    b_ref, dx_ref, dw_ref, db_ref, acc_w, acc_b = rest
  else:
    (dx_ref, dw_ref, acc_w, acc_b), b_ref, db_ref = rest, None, None
  rows, lanes = x_ref.shape
  batch, tile = pl.program_id(1), pl.program_id(2)
  last_tile = pl.num_programs(2) - 1

  @pl.when((batch == 0) & (tile == 0))
  def _():
    acc_w[...] = jnp.zeros_like(acc_w)
    acc_b[...] = jnp.zeros_like(acc_b)

  def lane_chunk(c, _):
    cols = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
    w = w_ref[:, cols].astype(jnp.float32)
    bias = None if b_ref is None else b_ref[:, cols].astype(jnp.float32)

    def grad_of_mixed(prev, cur, dy):
      """dm for the rows of `cur` (x's rows before them in `prev`), and
      x shifted by 0..width-1 rows over them."""
      xx = jnp.concatenate([prev, cur], axis=0)
      n = cur.shape[0]
      shifted = [xx[_HALO - s:_HALO - s + n] for s in range(width)]
      mixed = _taps(lambda s: shifted[s], w, width, bias)
      gate = jax.nn.sigmoid(mixed)
      return dy * (gate * (1.0 + mixed * (1.0 - gate))), shifted

    def write_dx(k, dm, dm_next):
      """dx of strip k from its dm and the 8 rows of dm after it."""
      dd = jnp.concatenate([dm, dm_next], axis=0)
      dx = _taps(lambda s: dd[s:s + strip], w, width)
      at = pl.ds(pl.multiple_of(k * strip, strip), strip)
      dx_ref[at, cols] = dx.astype(dx_ref.dtype)

    def row_strip(k, carry):
      prev, dm_prev, sums = carry
      at = pl.ds(pl.multiple_of(k * strip, strip), strip)
      cur = x_ref[at, cols].astype(jnp.float32)
      dm, shifted = grad_of_mixed(prev, cur, dy_ref[at, cols].astype(
          jnp.float32))

      @pl.when(k > 0)
      def _():
        write_dx(k - 1, dm_prev, dm[:_HALO])

      sums = tuple(a + _fold(x * dm) for a, x in zip(sums, shifted)) + (
          sums[-1] + _fold(dm),)
      return cur[strip - _HALO:], dm, sums

    before = jnp.where(tile == 0, 0.0,
                       before_ref[:, cols].astype(jnp.float32))
    zeros = jnp.zeros((_HALO, chunk), jnp.float32)
    prev, dm_last, sums = jax.lax.fori_loop(
        0, rows // strip, row_strip,
        (before, jnp.zeros((strip, chunk), jnp.float32),
         (zeros,) * (width + 1)))
    dy_after = jnp.where(tile == last_tile, 0.0,
                         dy_after_ref[:, cols].astype(jnp.float32))
    dm_after, _ = grad_of_mixed(
        prev, after_ref[:, cols].astype(jnp.float32), dy_after)
    write_dx(rows // strip - 1, dm_last, dm_after)
    for s in range(width):
      j = width - 1 - s
      acc_w[j:j + 1, cols] += jnp.sum(sums[s], axis=0, keepdims=True)
    acc_b[:, cols] += jnp.sum(sums[width], axis=0, keepdims=True)
    return 0

  jax.lax.fori_loop(0, lanes // chunk, lane_chunk, 0)

  @pl.when((batch == pl.num_programs(1) - 1) & (tile == last_tile))
  def _():
    dw_ref[...] = acc_w[:width].astype(dw_ref.dtype)
    if db_ref is not None:
      db_ref[...] = acc_b[...].astype(db_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _forward(x, kernel, bias, start: int, plan, interpret: bool):
  """y [B, T, C] from columns start:start + C of x. Under `jit` so that a
  step's many calls of one shape trace the kernel once."""
  b, t, _ = x.shape
  width, channels = kernel.shape
  rows, block, strip, chunk = plan
  first, per = start // block, rows // _HALO
  in_specs = [
      pl.BlockSpec((None, rows, block), lambda n, i, c: (n, i, first + c)),
      pl.BlockSpec((None, _HALO, block),
                   lambda n, i, c: (n, jnp.maximum(i * per - 1, 0),
                                    first + c)),
      pl.BlockSpec((width, block), lambda n, i, c: (0, c)),
  ]
  operands = [x, x, kernel]
  if bias is not None:
    in_specs.append(pl.BlockSpec((1, block), lambda n, i, c: (0, c)))
    operands.append(bias.reshape(1, channels))
  return pl.pallas_call(
      functools.partial(_fwd_kernel, width=width, strip=strip, chunk=chunk),
      grid=(b, t // rows, channels // block),
      in_specs=in_specs,
      out_specs=pl.BlockSpec((None, rows, block), lambda n, i, c: (n, i, c)),
      out_shape=jax.ShapeDtypeStruct((b, t, channels), x.dtype),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "parallel", "parallel")),
      interpret=interpret,
      name="short_conv",
  )(*operands)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _backward(x, kernel, bias, dy, start: int, plan, interpret: bool):
  """(dx [B, T, C], dk, db or None) for y = the forward's."""
  b, t, _ = x.shape
  width, channels = kernel.shape
  rows, block, strip, chunk = plan
  first, per, halos = start // block, rows // _HALO, t // _HALO
  before = lambda c, n, i: (  # noqa: E731
      n, jnp.maximum(i * per - 1, 0), first + c)
  after = lambda i: jnp.minimum((i + 1) * per, halos - 1)  # noqa: E731
  in_specs = [
      pl.BlockSpec((None, rows, block), lambda c, n, i: (n, i, first + c)),
      pl.BlockSpec((None, _HALO, block), before),
      pl.BlockSpec((None, _HALO, block),
                   lambda c, n, i: (n, after(i), first + c)),
      pl.BlockSpec((None, rows, block), lambda c, n, i: (n, i, c)),
      pl.BlockSpec((None, _HALO, block), lambda c, n, i: (n, after(i), c)),
      pl.BlockSpec((width, block), lambda c, n, i: (0, c)),
  ]
  operands = [x, x, x, dy, dy, kernel]
  out_specs = [pl.BlockSpec((None, rows, block), lambda c, n, i: (n, i, c)),
               pl.BlockSpec((width, block), lambda c, n, i: (0, c))]
  out_shape = [jax.ShapeDtypeStruct((b, t, channels), x.dtype),
               jax.ShapeDtypeStruct((width, channels), kernel.dtype)]
  if bias is not None:
    in_specs.append(pl.BlockSpec((1, block), lambda c, n, i: (0, c)))
    operands.append(bias.reshape(1, channels))
    out_specs.append(pl.BlockSpec((1, block), lambda c, n, i: (0, c)))
    out_shape.append(jax.ShapeDtypeStruct((1, channels), bias.dtype))
  grads = pl.pallas_call(
      functools.partial(_bwd_kernel, width=width, strip=strip, chunk=chunk),
      grid=(channels // block, b, t // rows),
      in_specs=in_specs,
      out_specs=out_specs,
      out_shape=out_shape,
      scratch_shapes=[pltpu.VMEM((_HALO, block), jnp.float32),
                      pltpu.VMEM((1, block), jnp.float32)],
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary", "arbitrary")),
      interpret=interpret,
      name="short_conv_bwd",
  )(*operands)
  if bias is None:
    return grads[0], grads[1], None
  return grads[0], grads[1], grads[2].reshape(channels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, kernel, bias, start, interpret):
  plan = _plan(x.shape[1], kernel.shape[1], start, x.dtype)
  return _forward(x, kernel, bias, start, plan, interpret)


def _conv_fwd(x, kernel, bias, start, interpret):
  return _conv(x, kernel, bias, start, interpret), (x, kernel, bias)


def _conv_bwd(start, interpret, residuals, dy):
  x, kernel, bias = residuals
  plan = _plan(x.shape[1], kernel.shape[1], start, x.dtype)
  dx, dk, db = _backward(x, kernel, bias, dy.astype(x.dtype), start, plan,
                         interpret)
  channels = kernel.shape[1]
  pad = ((0, 0), (0, 0), (start, x.shape[2] - start - channels))
  return jnp.pad(dx, pad), dk, db


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_silu(x, kernel, bias=None, start: int = 0,
                     interpret: Optional[bool] = None):
  """silu(causal depthwise convolution + bias) of columns start:start + C
  of x [B, T, >= start + C], C = kernel.shape[1]; kernel [width, C], bias
  [C] or None. Returns [B, T, C] in x's dtype; differentiable in all
  three (the cotangent of x is zero outside the columns read). The Pallas
  kernels where `_kernel_takes` the shape, else `_conv_silu_xla`.
  `interpret`: whether the kernels run interpreted (off the TPU) or as
  Mosaic kernels; None follows the lowering platform."""
  width, channels = kernel.shape
  if not _kernel_takes(x.shape, x.dtype, width, channels):
    return _conv_silu_xla(x[..., start:start + channels], kernel, bias)
  if start % _LANES:  # no 128-lane block starts there: the slice
    x, start = x[..., start:start + channels], 0
  if interpret is None:
    return jax.lax.platform_dependent(
        x, kernel, bias,
        tpu=lambda *a: _conv(*a, start, False),
        default=lambda *a: _conv(*a, start, True))
  return _conv(x, kernel, bias, start, bool(interpret))
