"""Grouped matrix products: one op that owns all three directions.

With the rows of `lhs` [rows, K] split into G consecutive groups of
`group_sizes` rows (they add up to `rows`) and `rhs` [G, K, N],

    out[r]  = lhs[r] rhs[g(r)]                       forward, [rows, N]
    dlhs[r] = dout[r] rhs[g(r)]^T                    the rows' cotangent
    drhs[g] = sum over r in g of lhs[r]^T dout[r]    the weights' cotangent

which is what `jax.lax.ragged_dot` and its two transposes compute.
`grouped_matmul` is that op with a `custom_vjp`: operands go to the MXU as
they are handed in (bfloat16 on the training path), sums are float32, the
result is float32, and the cotangent is cast to the weights' dtype on its
way into the two transposed products, as a bfloat16 `nn.Dense` hands its
own back; the cotangents leave in their operands' dtypes, rounded once
from the float32 sum.

Two Pallas kernels, `grouped_matmul` (forward and, with the weight block
read transposed, the rows' cotangent) and `grouped_matmul_t` (the weights'
cotangent). Both walk the (row tile, group) pairs of `_visits`: a tile
that holds rows of several groups is visited once a group and only that
group's rows are stored or summed; group offsets and the two visit lists
are scalar-prefetched, and consecutive tiles of one group keep the weight
block's index, so it is fetched once a group. Every row tile is visited
whatever the sizes are.

What follows the shapes, and on which readings (one v5e chip, PR 36: the
op alone, host-timed; the probe's table is in PERF.md section 6. XLA's
`ragged_dot` took 2.36-4.09 ms a product at the first two shapes below and
0.63-2.09 at the last two; inside the two cells' compiled steps the
kernels read 0.44-0.49 and 0.34-0.71 ms a call in the trace):

* the row tile is 256 where a group has that many rows on average
  (rows / G), else 128. At [6144, 2688] x [8, 2688, 1856],
  [6144, 1856] x [8, 1856, 2688], [20480, 2048] x [32, 2048, 1024] and
  [20480, 512] x [32, 512, 2048], 256 read 0.5-9 % under 128 and 5-17 %
  under XLA's own 512 in all three directions: a group of 80-200 rows
  straddles most 512-row tiles.
* K is whole in every block (no masked last tile, no accumulator in the
  forward kernel) and N is whole where the blocks fit `_VMEM_BUDGET`,
  else the widest multiple of 128 lanes that does; a last N tile past the
  array's edge computes columns that are never stored. Whole N read
  fastest at all four shapes (0.46-0.91 ms against 0.84-1.17 at 256).
* the weights go to the kernels with their lane-aligned dimension last.
  The chip keeps a [G, 2688, 1856] array with 2688 in the lanes (1856 is
  14.5 lane tiles), and a kernel that is handed it with 1856 last gets a
  transposed copy made first: 0.78-0.91 ms a product against 0.52-0.55
  for the same work on [G, 1856, 2688]. So where N is no multiple of 128
  and K is, the kernels take `swapaxes(rhs, 1, 2)`, a relabelling of what
  is already in memory, and the weights' cotangent is computed as
  [G, N, K] and relabelled back.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul"]

_LANES = 128
# What the blocks of one kernel may take of the v5e's 128 MiB of VMEM; the
# kernels state their limit from their own byte counts (`_vmem_limit`).
_VMEM_BUDGET = 64 * 2**20


def _visits(group_sizes, rows: int, tm: int, empty_groups: bool):
  """The (row tile, group) pairs a kernel visits, in order: a tile that
  holds rows of several groups is visited once a group. Returns the
  groups' row offsets [G + 1], the group and the tile of each visit (both
  of the static length rows / tm + G - 1, the most there can be: the
  first group that holds a row starts its tile, every other adds at most
  one visit) and the number of visits. `empty_groups`: a group of no rows
  is visited once all the same (its weights' cotangent has to be
  zeroed)."""
  group_sizes = group_sizes.astype(jnp.int32)
  groups = group_sizes.shape[0]
  tiles = rows // tm
  length = tiles + groups - 1
  ends = jnp.cumsum(group_sizes)
  starts = ends - group_sizes
  first = jnp.minimum(starts // tm, tiles - 1)
  count = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first,
                    int(empty_groups))
  group_ids = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), count,
                         total_repeat_length=length)
  before = jnp.cumsum(count) - count
  tile_ids = jnp.minimum(
      first[group_ids] + jnp.arange(length, dtype=jnp.int32)
      - before[group_ids], tiles - 1)
  offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
  return (offsets, group_ids, tile_ids), jnp.sum(count)


def _rows_of_group(offsets, group_ids, tile_ids, visit, shape):
  """[tm, width] mask: which rows of this visit's tile its group holds."""
  group = group_ids[visit]
  row = tile_ids[visit] * shape[0] + jax.lax.broadcasted_iota(
      jnp.int32, shape, 0)
  return (row >= offsets[group]) & (row < offsets[group + 1])


def _row_tile(rows: int, groups: int) -> int:
  if rows % _LANES:
    raise ValueError(f"grouped_matmul takes a whole number of {_LANES}-row "
                     f"tiles, not {rows} rows")
  return 256 if rows % 256 == 0 and rows // groups >= 256 else _LANES


def _widest_tile(width: int, bytes_of) -> int:
  """`width` whole where `bytes_of(tile)` fits the budget, else the widest
  multiple of 128 lanes that does."""
  for tile in (width, *range((width - 1) // _LANES * _LANES, 0, -_LANES)):
    if bytes_of(tile) <= _VMEM_BUDGET:
      return tile
  raise ValueError("grouped_matmul: no 128-lane block of these operands "
                   f"fits {_VMEM_BUDGET} bytes of VMEM")


def _vmem_limit(block_bytes: int) -> int:
  return min(block_bytes + 16 * 2**20, 100 * 2**20)


def _gmm_kernel(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs: bool):
  """One visit of `grouped_matmul`: a row tile against one group's block,
  the contracted dimension whole; the group's rows of the result are
  stored, the others are left to the visits of their own groups."""
  visit = pl.program_id(1)
  product = jax.lax.dot_general(
      lhs_ref[...], rhs_ref[...],
      (((1,), (1 if transpose_rhs else 0,)), ((), ())),
      preferred_element_type=jnp.float32)
  inside = _rows_of_group(offsets, group_ids, tile_ids, visit, product.shape)
  out_ref[...] = jnp.where(
      inside, product, out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gmm(lhs, rhs, group_sizes, out_dtype, transpose_rhs: bool,
         interpret: bool):
  """lhs [rows, K] x rhs [G, K, N] -> [rows, N] by group; with
  `transpose_rhs`, rhs is [G, N, K] and is read transposed. Under `jit`
  so that a step's many calls of one shape trace the kernel once."""
  rows, k = lhs.shape
  groups = rhs.shape[0]
  n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
  tm = _row_tile(rows, groups)
  out_size = jnp.dtype(out_dtype).itemsize

  def block_bytes(tn):  # every block is double-buffered
    return 2 * (tm * k * lhs.dtype.itemsize + k * tn * rhs.dtype.itemsize
                + tm * tn * out_size)

  tn = _widest_tile(n, block_bytes)
  metadata, visits = _visits(group_sizes, rows, tm, empty_groups=False)
  if transpose_rhs:
    rhs_spec = pl.BlockSpec((None, tn, k), lambda j, v, o, g, t: (g[v], j, 0))
  else:
    rhs_spec = pl.BlockSpec((None, k, tn), lambda j, v, o, g, t: (g[v], 0, j))
  return pl.pallas_call(
      functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
      out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=3,
          in_specs=[
              pl.BlockSpec((tm, k), lambda j, v, o, g, t: (t[v], 0)),
              rhs_spec,
          ],
          out_specs=pl.BlockSpec((tm, tn), lambda j, v, o, g, t: (t[v], j)),
          grid=(pl.cdiv(n, tn), visits)),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary"),
          vmem_limit_bytes=_vmem_limit(block_bytes(tn))),
      interpret=interpret,
      name="grouped_matmul",
  )(*metadata, lhs, rhs)


def _tgmm_kernel(offsets, group_ids, tile_ids, lhs_ref, rhs_ref, out_ref,
                 acc_ref):
  """One visit of `grouped_matmul_t`: a row tile's share of its group's
  block of lhs^T rhs; the block is stored when the group's visits end."""
  visit = pl.program_id(1)
  last = pl.num_programs(1) - 1
  group = group_ids[visit]

  @pl.when((visit == 0) | (group_ids[jnp.maximum(visit - 1, 0)] != group))
  def _():
    acc_ref[...] = jnp.zeros_like(acc_ref)

  lhs, rhs = lhs_ref[...], rhs_ref[...]
  # Rows of other groups are zeroed in the narrower operand.
  if lhs.shape[1] < rhs.shape[1]:
    lhs = jnp.where(
        _rows_of_group(offsets, group_ids, tile_ids, visit, lhs.shape),
        lhs, jnp.zeros_like(lhs))
  else:
    rhs = jnp.where(
        _rows_of_group(offsets, group_ids, tile_ids, visit, rhs.shape),
        rhs, jnp.zeros_like(rhs))
  acc_ref[...] += jax.lax.dot_general(
      lhs, rhs, (((0,), (0,)), ((), ())),
      preferred_element_type=jnp.float32)

  @pl.when((visit == last)
           | (group_ids[jnp.minimum(visit + 1, last)] != group))
  def _():
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _tgmm(lhs, rhs, group_sizes, out_dtype, interpret: bool):
  """lhs [rows, K], rhs [rows, N] -> [G, K, N]: lhs^T rhs over each
  group's rows. Under `jit` as `_gmm` is."""
  rows, k = lhs.shape
  n = rhs.shape[1]
  groups = group_sizes.shape[0]
  tm = _row_tile(rows, groups)
  out_size = jnp.dtype(out_dtype).itemsize

  def block_bytes(tn):  # a float32 accumulator beside the buffered blocks
    return k * tn * 4 + 2 * (tm * k * lhs.dtype.itemsize
                             + tm * tn * rhs.dtype.itemsize
                             + k * tn * out_size)

  tn = _widest_tile(n, block_bytes)
  metadata, visits = _visits(group_sizes, rows, tm, empty_groups=True)
  return pl.pallas_call(
      _tgmm_kernel,
      out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
      grid_spec=pltpu.PrefetchScalarGridSpec(
          num_scalar_prefetch=3,
          in_specs=[
              pl.BlockSpec((tm, k), lambda j, v, o, g, t: (t[v], 0)),
              pl.BlockSpec((tm, tn), lambda j, v, o, g, t: (t[v], j)),
          ],
          out_specs=pl.BlockSpec((None, k, tn),
                                 lambda j, v, o, g, t: (g[v], 0, j)),
          grid=(pl.cdiv(n, tn), visits),
          scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)]),
      compiler_params=pltpu.CompilerParams(
          dimension_semantics=("parallel", "arbitrary"),
          vmem_limit_bytes=_vmem_limit(block_bytes(tn))),
      interpret=interpret,
      name="grouped_matmul_t",
  )(*metadata, lhs, rhs)


def _lanes_last(rhs):
  """(the weights as the kernels take them, whether that is [G, N, K]):
  the lane-aligned one of K and N last (the module's docstring)."""
  _, k, n = rhs.shape
  swapped = n % _LANES != 0 and k % _LANES == 0
  return (jnp.swapaxes(rhs, 1, 2) if swapped else rhs), swapped


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(lhs, rhs, group_sizes, interpret):
  weights, swapped = _lanes_last(rhs)
  return _gmm(lhs, weights, group_sizes, jnp.float32, swapped, interpret)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, interpret):
  return (_grouped_matmul(lhs, rhs, group_sizes, interpret),
          (lhs, rhs, group_sizes))


def _grouped_matmul_bwd(interpret, residuals, cotangent):
  lhs, rhs, group_sizes = residuals
  cotangent = cotangent.astype(rhs.dtype)
  weights, swapped = _lanes_last(rhs)
  dlhs = _gmm(cotangent, weights, group_sizes, lhs.dtype, not swapped,
              interpret)
  if swapped:
    drhs = jnp.swapaxes(
        _tgmm(cotangent, lhs, group_sizes, rhs.dtype, interpret), 1, 2)
  else:
    drhs = _tgmm(lhs, cotangent, group_sizes, rhs.dtype, interpret)
  return dlhs, drhs, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret: Optional[bool] = None):
  """lhs [rows, K] x rhs [G, K, N] -> float32 [rows, N], row r against the
  weights of the group that holds it; `group_sizes` int32 [G] adds up to
  `rows`, a multiple of 128. Differentiable in `lhs` and `rhs` (cotangents
  in their dtypes). `interpret`: whether the kernels run interpreted (off
  the TPU) or as Mosaic kernels; None follows the lowering platform, as
  `linear_attention._inverse_of_unit_lower` does."""
  if interpret is None:
    return jax.lax.platform_dependent(
        lhs, rhs, group_sizes,
        tpu=lambda *xs: _grouped_matmul(*xs, False),
        default=lambda *xs: _grouped_matmul(*xs, True))
  return _grouped_matmul(lhs, rhs, group_sizes, bool(interpret))
