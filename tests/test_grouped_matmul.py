"""`ops/grouped_matmul.py` against a plain loop over the groups (float32
products at `highest`), forward and both cotangents, kernels interpreted."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensor2robot_tpu.ops import grouped_matmul as gm

_HIGHEST = jax.lax.Precision.HIGHEST

# (K, N): on and off the 128-lane tile; 116 is 1,856 / 16. Where N is off
# it and K on it the kernels take the weights as [G, N, K].
WIDTHS = [(64, 116), (116, 128), (128, 116), (128, 128), (232, 64),
          (128, 232)]
GROUPS = {
    "an_empty_group": [100, 0, 156, 256],
    "a_group_over_four_tiles": [10, 400, 60, 42],
    "the_empty_rows_in_the_last_group": [30, 50, 20, 412],
    "one_group_holds_everything": [0, 512, 0, 0],
    "a_row_tile_of_256": [300, 0, 424, 300],
}


def _per_group(sizes, product):
  out, start = [], 0
  for group, size in enumerate(sizes):
    out.append(product(group, slice(start, start + size)))
    start += size
  return out


def _reference(lhs, rhs, sizes, cotangent):
  """(out, dlhs, drhs) by a loop over the groups, float32 at `highest`;
  the cotangent rounded to the weights' dtype first, as the op does."""
  lhs32, rhs32 = lhs.astype(jnp.float32), rhs.astype(jnp.float32)
  cot = cotangent.astype(rhs.dtype).astype(jnp.float32)
  dot = functools.partial(jnp.dot, precision=_HIGHEST)
  out = jnp.concatenate(_per_group(
      sizes, lambda g, rows: dot(lhs32[rows], rhs32[g])))
  dlhs = jnp.concatenate(_per_group(
      sizes, lambda g, rows: dot(cot[rows], rhs32[g].T)))
  drhs = jnp.stack(_per_group(
      sizes, lambda g, rows: dot(lhs32[rows].T, cot[rows])))
  return out, dlhs, drhs


@jax.jit
def _op_and_cotangents(lhs, rhs, sizes, cotangent):
  out, vjp = jax.vjp(
      lambda a, b: gm.grouped_matmul(a, b, sizes, interpret=True), lhs, rhs)
  return (out,) + vjp(cotangent)


def _operands(k, n, sizes, dtype, seed=0):
  keys = jax.random.split(jax.random.PRNGKey(seed), 3)
  rows = sum(sizes)
  lhs = jax.random.normal(keys[0], (rows, k), jnp.float32).astype(dtype)
  rhs = jax.random.normal(keys[1], (len(sizes), k, n),
                          jnp.float32).astype(dtype)
  cotangent = jax.random.normal(keys[2], (rows, n), jnp.float32)
  return lhs, rhs, jnp.asarray(sizes, jnp.int32), cotangent


def _assert_close(got, want):
  """Within 1e-5 of the largest entry; a bfloat16 cotangent is the float32
  sum rounded once, 2^-9 of an entry."""
  tolerance = 1e-5 if got.dtype == jnp.float32 else 2.0 ** -8
  gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
  assert gap <= tolerance * float(jnp.max(jnp.abs(want))), (gap, tolerance)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("groups", list(GROUPS))
@pytest.mark.parametrize("k,n", WIDTHS)
def test_the_three_products_against_a_loop_over_the_groups(k, n, groups,
                                                           dtype):
  sizes = GROUPS[groups]
  lhs, rhs, group_sizes, cotangent = _operands(k, n, sizes, dtype)
  got = _op_and_cotangents(lhs, rhs, group_sizes, cotangent)
  want = _reference(lhs, rhs, sizes, cotangent)
  for g, w in zip(got, want):
    assert g.shape == w.shape
    _assert_close(g, w)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_results_are_float32_and_cotangents_have_the_operands_dtypes(dtype):
  lhs, rhs, group_sizes, cotangent = _operands(128, 116, GROUPS[
      "an_empty_group"], dtype)
  out, dlhs, drhs = _op_and_cotangents(lhs, rhs, group_sizes, cotangent)
  assert out.dtype == jnp.float32
  assert dlhs.dtype == lhs.dtype == dtype and dlhs.shape == lhs.shape
  assert drhs.dtype == rhs.dtype == dtype and drhs.shape == rhs.shape


@pytest.fixture
def vmem_budget(monkeypatch):
  """Sets `_VMEM_BUDGET` for a test; the kernels' callers are under `jit`,
  which does not see a module global change, so their caches are dropped
  before and after."""
  def drop():
    gm._gmm.clear_cache()
    gm._tgmm.clear_cache()

  def set_budget(nbytes):
    monkeypatch.setattr(gm, "_VMEM_BUDGET", nbytes)
    drop()

  yield set_budget
  drop()


@pytest.mark.parametrize("product", ["forward", "rows_cotangent",
                                     "weights_cotangent"])
def test_a_width_tiled_to_fit_the_budget(product, vmem_budget):
  """Blocks that do not fit `_VMEM_BUDGET` whole are cut into 128-lane
  tiles (two of 256 and four of 128 here), the last of them past the edge
  of a width of 488."""
  vmem_budget(700 * 1024)
  sizes = GROUPS["a_group_over_four_tiles"]
  lhs, rhs, group_sizes, cotangent = _operands(128, 488, sizes, jnp.float32)
  out, _, drhs = _reference(lhs, rhs, sizes, cotangent)
  if product == "forward":
    got, want = gm._gmm(lhs, rhs, group_sizes, jnp.float32, False, True), out
  elif product == "rows_cotangent":
    # [rows, 128] against [G, 488, 128] read transposed -> [rows, 488]
    got = gm._gmm(lhs, jnp.swapaxes(rhs, 1, 2), group_sizes, jnp.float32,
                  True, True)
    want = out
  else:
    got, want = gm._tgmm(lhs, cotangent, group_sizes, jnp.float32, True), drhs
  _assert_close(got, want)


def test_operands_that_fit_no_block_are_refused(vmem_budget):
  vmem_budget(64 * 1024)
  lhs, rhs, group_sizes, _ = _operands(128, 488, GROUPS["an_empty_group"],
                                       jnp.float32)
  with pytest.raises(ValueError, match="VMEM"):
    gm.grouped_matmul(lhs, rhs, group_sizes, interpret=True)


@pytest.mark.parametrize("sizes", list(GROUPS.values()) + [[0, 0, 0, 128]],
                         ids=list(GROUPS) + ["one_tile"])
@pytest.mark.parametrize("empty_groups", [False, True])
def test_visits_cover_every_row_of_every_group_once(sizes, empty_groups):
  rows, tm = sum(sizes), gm._row_tile(sum(sizes), len(sizes))
  (offsets, group_ids, tile_ids), visits = gm._visits(
      jnp.asarray(sizes, jnp.int32), rows, tm, empty_groups)
  visits = int(visits)
  assert len(group_ids) == len(tile_ids) == rows // tm + len(sizes) - 1
  assert visits <= len(group_ids)
  seen = np.zeros(rows, np.int32)
  for group, tile in zip(np.asarray(group_ids)[:visits],
                         np.asarray(tile_ids)[:visits]):
    row = np.arange(tile * tm, (tile + 1) * tm)
    seen[row[(row >= offsets[group]) & (row < offsets[group + 1])]] += 1
  assert (seen == 1).all()
  # every row tile is visited, and a group's visits are consecutive
  assert set(np.asarray(tile_ids)[:visits]) == set(range(rows // tm))
  visited = list(np.asarray(group_ids)[:visits])
  assert visited == sorted(visited)
  want = {g for g, size in enumerate(sizes) if size or empty_groups}
  assert set(visited) == want


def test_rows_that_are_no_whole_number_of_tiles_are_refused():
  lhs, rhs, _, _ = _operands(64, 64, [100, 100], jnp.float32)
  with pytest.raises(ValueError, match="128-row"):
    gm.grouped_matmul(lhs, rhs, jnp.asarray([100, 100], jnp.int32),
                      interpret=True)


def test_default_interpret_follows_the_platform():
  """Off the TPU the op runs by itself (interpreted), under `jit` and
  `grad`, as the layer calls it."""
  sizes = GROUPS["an_empty_group"]
  lhs, rhs, group_sizes, cotangent = _operands(64, 116, sizes, jnp.float32)
  got = jax.jit(jax.grad(
      lambda a, b: jnp.sum(gm.grouped_matmul(a, b, group_sizes) * cotangent),
      argnums=(0, 1)))(lhs, rhs)
  _, dlhs, drhs = _reference(lhs, rhs, sizes, cotangent)
  _assert_close(got[0], dlhs)
  _assert_close(got[1], drhs)
