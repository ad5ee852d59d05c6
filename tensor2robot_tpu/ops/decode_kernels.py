"""graftkern: Pallas decode-tick kernels — fused cached-attention +
arena gather/append/scatter for O(1) session ticks (ISSUE 20).

The session decode tick is the innermost serving loop: every robot pays
it at control frequency. Up to PR 19 it was XLA-default — the reference
re-ran a SavedModel end to end per control tick
(/root/reference/predictors/exported_savedmodel_predictor.py:53-359,
/root/reference/policies/policies.py:188-218 thread recurrent state
host-side), and this repo's `SessionEngine` replaced that with an O(1)
tick whose attention still materializes a masked softmax over the FULL
[B, Tmax] horizon (`ops.attention.cached_attention`) and whose dispatch
round-trips gather -> decode -> scatter as three HBM passes over the
arena KV leaves (`serving/session.py decode_dispatch`). This module is
the Pallas tier that collapses both (PAPER.md §0 scopes Pallas as the
native-code tier; PAPERS.md arXiv:2603.09555's compiler-first O(1)
autoregressive caching is the blueprint):

* `fused_decode_attention` — ONE `pl.pallas_call` per arena KV leaf
  family: for each lane it streams the session's own K/V blocks out of
  the arena AT THE LANE'S SLOT (scalar-prefetched slot indices steer
  the BlockSpec index_map — the gather never materializes), absorbs
  them into a one-row online softmax (the [B, Tmax] score matrix never
  exists; blocks past the lane's tick index are neither fetched — the
  clamped index_map revisits the previous block, which Pallas skips
  re-DMAing — nor computed, via `pl.when`), absorbs this tick's K/V as
  the final softmax position, and writes the appended row back IN
  PLACE through `input_output_aliases` (the scatter is a one-row
  window, not a full-leaf pass). Pad lanes ride through unchanged:
  their masked write lands the OLD row value on the null slot.
* `reference_decode_attention` — the XLA composition
  (gather -> `.at[rows, index].set` append -> `cached_attention` ->
  masked scatter) the kernel is numerics-pinned against.

Numerics contract: identical unmasked score set as `cached_attention`
over the post-append cache (arena positions strictly below the lane's
index, plus the appended position AT the index), f32 online softmax
with the same `_mask_value` masking — tick-by-tick parity is pinned by
tests/test_decode_kernels.py at every T.

CPU smoke runs the kernel with `interpret=True` (`interpret=None`
resolves from the process backend at trace time — see the note inside
`fused_decode_attention` for why flash_attention's platform_dependent
auto-select cannot be used here); the Mosaic lowering is validated
hardware-free by tests/test_mosaic_lowering.py (explicit
`interpret=False` under a TPU-platform export).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensor2robot_tpu.ops import attention as attention_ops

__all__ = ["fused_decode_attention", "reference_decode_attention"]

def reference_decode_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                               v_new: jnp.ndarray, k_arena: jnp.ndarray,
                               v_arena: jnp.ndarray, slots: jnp.ndarray,
                               index: jnp.ndarray, mask: jnp.ndarray
                               ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
  """The XLA composition the fused kernel replaces (and is pinned to).

  Gathers each lane's KV rows from the arena, appends this tick's K/V
  at the lane's index, runs `cached_attention`, and scatters the
  appended rows back masked — three full-leaf HBM passes. Pad lanes
  (mask False) scatter the OLD row value through the null slot, so
  duplicates are write-idempotent.
  """
  b = q.shape[0]
  rows = jnp.arange(b)
  k_cache = k_arena[slots].at[rows, index].set(k_new)
  v_cache = v_arena[slots].at[rows, index].set(v_new)
  out = attention_ops.cached_attention(q, k_cache, v_cache, index)
  lane = mask[:, None, None]
  k_row = jnp.where(lane, k_new, k_arena[slots, index])
  v_row = jnp.where(lane, v_new, v_arena[slots, index])
  return (out, k_arena.at[slots, index].set(k_row),
          v_arena.at[slots, index].set(v_row))


def _decode_tick_kernel(slots_ref, idx_ref, mask_ref, q_ref, knew_ref,
                        vnew_ref, karena_ref, varena_ref, out_ref,
                        kupd_ref, vupd_ref, m_ref, l_ref, o_ref,
                        kold_ref, vold_ref, *, block_k: int):
  """One (lane, k-block) program of the fused decode tick.

  Grid (B, NB), NB innermost: the VMEM scratch (running max / denom /
  numerator + the stashed old row at the append position) persists
  across a lane's sequential k-block iterations. Blocks past the
  lane's append block are neither fetched (the clamped index_map
  revisits the previous block index, whose DMA Pallas skips) nor
  computed (`pl.when`), so per-lane HBM traffic is O(index), not
  O(Tmax).
  """
  b = pl.program_id(0)
  kb = pl.program_id(1)
  nb = pl.num_programs(1)
  idx = idx_ref[b]
  last_in = idx // block_k  # block holding the append position
  d = q_ref.shape[-1]
  scale = 1.0 / math.sqrt(d)
  mask_val = jnp.float32(jnp.finfo(jnp.float32).min / 2)

  @pl.when(kb == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    o_ref[...] = jnp.zeros_like(o_ref)

  @pl.when(kb <= last_in)
  def _absorb():
    # Online-softmax absorb of one arena K/V block. Entries at or past
    # the lane's index score `_mask_value` — the same masked row
    # `cached_attention` softmaxes — so a partial block (and a pad
    # lane's fully-masked block 0) contributes exactly 0 after the
    # final rescale.
    q = q_ref[0].astype(jnp.float32)                   # [H, D]
    k_blk = karena_ref[0].astype(jnp.float32)          # [bk, H, D]
    v_blk = varena_ref[0].astype(jnp.float32)
    s = jnp.sum(q[None, :, :] * k_blk, axis=-1) * scale  # [bk, H]
    t_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, 1), 0)
    s = jnp.where(t_pos < idx, s, mask_val)
    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))    # [H]
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[None, :])                    # [bk, H]
    l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=0)
    o_ref[...] = (o_ref[...] * alpha[:, None]
                  + jnp.sum(p[:, :, None] * v_blk, axis=0))
    m_ref[0] = m_new

  @pl.when(kb == last_in)
  def _stash_old_row():
    # The pre-append value at the lane's index, for masked write-back:
    # a pad lane's "append" must land the OLD row (null-slot immunity).
    row = idx - kb * block_k
    kold_ref[...] = karena_ref[0, pl.ds(row, 1)]
    vold_ref[...] = varena_ref[0, pl.ds(row, 1)]

  @pl.when(kb == nb - 1)
  def _epilogue():
    # The appended position is absorbed directly from k_new/v_new (no
    # read-after-write hazard with the in-place row update): its score
    # is the one `cached_attention` sees at position == index.
    q = q_ref[0].astype(jnp.float32)
    s_new = jnp.sum(q * knew_ref[0].astype(jnp.float32),
                    axis=-1) * scale                   # [H]
    m_prev = m_ref[0]
    m_fin = jnp.maximum(m_prev, s_new)
    alpha = jnp.exp(m_prev - m_fin)
    p_new = jnp.exp(s_new - m_fin)
    l_fin = l_ref[0] * alpha + p_new
    o_fin = (o_ref[...] * alpha[:, None]
             + p_new[:, None] * vnew_ref[0].astype(jnp.float32))
    out_ref[0] = (o_fin
                  / jnp.maximum(l_fin, 1e-30)[:, None]).astype(out_ref.dtype)
    live = mask_ref[b] != 0
    kupd_ref[0, 0] = jnp.where(live, knew_ref[0], kold_ref[0])
    vupd_ref[0, 0] = jnp.where(live, vnew_ref[0], vold_ref[0])


def _effective_block(t: int, block_k: int) -> int:
  """Largest block <= block_k that divides T (every T tiles exactly —
  partial-horizon arithmetic stays in the index clamp, not in padding)."""
  block = max(1, min(int(block_k), t))
  while t % block:
    block -= 1
  return block


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def fused_decode_attention(q: jnp.ndarray, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, k_arena: jnp.ndarray,
                           v_arena: jnp.ndarray, slots: jnp.ndarray,
                           index: jnp.ndarray, mask: jnp.ndarray,
                           block_k: int = 8,
                           interpret: Optional[bool] = None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                      jnp.ndarray]:
  """Fused gather + append + cached-attention decode tick, in place.

  q / k_new / v_new: [B, H, D] — this tick's per-lane query and K/V;
  k_arena / v_arena: [S, T, H, D] — the WHOLE session arena leaf
  (slot-major; slot 0 is the reserved null slot);
  slots: [B] int32 — each lane's arena slot (live lanes distinct);
  index: [B] int32 — each lane's tick position (append target);
  mask:  [B] bool  — live lanes; pad lanes write their OLD row back.

  Returns (out [B, H, D], k_arena', v_arena') with the arenas updated
  only at each live lane's (slot, index) row — alias-updated in place
  when the caller donates them.
  """
  if interpret is None:
    # Resolve from the PROCESS backend at trace time. The serving
    # engine compiles its dispatch for the backend it executes on, so
    # this is correct by construction there; flash_attention's
    # platform_dependent auto-select is NOT usable here because inside
    # jit the switch lowers BOTH branches and the interpret=False
    # branch hard-fails CPU lowering ("Only interpret mode is supported
    # on CPU backend") — the eager-only fold is why the model layers
    # pass flash_interpret statically. Cross-platform AOT exports
    # (TPU-target program lowered from a CPU host) must pass
    # interpret=False explicitly (tests/test_mosaic_lowering.py does).
    interpret = jax.default_backend() != "tpu"
  b, h, d = q.shape
  s_sz, t = k_arena.shape[0], k_arena.shape[1]
  bk = _effective_block(t, block_k)
  nb = t // bk
  slots = slots.astype(jnp.int32)
  index = index.astype(jnp.int32)
  mask_i = mask.astype(jnp.int32)
  if not interpret:
    # Pin kernel operands to plain HBM buffers (the flash_attention
    # barrier discipline: XLA:TPU otherwise fuses surrounding layout
    # ops into the custom call's scoped-VMEM region).
    q, k_new, v_new, k_arena, v_arena = jax.lax.optimization_barrier(
        (q, k_new, v_new, k_arena, v_arena))

  def lane(bi, kbi, slots_ref, idx_ref, mask_ref):
    del kbi, slots_ref, idx_ref, mask_ref
    return (bi, 0, 0)

  def arena_block(bi, kbi, slots_ref, idx_ref, mask_ref):
    del mask_ref
    # Clamp past-the-append blocks to the append block: Pallas skips
    # the DMA of a revisited block index, so a lane only ever fetches
    # blocks 0..index//bk — O(index) HBM traffic per tick.
    return (slots_ref[bi], jnp.minimum(kbi, idx_ref[bi] // bk), 0, 0)

  def append_row(bi, kbi, slots_ref, idx_ref, mask_ref):
    del kbi, mask_ref
    return (slots_ref[bi], idx_ref[bi], 0, 0)

  grid_spec = pltpu.PrefetchScalarGridSpec(
      num_scalar_prefetch=3,
      grid=(b, nb),
      in_specs=[
          pl.BlockSpec((1, h, d), lane),          # q
          pl.BlockSpec((1, h, d), lane),          # k_new
          pl.BlockSpec((1, h, d), lane),          # v_new
          pl.BlockSpec((1, bk, h, d), arena_block),   # k_arena
          pl.BlockSpec((1, bk, h, d), arena_block),   # v_arena
      ],
      out_specs=[
          pl.BlockSpec((1, h, d), lane),              # out
          pl.BlockSpec((1, 1, h, d), append_row),     # k_arena'
          pl.BlockSpec((1, 1, h, d), append_row),     # v_arena'
      ],
      scratch_shapes=[
          pltpu.VMEM((1, h), jnp.float32),        # running max
          pltpu.VMEM((1, h), jnp.float32),        # running denom
          pltpu.VMEM((h, d), jnp.float32),        # unnormalized numerator
          pltpu.VMEM((1, h, d), k_arena.dtype),   # old row at index
          pltpu.VMEM((1, h, d), v_arena.dtype),
      ])
  out, k_upd, v_upd = pl.pallas_call(
      functools.partial(_decode_tick_kernel, block_k=bk),
      grid_spec=grid_spec,
      out_shape=[
          jax.ShapeDtypeStruct((b, h, d), q.dtype),
          jax.ShapeDtypeStruct(k_arena.shape, k_arena.dtype),
          jax.ShapeDtypeStruct(v_arena.shape, v_arena.dtype),
      ],
      input_output_aliases={6: 1, 7: 2},  # arenas update in place
      interpret=interpret,
  )(slots, index, mask_i, q, k_new, v_new, k_arena, v_arena)
  if not interpret:
    out, k_upd, v_upd = jax.lax.optimization_barrier((out, k_upd, v_upd))
  return out, k_upd, v_upd
