"""Attention ops: fused flash attention + ring attention for sequence
parallelism.

The reference has no attention-scale sequence machinery at all
(SURVEY.md §5 "long-context: none") — its longest-sequence handling is
SequenceExample padding and GRU/SNAIL layers. This module adds the
long-context capability TPU-first:

* `attention` — reference jnp implementation (any backend);
* `flash_attention` — Pallas TPU kernel: block-streamed online softmax
  so the [T, T] score matrix never materializes in HBM (O(T) memory);
* `ring_attention` — context parallelism over a mesh axis: each device
  holds a sequence shard, K/V blocks rotate around the ICI ring via
  `ppermute` inside `shard_map` while the online-softmax accumulator
  absorbs one block per hop. Exact (not approximate) attention over
  sequences `axis_size`x longer than one chip's memory; compute and
  ring transfers overlap under XLA's async collectives.

All functions take [batch, heads, seq, head_dim] ("BHTD") arrays, except
`flash_attention`, which takes and returns [batch, seq, heads x head_dim]:
the q/k/v projections' own layout, which its kernels read in place.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec

from tensor2robot_tpu.parallel import mesh as mesh_lib

__all__ = ["attention", "cached_attention", "flash_attention",
           "ring_attention", "ulysses_attention"]


def _mask_value(dtype) -> jnp.ndarray:
  return jnp.asarray(jnp.finfo(dtype).min / 2, dtype)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = False,
              window: Optional[int] = None) -> jnp.ndarray:
  """Reference softmax attention, [B, H, T, D]. `window` (causal only): query
  i sees keys i - window + 1 .. i, `window` keys counting its own."""
  scale = 1.0 / math.sqrt(q.shape[-1])
  scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
  if causal:
    tq, tk = scores.shape[-2], scores.shape[-1]
    mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
    if window is not None:
      mask &= ~jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq - window)
    scores = jnp.where(mask, scores, _mask_value(scores.dtype))
  weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
  return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(q.dtype), v)


def cached_attention(q_t: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, index: jnp.ndarray
                     ) -> jnp.ndarray:
  """One decode tick against a per-session KV cache: O(1) attention work
  per step instead of the O(T) full-prefix re-run (ISSUE 11 / PAPERS.md
  "Portable O(1) Autoregressive Caching for Inference").

  q_t: [B, H, D] — this tick's single query per session;
  k_cache/v_cache: [B, T_max, H, D] — T-major so the serving arena's
  per-session append is one advanced-index `.at[rows, index].set`;
  index: [B] int32 — each session's CURRENT tick (sessions in one
  continuous-batching dispatch sit at different episode positions).

  Numerics are pinned to row `index` of `attention(..., causal=True)`:
  positions past a session's index score `_mask_value` — exactly what
  the causal mask assigns them there — so the f32 softmax sees the same
  masked score row and `exp` underflows them to exactly 0.
  """
  scale = 1.0 / math.sqrt(q_t.shape[-1])
  scores = jnp.einsum("bhd,bthd->bht", q_t, k_cache) * scale
  valid = jnp.arange(k_cache.shape[1])[None, :] <= index[:, None]  # [B,T]
  scores = jnp.where(valid[:, None, :], scores, _mask_value(scores.dtype))
  weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
  return jnp.einsum("bht,bthd->bhd", weights.astype(q_t.dtype), v_cache)


# -- online-softmax block update (shared by flash + ring) -------------------


def _sum_rides(d: int) -> bool:
  """Whether the softmax denominator is taken from the p.v product.

  A column of ones beside v makes the product sum each row of p as it
  goes. Where the head is no multiple of the MXU's 128 columns the product
  has idle output columns and the sum costs nothing, while a reduction
  over the score tile's lanes is a pass through the XLU; where the head
  fills the columns, the 129th costs the product another pass of the MXU.
  Flash forward alone on the v5e (PERF.md section 6, PR 27; T 2048,
  512 x 512 tiles, causal, bf16, bh x d = 65,536), ms a call with the sum
  riding / reduced: d 64 12.05 / 13.68, d 128 8.26 / 6.85, d 256 6.30 /
  5.69. The riding sum adds p as the product sees it, rounded to v's
  dtype, with float32 accumulation like the numerator it divides: in bf16
  the log-sum-exp is then within one rounding (2^-9) of the exact one
  however long the row (0.0018 at most, 0.0001-0.0003 rms at T 2048).
  """
  return d % 128 != 0


def _online_init(q):
  """(m, l, o) before the first block, for q [..., Tq, D]: m and l are
  [..., Tq, 1]; o has a last column for the denominator where it rides,
  and l then stays unused."""
  d = q.shape[-1]
  m = jnp.full(q.shape[:-1] + (1,), -jnp.inf, jnp.float32)
  l = jnp.zeros(q.shape[:-1] + (1,), jnp.float32)
  o = jnp.zeros(q.shape[:-1] + (d + 1 if _sum_rides(d) else d,),
                jnp.float32)
  return m, l, o


def _online_block_update(q, k_blk, v_blk, m_prev, l_prev, o_prev,
                         score_mask=None):
  """Absorbs one K/V block into the running (max, denom, output).

  q: [..., Tq, D]; k_blk/v_blk: [..., Tk, D]; m_prev, l_prev, o_prev (the
  unnormalized numerator) as `_online_init` shapes them. Returns updated
  (m, l, o).
  """
  d = q.shape[-1]
  s = jnp.einsum("...qd,...kd->...qk", q, k_blk,
                 preferred_element_type=jnp.float32) * (1.0 / math.sqrt(d))
  if score_mask is not None:
    s = jnp.where(score_mask, s, _mask_value(s.dtype))
  m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
  alpha = jnp.exp(m_prev - m_new)
  p = jnp.exp(s - m_new)
  if _sum_rides(d):
    l_new = l_prev
    v_blk = jnp.concatenate(
        [v_blk, jnp.ones(v_blk.shape[:-1] + (1,), v_blk.dtype)], axis=-1)
  else:
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
  o_new = o_prev * alpha + jnp.einsum(
      "...qk,...kd->...qd", p.astype(v_blk.dtype), v_blk,
      preferred_element_type=jnp.float32)
  return m_new, l_new, o_new


def _normalize(l, o, d: int):
  """(attention output, denominator) from the last block's (l, o)."""
  if _sum_rides(d):
    l, o = o[..., d:], o[..., :d]
  l = jnp.maximum(l, 1e-30)
  return o / l, l


# -- Pallas flash attention --------------------------------------------------
#
# Forward: FlashAttention online softmax; also emits the per-row
# logsumexp needed by the backward. Backward: one recompute kernel over
# the k-block grid that takes dV, dK and dQ from one recomputation of each
# score tile — the [T, T] score matrix never materializes in HBM in
# either direction. Sequences that don't tile are PADDED to the block
# size and masked (never a silent O(T^2) fallback).
#
# What a tile costs on the v5e (PERF.md section 6, PR 27; bh 1024,
# T 2048, d 64, 512 x 512 tiles, causal; each piece taken out of the
# kernel in turn): the matrix products, and the passes that cross lanes.
# A product that makes a [block, block] tile from a contraction over d
# (q.k^T, dO.v^T) takes 0.62 us a tile whichever operand is transposed,
# one that contracts over a block into [block, d] 0.31 us; a row maximum
# of the tile 0.23 us, a row sum 0.28 us, a transpose of it 0.18 us. The
# scale, the mask, the exp and every other elementwise pass hide under
# them (taking each out moved no kernel by more than 2.5 %; a loop split
# into masked and unmasked tiles cost 10 %), and so does the operands'
# width: Mosaic feeds the MXU bf16 from float32 operands at default
# precision, bit for bit what a cast gives. So the kernels keep the
# elementwise passes they had and lose what crosses lanes: the backward
# works on the transposed tile, the forward's row sum rides the p.v
# product where a head leaves it idle columns (`_sum_rides`), and `delta`
# no longer travels as a lane-padded column.
#
# One backward kernel (PERF.md section 6, PR 32). FlashAttention-2's two
# kernels (dQ over the q-block grid, dK/dV over the k-block grid) each
# recomputed q.k^T, the exp over it, the mask and dO.v^T: four
# [block, block] products and three accumulating ones a tile pair. The one
# kernel is the dK/dV kernel with dQ's product added: a tile pair costs
# two [block, block] products (S^T, dP^T), three accumulating ones (dV,
# dK, dQ) and one transpose of dS^T, which has to meet K_h with the
# contraction over its first dimension. Readings, v5e, the kernel alone
# (device time in a trace), 128 x 2048 x 8 x 64, 512 x 512 tiles, causal,
# bf16, ms a call (the two kernels: dQ 12.38 + dK/dV 18.53 = 30.91):
#   no dQ product at all (dK/dV with the strip beside it)       18.86
#   (A) dS^T.T, then a plain product                             22.65  (kept)
#   (B) `dot_general` contracting dimension 0 of both            22.65
#   (C) K_h^T, turned once a program, as the left operand:
#       dQ^T[lanes, block_q] += K_h^T.dS^T, turned at the end    22.88
#   (A) with dS^T cast to bf16 before it is turned               22.51
# Mosaic lowers (B) as (A)'s transpose; (C)'s product has 128 rows, half
# of them another head's zeros. So dQ costs 3.8 ms a call where its own
# kernel cost 12.4. Casting dS^T first is 0.13 ms faster and the same
# bits on the chip (the MXU is fed bf16 either way), but it would round
# an operand that no other product of the kernel rounds where operands
# stay float32 (interpret mode, float32 inputs): not taken. dK and dV
# equal the two kernels' bit for bit; dQ differs in 21,416 of 134,217,728
# elements, by one bf16 rounding at most (0.00195 on values up to 5.5):
# same products, summed over k blocks in the same order, but `delta`
# comes from XLA's sum where dQ's kernel summed its own rows.
#
# VMEM. dQ's strip stays resident beside the whole-T q and dO: in bf16 a
# program holds 16 bytes an element of [T, lanes] (q and dO
# double-buffered 8, dQ's output block 4, its float32 accumulator 4): 4 MB
# at T 2048 and 128 lanes, 16 MB at T 8192 (the dK/dV kernel held 8 of
# them). `_bwd_vmem_bytes` states the kernel's limit from those counts. The least limit that compiles (described v5e, two heads of 64):
# 9.8 MB at T 2048, 13.6 at T 4096, 21.9 at T 8192, the last past
# Mosaic's default 16 MB; one head of 128 and four of 32 need 19.9 and
# 22.8 MB at T 8192, one of 256 23.3 at T 4096. The chip has 128 MB.
#
# Layout (PERF.md section 6, PR 30). The kernels read q, k, v, dO and
# write O, dQ, dK, dV as [B, T, H x D], the layout the q/k/v projections
# write and the output projection reads: the eight head-split transposes a
# layer ran between the two (1.20-1.26 ms each at 128 x 2048 x 8 x 64)
# are gone. Heads are indexed through the `BlockSpec`s: a program takes
# `lane_block(H, D)` lanes of the last dimension, the fewest whole heads
# that make a multiple of 128 (two heads of 64), grid (B, H x D / lanes,
# T / block). Inside a program every head of the block goes through the
# one tile loop, and is told from its neighbours by operands with the
# other heads' lanes zeroed (`_only`): a contraction over the whole block
# then gives one head's scores exactly (the other terms are exact zeros;
# on the 128-deep MXU it costs what the contraction over 64 at half fill
# cost), and a product into [block, lanes] puts a head's result in its own
# lanes. The forward's row sum rides in the lanes of the other heads (v
# set to one there), as it rides in the 65th column where a program is one
# head. Same products, same float32 accumulation: on the chip every output
# and gradient is bit for bit the [B x H, T, D] kernels'. Readings, v5e,
# 128 x 2048 x 8 x 64, 512 x 512 tiles, causal, bf16, ms a call, forward /
# dQ / dK/dV (the [B x H, T, D] kernels: 12.09 / 13.75 / 19.17):
#   static lane slices of the refs, one head's loop after the other
#                                              12.62 / 14.62 / 18.79
#   lane slices, the heads in one loop         11.68 / 13.70 / 17.95
#   masked operands, one loop after the other  11.61 / 13.51 / 17.80
#   masked operands, the heads in one loop     11.60 / 12.55 / 17.56  (kept)
# Two heads in one loop body give the scheduler one head's products to
# run under the other's passes over lanes; a slice at lane 64 costs a
# lane shift a tile, a select on a [block, 128] operand nothing.
#
# Grouped-query heads. k and v may carry fewer heads than q, H_kv dividing
# H: query head h reads key/value head h // (H / H_kv). Where a program
# holds one head (`lane_block(H, D) == D`: heads of 128 and 256) the
# kernels read k and v at their own width through the `BlockSpec` index
# maps, so no repeat of them to the query heads runs ahead of the kernels
# and no sum of their cotangents over a group runs after. The forward's
# grid is the one above; its whole-T k and v strips take the block
# (b, 0, h // group), the same for the consecutive heads of a group, so
# Pallas fetches each strip once a group. The backward's grid is (B, H_kv,
# group member, T / block_k), in order on one core: q, dO, `lse` and
# `delta` are those of query head j x group + m, the k and v tiles those of
# (j, k block); dQ keeps its whole-T accumulator over the k blocks, and dK
# and dV add up in whole-T float32 strips over (member, k block), cast once
# into their output blocks (b, 0, j) at the last member (`_sum_over_group`).
# So dK and dV are summed over the group in float32 before their one
# rounding. At H_kv == H, and where a program holds several heads (which
# then see k and v repeated to the query heads by `flash_attention`), the
# kernels are those above.


def _valid_mask(q_start, k_start, q_block, k_block, causal: bool,
                valid_len: int, padded_len: int, q_axis: int = 0,
                window: Optional[int] = None):
  """Score-entry validity: causal triangle, the band's lower edge where
  there is a `window`, key/query padding. The tile is [q_block, k_block],
  or its transpose where `q_axis` is 1."""
  if not causal and valid_len == padded_len:
    return None
  shape = (q_block, k_block) if q_axis == 0 else (k_block, q_block)
  q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
  k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
  mask = jnp.ones(shape, bool)
  if causal:
    mask &= q_pos >= k_pos
  if window is not None:
    mask &= q_pos - k_pos < window
  if valid_len != padded_len:
    mask &= (k_pos < valid_len) & (q_pos < valid_len)
  return mask


def lane_block(num_heads: int, head_dim: int) -> int:
  """Lanes of [B, T, H x D] that one program of a flash kernel takes.

  A TPU block's last dimension is a multiple of 128 lanes or the whole
  dimension, so a program takes the smallest run of whole heads that is a
  multiple of 128 lanes and divides H x D (D 64: two heads, D 32: four,
  D 128 and D 256: one), and the whole H x D where there is none (the CPU
  tests' heads of 8). It follows the operands' shape and nothing else.
  """
  lanes = math.lcm(head_dim, 128)
  return lanes if (num_heads * head_dim) % lanes == 0 else (
      num_heads * head_dim)


# Heads of a lane block that share one tile loop. Two is what the chip
# chose at two heads of 64 (the readings are above); each head in a loop
# holds its own [block, block] float32 tiles in VMEM, and four heads of 32
# in one loop are refused at T 8192 (16.07 MB of the 16 MB scoped limit;
# described v5e, PR 30). Further heads of the block take further loops.
_HEADS_A_LOOP = 2


def _head_groups(lanes: int, head_dim: int):
  """The heads of a program's `lanes`-wide block, `_HEADS_A_LOOP` to a
  group: for each head its index and the [1, lanes] mask of its own lanes
  (`None` where the block is one head)."""
  if lanes == head_dim:
    return [[(0, None)]]
  lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
  heads = [(h, (lane >= h * head_dim) & (lane < (h + 1) * head_dim))
           for h in range(lanes // head_dim)]
  return [heads[i:i + _HEADS_A_LOOP]
          for i in range(0, len(heads), _HEADS_A_LOOP)]


def _only(in_head, x, other=0):
  """x with the lanes outside a head set to `other` (x itself where the
  block is one head)."""
  return x if in_head is None else jnp.where(in_head, x, other)


def _k_block_range(q_idx, q_block: int, block_k: int, num_k_blocks: int,
                   causal: bool, window: Optional[int]):
  """[first, end) of the key blocks that q block `q_idx` streams: every
  block that holds an entry its rows may see, and no other."""
  first = 0
  if causal:
    # Future blocks are fully masked: stop the stream at the diagonal.
    num_k_blocks = jnp.minimum(
        num_k_blocks,
        ((q_idx + 1) * q_block + block_k - 1) // block_k)
  if window is not None:
    # Blocks wholly below the band are fully masked too: start the stream
    # at the block of the first row's earliest key.
    first = jnp.maximum(0, (q_idx * q_block - window + 1) // block_k)
  return first, num_k_blocks


def _q_block_range(k_idx, k_block: int, block_q: int, num_q_blocks: int,
                   causal: bool, window: Optional[int]):
  """[first, end) of the query blocks whose rows see a key of k block
  `k_idx`: the backward's loop over them."""
  first = 0
  if causal:
    # Blocks strictly above the diagonal see no unmasked entries.
    first = (k_idx * k_block) // block_q
  if window is not None:
    # Nor do blocks wholly past the band: the last row that sees a key of
    # this block is its last key's + window - 1.
    num_q_blocks = jnp.minimum(
        num_q_blocks, (k_idx * k_block + k_block + window - 2) // block_q + 1)
  return first, num_q_blocks


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      head_dim: int, block_k: int, causal: bool,
                      q_block: int, valid_len: int,
                      window: Optional[int] = None):
  """One (batch, lane block, q_block) program: stream K/V blocks through
  VMEM, every head of the lane block in the one loop."""
  tq_idx = pl.program_id(2)
  seq_len, lanes = k_ref.shape
  first_k_block, num_k_blocks = _k_block_range(
      tq_idx, q_block, block_k, seq_len // block_k, causal, window)
  q = q_ref[:]  # [block_q, lanes]

  def tile(kb):
    rows = pl.ds(kb * block_k, block_k)
    mask = _valid_mask(tq_idx * q_block, kb * block_k, q_block, block_k,
                       causal, valid_len, seq_len, window=window)
    return k_ref[rows, :], v_ref[rows, :], mask

  if lanes == head_dim:
    # One head a program: the block update the ring shares, as it is.
    def body(kb, carry):
      k_blk, v_blk, mask = tile(kb)
      return _online_block_update(q, k_blk, v_blk, *carry, mask)

    m, l, o = jax.lax.fori_loop(first_k_block, num_k_blocks, body,
                                _online_init(q))
    out, l = _normalize(l, o, head_dim)
    stats = [(m, l)]
  else:
    # Several heads side by side. q with the other heads' lanes zeroed
    # against the whole k block contracts to this head's scores exactly
    # (the other terms are exact zeros), and p.v over the whole v block
    # puts this head's numerator in its own lanes. The other heads' lanes
    # of that product are idle, so v is set to one there and they carry
    # the row sum, as the 65th column does for one head (`_sum_rides`).
    scale = 1.0 / math.sqrt(head_dim)

    def body(kb, carries, group, q_heads):
      k_blk, v_blk, mask = tile(kb)
      new = []
      for (_, in_head), q_h, (m_prev, o_prev) in zip(group, q_heads,
                                                     carries):
        s = jnp.einsum("qd,kd->qk", q_h, k_blk,
                       preferred_element_type=jnp.float32) * scale
        if mask is not None:
          s = jnp.where(mask, s, _mask_value(s.dtype))
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        o_new = o_prev * jnp.exp(m_prev - m_new) + jnp.matmul(
            p.astype(v_blk.dtype), _only(in_head, v_blk, 1),
            preferred_element_type=jnp.float32)
        new.append((m_new, o_new))
      return tuple(new)

    out, stats = jnp.zeros((q_block, lanes), jnp.float32), []
    for group in _head_groups(lanes, head_dim):
      carries = jax.lax.fori_loop(
          first_k_block, num_k_blocks,
          functools.partial(
              body, group=group,
              q_heads=[_only(in_head, q) for _, in_head in group]),
          ((jnp.full((q_block, 1), -jnp.inf, jnp.float32),
            jnp.zeros((q_block, lanes), jnp.float32)),) * len(group))
      for (h, in_head), (m, o) in zip(group, carries):
        beside = ((h + 1) * head_dim) % lanes  # a lane of another head
        l = jnp.maximum(o[:, beside:beside + 1], 1e-30)
        out = jnp.where(in_head, o / l, out)
        stats.append((m, l))
  o_ref[:] = out.astype(o_ref.dtype)
  # logsumexp per query row, stored [T, 1]: the trailing unit lane dim
  # keeps the block shape inside Mosaic's (8, 128)-divisible-or-whole
  # tiling rule for EVERY block_q (a [T]-flat lse blocked at block_q
  # fails TPU lowering whenever 8 <= block_q < 128 — caught by the
  # local Mosaic lowering tests; interpret mode hides it).
  # Fully-masked (padded) rows would otherwise carry
  # lse = mask_value + log(block) ~ -1e38, making the backward recompute
  # exp(s - lse) overflow before its own mask zeroes it; pin those rows
  # to 0 (their p is masked to 0 in the backward anyway). Validity is
  # positional: a row is real iff its query index < valid_len (for
  # causal rows the diagonal entry is always unmasked, so l > 0).
  # broadcasted_iota, not 1D lax.iota: Mosaic rejects 1D iota at compile
  # time (TPU vectors are 2D sublane x lane; interpret mode hides this).
  q_pos = tq_idx * q_block + jax.lax.broadcasted_iota(
      jnp.int32, (q_block, 1), 0)
  row_valid = q_pos < valid_len
  for h, (m, l) in enumerate(stats):
    lse_ref[h] = jnp.where(row_valid, m + jnp.log(l), 0.0)


def _delta(do, o):
  """delta_i = sum_d dO_id * O_id (FlashAttention-2's backward precompute),
  float32 [..., T, 1]."""
  return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                 keepdims=True)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, *kv_acc,
                      head_dim: int, block_q: int, causal: bool,
                      k_block: int, valid_len: int,
                      window: Optional[int] = None):
  """The whole backward for one k block, from one recomputation of each
  score tile: dV = P^T.dO, dK = scale * dS^T.Q, and this k block's term of
  dQ = scale * dS.K.

  Works on the transposed tile: S^T = K.Q^T and dP^T = V.dO^T come out of
  the MXU as [k_block, block_q], so P^T and dS^T feed the two products
  that accumulate dV and dK as they are; dS^T is turned once for dQ.
  `lse` and `delta` arrive as lane-dense rows, [heads, T // block_q, 1,
  block_q]: a sublane broadcast a tile. Where the block holds several
  heads, k and v with the other heads' lanes zeroed (once a program)
  against the whole q and dO give this head's tiles; its dV, dK and dQ
  then stand in its own lanes, and what the products put in the other
  lanes of dV and dK is dropped at the end (dS.K_h puts exact zeros there).

  dQ is summed over k blocks, which are grid steps: `dq_acc` is a whole-T
  float32 strip that stays in VMEM across the k-block axis of one (batch,
  lane block), zeroed at the first k block, cast into `dq_ref` (whose
  block index ignores the k block) at the last. The k-block axis must run
  in order on one core: it carries no `parallel` dimension semantics.

  With grouped-query heads (`kv_acc`, the whole-T float32 strips of dK and
  dV) the grid is (batch, key/value head, group member, k block): this
  program's dK and dV tiles add into the strips (`_sum_over_group`).
  """
  scale = 1.0 / math.sqrt(head_dim)
  k_axis = 3 if kv_acc else 2
  tk_idx = pl.program_id(k_axis)
  seq_len, lanes = q_ref.shape
  start_q, num_q_blocks = _q_block_range(
      tk_idx, k_block, block_q, seq_len // block_q, causal, window)

  @pl.when(tk_idx == 0)
  def _():
    dq_acc[...] = jnp.zeros_like(dq_acc)

  def body(qb, carries, group, k_heads, v_heads):
    rows = pl.ds(qb * block_q, block_q)
    q_blk = q_ref[rows, :]
    do_blk = do_ref[rows, :].astype(jnp.float32)
    mask = _valid_mask(qb * block_q, tk_idx * k_block, block_q, k_block,
                       causal, valid_len, seq_len, q_axis=1, window=window)
    new, dq = [], []
    for (h, _), k_h, v_h, (dk, dv) in zip(group, k_heads, v_heads, carries):
      st = jnp.matmul(k_h, q_blk.T,
                      preferred_element_type=jnp.float32) * scale
      pt = jnp.exp(st - lse_ref[h, qb])               # row [1, block_q]
      if mask is not None:
        pt = jnp.where(mask, pt, 0.0)
      dv = dv + jnp.matmul(pt, do_blk, preferred_element_type=jnp.float32)
      dpt = jnp.matmul(v_h, do_blk.T, preferred_element_type=jnp.float32)
      dst = pt * (dpt - delta_ref[h, qb]) * scale
      dk = dk + jnp.matmul(dst, q_blk, preferred_element_type=jnp.float32)
      dq.append(jnp.matmul(dst.T, k_h, preferred_element_type=jnp.float32))
      new.append((dk, dv))
    dq_acc[rows, :] += functools.reduce(jnp.add, dq)
    return tuple(new)

  zeros = jnp.zeros((k_block, lanes), jnp.float32)
  dk, dv = zeros, zeros
  for group in _head_groups(lanes, head_dim):
    carries = jax.lax.fori_loop(
        start_q, num_q_blocks,
        functools.partial(
            body, group=group,
            k_heads=[_only(in_head, k_ref[:]) for _, in_head in group],
            v_heads=[_only(in_head, v_ref[:]) for _, in_head in group]),
        ((zeros, zeros),) * len(group))
    for (_, in_head), (dk_h, dv_h) in zip(group, carries):
      dk, dv = _only(in_head, dk_h, dk), _only(in_head, dv_h, dv)
  if kv_acc:
    _sum_over_group(pl.ds(tk_idx * k_block, k_block), (dk, dv),
                    (dk_ref, dv_ref), kv_acc)
  else:
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)

  @pl.when(tk_idx == pl.num_programs(k_axis) - 1)
  def _():
    dq_ref[:] = dq_acc[...].astype(dq_ref.dtype)


def _sum_over_group(rows, tiles, out_refs, accs):
  """Adds one group member's dK and dV `tiles` for the key rows `rows` into
  the whole-T float32 strips `accs`; the last member (grid axis 2) casts
  the sum into `out_refs`, the whole-T output blocks of the key/value head,
  which stay resident over the member and k-block axes."""
  member, last = pl.program_id(2), pl.num_programs(2) - 1

  @pl.when(member == 0)
  def _():
    for acc, tile in zip(accs, tiles):
      acc[rows, :] = tile

  @pl.when((member > 0) & (member < last))
  def _():
    for acc, tile in zip(accs, tiles):
      acc[rows, :] += tile

  @pl.when(member == last)
  def _():
    for out, acc, tile in zip(out_refs, accs, tiles):
      out[rows, :] = (acc[rows, :] + tile).astype(out.dtype)


def _block_specs(t: int, block: int, lanes: int, head_dim: int):
  """BlockSpecs over a (batch, lane block, T block) grid: a [block, lanes]
  tile and the whole-T [T, lanes] strip of [B, T, H x D], and the
  [heads, block, 1] columns of [B, H, T, 1] that go with the tile."""
  return (pl.BlockSpec((None, block, lanes), lambda b, g, i: (b, i, g)),
          pl.BlockSpec((None, t, lanes), lambda b, g, i: (b, 0, g)),
          pl.BlockSpec((None, lanes // head_dim, block, 1),
                       lambda b, g, i: (b, g, i, 0)))


def _group(q, k) -> int:
  """Query heads a key/value head serves: H x D over H_kv x D."""
  return q.shape[-1] // k.shape[-1]


def _flash_forward(q, k, v, num_heads, causal, block_q, block_k, valid_len,
                   interpret, window=None):
  """q [B, T, H x D], k, v [B, T, H_kv x D] -> (out [B, T, H x D], lse
  [B, H, T, 1]). At H_kv < H a program holds one head."""
  b, t, hd = q.shape
  d = hd // num_heads
  lanes = lane_block(num_heads, d)
  group = _group(q, k)
  kernel = functools.partial(
      _flash_fwd_kernel, head_dim=d, block_k=block_k, causal=causal,
      q_block=block_q, valid_len=valid_len)
  if window is not None:
    kernel = functools.partial(kernel, window=window)
  tile, whole, column = _block_specs(t, block_q, lanes, d)
  kv_whole = whole
  if group > 1:
    # Query head h reads key/value head h // group: the same strip for the
    # heads of a group, fetched once.
    kv_whole = pl.BlockSpec((None, t, lanes),
                            lambda b, h, i: (b, 0, h // group))
  out, lse = pl.pallas_call(
      kernel,
      grid=(b, hd // lanes, t // block_q),
      in_specs=[tile, kv_whole, kv_whole],
      out_specs=[tile, column],
      out_shape=[
          jax.ShapeDtypeStruct((b, t, hd), q.dtype),
          jax.ShapeDtypeStruct((b, num_heads, t, 1), jnp.float32),
      ],
      interpret=interpret,
      name="flash_fwd" if window is None else "flash_window_fwd",
  )(q, k, v)
  return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5, 6))
def _flash(num_heads: int, causal: bool, block_q: int, block_k: int,
           valid_len: int, interpret: bool, window: Optional[int], q, k, v):
  out, _ = _flash_forward(q, k, v, num_heads, causal, block_q, block_k,
                          valid_len, interpret, window)
  return out


def _flash_fwd(num_heads, causal, block_q, block_k, valid_len, interpret,
               window, q, k, v):
  out, lse = _flash_forward(q, k, v, num_heads, causal, block_q, block_k,
                            valid_len, interpret, window)
  return out, (q, k, v, out, lse)


# What Mosaic's default scoped-VMEM limit gives a kernel for everything.
_SCOPED_VMEM_BYTES = 16 * 2 ** 20


def _bwd_vmem_bytes(t: int, lanes: int, head_dim: int, block_q: int,
                    block_k: int, itemsize: int, grouped: bool = False) -> int:
  """The backward kernel's VMEM limit, from its operands' shapes: the
  blocks it keeps resident, and on top of them the default limit, which
  then has only the tile loop's own float32 tiles to hold. `grouped`: dK
  and dV also stand whole-T, as two-buffer output blocks and float32
  strips."""
  strip = t * lanes
  resident = (
      2 * 2 * strip * itemsize               # q and dO, whole T, two buffers
      + 2 * strip * itemsize + 4 * strip     # dQ: output block, accumulator
      + 4 * 2 * block_k * lanes * itemsize   # k, v, dK, dV tiles
      # lse and delta rows [heads, T / block_q, 1, block_q] float32: the
      # unit dimension is padded to 8 sublanes, the row to 128 lanes.
      + 2 * 2 * (lanes // head_dim) * (t // block_q) * 8
      * max(block_q, 128) * 4)
  if grouped:
    resident += 2 * (2 * itemsize + 4) * strip
  return resident + _SCOPED_VMEM_BYTES


def _flash_bwd(num_heads, causal, block_q, block_k, valid_len, interpret,
               window, residuals, g):
  q, k, v, out, lse = residuals
  b, t, hd = q.shape
  d = hd // num_heads
  lanes = lane_block(num_heads, d)
  # One row a q block (the block is the whole of the last two dims, so
  # every block_q lowers, sub-128 ones too).
  rows = (b, num_heads, t // block_q, 1, block_q)
  # The kernel needs every q block's `delta` as a row. One sum over the
  # whole H x D lanes a head, the other heads' lanes zeroed: XLA makes one
  # pass of them all (0.78 ms at 128 x 2048 x 8 x 64, v5e), where a
  # reduction over [B, T, H, D]'s last dimension needs the product laid out
  # again with 64 of every 128 lanes idle (3.24 ms; PERF.md section 6,
  # PR 30).
  head_of_lane = jnp.arange(hd) // d
  delta = jnp.stack(
      [_delta(jnp.where(head_of_lane == h, g, 0), out)[..., 0]
       for h in range(num_heads)], axis=1).reshape(rows)
  kernel = functools.partial(
      _flash_bwd_kernel, head_dim=d, block_q=block_q, causal=causal,
      k_block=block_k, valid_len=valid_len)
  if window is not None:
    kernel = functools.partial(kernel, window=window)
  group = _group(q, k)
  if group == 1:
    tile, whole, _ = _block_specs(t, block_k, lanes, d)
    grid = (b, hd // lanes, t // block_k)
    q_spec, kv_in, kv_out = whole, tile, tile
    rows_spec = pl.BlockSpec((None, lanes // d) + rows[2:],
                             lambda b, g, kb: (b, g, 0, 0, 0))
    scratch = [pltpu.VMEM((t, lanes), jnp.float32)]
  else:
    # One head a program over (B, H_kv, group member, T / block_k): q, dO,
    # `lse` and `delta` of query head j x group + m, the k and v tiles of
    # key/value head j, and its dK and dV whole-T, summed over the group
    # in the float32 strips `kv_acc` of the kernel.
    head = lambda j, m: j * group + m  # noqa: E731
    grid = (b, k.shape[-1] // d, group, t // block_k)
    q_spec = pl.BlockSpec((None, t, d),
                          lambda b, j, m, kb: (b, 0, head(j, m)))
    kv_in = pl.BlockSpec((None, block_k, d), lambda b, j, m, kb: (b, kb, j))
    kv_out = pl.BlockSpec((None, t, d), lambda b, j, m, kb: (b, 0, j))
    rows_spec = pl.BlockSpec((None, 1) + rows[2:],
                             lambda b, j, m, kb: (b, head(j, m), 0, 0, 0))
    scratch = [pltpu.VMEM((t, d), jnp.float32)] * 3
  dq, dk, dv = pl.pallas_call(
      kernel,
      grid=grid,
      in_specs=[q_spec, kv_in, kv_in, q_spec, rows_spec, rows_spec],
      out_specs=[q_spec, kv_out, kv_out],
      out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
      scratch_shapes=scratch,
      compiler_params=pltpu.CompilerParams(
          vmem_limit_bytes=_bwd_vmem_bytes(t, lanes, d, block_q, block_k,
                                           q.dtype.itemsize, group > 1)),
      interpret=interpret,
      name="flash_bwd" if window is None else "flash_window_bwd",
  )(q, k, v, g, lse.reshape(rows), delta)
  return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _next_pow2(n: int) -> int:
  return 1 << (n - 1).bit_length()


def _pow2_floor(n: int) -> int:
  return 1 << (n.bit_length() - 1)


# Minimum block edge: Mosaic tiles f32 at (8, 128); sub-8 q/k blocks can
# fail to compile on real TPU hardware (CPU tests run the interpreter and
# would not catch it).
_MIN_BLOCK = 8


# Measured-winner block sizes (block_q, block_k) at every length the step
# compiles at (v5e, 2026-10-01, PERF.md section 6, PR 27; the backward was
# then two kernels, dQ and dK/dV, under the default VMEM limit): the whole
# train step of `configs/train_longcontext_flash.gin` (hidden 512, 8 heads
# x 64, bf16), milliseconds a step by (block_q, block_k).
#
# T 2048 x 128 sequences: 512x512 178.3, 1024x1024 183.1, 512x1024 186.4,
# 1024x512 186.7, 256x512 193.1, 512x256 197.1. T 4096 x 64 sequences:
# 512x512 239.0, 512x1024 243.3, 1024x512 245.2, 256x512 267.8; 1024x1024
# is refused inside the step (the dQ kernel runs out of scoped VMEM; alone
# it compiles). T 8192 x 32 sequences: 512x512 356.7, 256x512 (the old
# setup's winner there) 412.2; 512x1024 and 1024x512 are refused inside
# the step (dK/dV and dQ out of scoped VMEM). Small tiles pay a tile's
# fixed costs more often (256x256 takes 1.5x the time of 512x512 in every
# kernel alone), large ones hold more float32 tile in VMEM than they save.
# At T 16384 the step is refused whatever the blocks: the forward's
# whole-T k and v no longer fit (ROADMAP B1).
#
# The windowed kernels take the same blocks. Smaller ones visit less of
# what lies outside a band (at W 512 and T 4096 the tiles visited hold
# 2.00 x the band's entries at 512-blocks, 1.50 x at 256, 1.25 x at 128),
# but pay a tile's fixed costs more often, and that wins: v5e, 1 x 4096 x
# 64 heads of 128, W 512, ms a call, forward / forward and backward (the
# op alone, wall clock over 20 calls; PERF.md section 6): 512x512
# 1.58 / 8.08, 256x512 1.59 / 8.52, 512x256 - / 8.83, 256x256 2.02 / 9.23,
# 128x256 2.83 / 10.50, 128x128 4.06 / 12.99; causal at the same shape and
# 512x512 2.81 / 11.85.
_DEFAULT_BLOCKS = (512, 512)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    num_heads: int,
                    causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None) -> jnp.ndarray:
  """Pallas flash attention over `num_heads` heads, [B, T, H x D] in and
  out. Fully differentiable (one custom backward kernel, `flash_bwd`).

  k and v are [B, T, H_kv x D], H_kv dividing H (grouped-query heads; H_kv
  == H is plain multi-head attention): query head h reads key/value head
  h // (H / H_kv). Where a program holds one head (D 128 and D 256) the
  kernels read them at that width through their index maps and return dK
  and dV there, summed over each group in float32 inside the backward;
  elsewhere k and v are repeated to the query heads here, and autodiff sums
  their cotangents back.

  `window` (causal only): query i sees keys i - window + 1 .. i, `window`
  keys counting its own (a sliding-window layer's band). The kernels then
  skip every tile wholly outside the band, the forward's key loop starting
  at the band's lower edge and the backward's query loop ending at it, and
  mask the tiles that straddle it; they are named `flash_window_fwd` and
  `flash_window_bwd`. A
  window that reaches the first key from every row (window >= T) is plain
  causal attention and runs the causal kernels.

  The layout is the projections' own, as `nn.Dense` writes and reads it,
  and stays whole: the kernels index heads through their `BlockSpec`s
  (`lane_block`), so no head-split transpose runs on either side of them.
  (A [B, T, H, D] view would be no bitcast on the chip: a minor dimension
  of 64 is tiled to 128 lanes, so each reshape is a copy, 0.82 ms at
  128 x 2048 x 8 x 64; PERF.md section 6, PR 30.)

  Sequences that don't tile the block size are padded to the next block
  multiple and masked — never a silent O(T^2) fallback. `interpret=None`
  auto-selects PER LOWERING PLATFORM: real kernels in TPU-target
  programs, the interpreter elsewhere (CPU tests). Cross-attention
  (Tq != Tk) falls back to the reference implementation (the kernels
  assume self-attention layout). `block_q`/`block_k` default to the
  on-chip measured winners (`_DEFAULT_BLOCKS`).
  """
  b, t, hd = q.shape
  if hd % num_heads:
    raise ValueError(f"{num_heads} heads do not divide the last dimension "
                     f"of {q.shape}")
  d = hd // num_heads
  if (k.shape != v.shape or k.shape[-1] % d
      or num_heads % (k.shape[-1] // d)):
    raise ValueError(f"k {k.shape} and v {v.shape} hold no whole number of "
                     f"heads of {d} that divides {num_heads}")
  group = num_heads // (k.shape[-1] // d)
  if group > 1 and (k.shape[1] != t or lane_block(num_heads, d) != d):
    k, v = (jnp.repeat(x.reshape(x.shape[:2] + (-1, d)), group, axis=2)
            .reshape(x.shape[:2] + (hd,)) for x in (k, v))
  if window is not None:
    if not causal or window < 1:
      raise ValueError(f"a window ({window}) needs causal attention and at "
                       "least one key a row")
    if window >= k.shape[1]:
      window = None
  block_q = _DEFAULT_BLOCKS[0] if block_q is None else block_q
  block_k = _DEFAULT_BLOCKS[1] if block_k is None else block_k
  if k.shape[1] != t:
    heads = lambda x: x.reshape(b, -1, num_heads, d).transpose(0, 2, 1, 3)
    return attention(heads(q), heads(k), heads(v), causal=causal,
                     window=window).transpose(0, 2, 1, 3).reshape(b, t, hd)
  if interpret is None:
    # lax.platform_dependent, NOT jax.default_backend(): the process
    # backend bakes the HOST platform into the trace, so AOT-lowering a
    # TPU-topology program from a CPU host silently compiled (and cost-
    # priced) the interpreter emulation instead of the Mosaic kernel in
    # every path that relied on the auto-select (round-5 review catch;
    # pinned by test_default_interpret_lowers_mosaic_for_tpu). The
    # platform switch folds away in single-platform lowerings. The
    # barriers keep XLA:TPU from staging the cond's operands/results in
    # scoped VMEM at long T (same failure mode as the in-kernel
    # barriers below — 16 MB "stack" allocations at T=8192/h512).
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    return jax.lax.optimization_barrier(jax.lax.platform_dependent(
        q, k, v,
        tpu=functools.partial(flash_attention, num_heads=num_heads,
                              causal=causal, block_q=block_q,
                              block_k=block_k, interpret=False,
                              window=window),
        default=functools.partial(flash_attention, num_heads=num_heads,
                                  causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=True,
                                  window=window)))
  # Normalize blocks to powers of two in [_MIN_BLOCK, next_pow2(T)]: the
  # padding arithmetic below relies on lcm(bq, bk) == max(bq, bk), which
  # only holds for powers of two.
  eff_bq = max(_MIN_BLOCK, min(_pow2_floor(block_q), _next_pow2(t)))
  eff_bk = max(_MIN_BLOCK, min(_pow2_floor(block_k), _next_pow2(t)))
  tile = max(eff_bq, eff_bk)
  t_pad = ((t + tile - 1) // tile) * tile
  assert t_pad % eff_bq == 0 and t_pad % eff_bk == 0
  if t_pad != t:
    pad = ((0, 0), (0, t_pad - t), (0, 0))
    q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
  if not interpret:
    # XLA:TPU fuses surrounding layout ops (the non-tiling-T pads above,
    # a caller's own transposes) into the custom-call's
    # scoped-VMEM region; at long T the fused
    # operands/results exceed VMEM and compilation fails with
    # RESOURCE_EXHAUSTED "allocating on stack" (found at T=8192/h512 by
    # the round-5 seqattn duel — interpret mode hid it, like the
    # round-4 lse blocker). The barrier — placed directly on the kernel
    # operands, AFTER any padding — pins them to plain HBM buffers;
    # since its transpose rule is itself a barrier, the backward
    # kernels get the same protection. Pinned by TestFlashMosaicLowering
    # test_long_context_train_graph_compiles.
    q, k, v = jax.lax.optimization_barrier((q, k, v))
  out = _flash(num_heads, causal, eff_bq, eff_bk, t, interpret, window, q, k,
               v)
  if not interpret:
    out = jax.lax.optimization_barrier(out)  # see the entry barrier
  return out[:, :t] if t_pad != t else out


# -- Ulysses attention (all_to_all sequence parallelism) ---------------------


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      mesh: Mesh,
                      axis_name: str = "sp",
                      causal: bool = False,
                      batch_axis: Optional[str] = "data",
                      inner: str = "reference",
                      flash_interpret: Optional[bool] = None) -> jnp.ndarray:
  """Exact attention with the sequence dim sharded via head all_to_all
  (DeepSpeed-Ulysses style).

  Inputs are global [B, H, T, D] arrays with T sharded over `axis_name`
  (size S). all_to_alls re-shard q/k/v to [B, H/S, T, D] — each device
  holds its head group over the FULL sequence — the inner attention runs
  unchanged (including causal masking), and a transpose all_to_all
  restores the output's sequence sharding. Communication is 4
  activation-sized all_to_alls per forward (q, k, v inbound + output; 8
  with the VJP) in a FIXED number of steps, vs the ring's S-1 sequential
  K/V hops — at the cost of H % S == 0. The right trade when heads are
  plentiful and per-hop ring latency would dominate.

  `inner` selects the full-sequence kernel on each device: 'reference'
  (XLA) or 'flash' (the Pallas kernel).
  """
  s = mesh.shape[axis_name]
  b, h, t, d = q.shape
  if h % s:
    raise ValueError(f"num_heads={h} must be divisible by the "
                     f"'{axis_name}' axis size {s} for Ulysses "
                     f"(head-group all_to_all)")
  if k.shape[2] != t:
    raise ValueError("ulysses_attention assumes self-attention layout "
                     f"(Tq={t} != Tk={k.shape[2]})")
  if inner not in ("reference", "flash"):
    raise ValueError(f"Unknown inner kernel {inner!r}")
  io_spec = PartitionSpec(batch_axis, None, axis_name, None)

  def local_fn(q_l, k_l, v_l):
    # Shapes here are LOCAL: [B_l, H, T/S, D]. Both all_to_alls use the
    # symmetric split_axis == concat_axis == 0 form with explicit
    # transposes around them: the form with distinct split/concat axes
    # produced a mis-ordered cotangent under autodiff whenever H/S > 1
    # (dims swapped in the VJP), while the 0,0 form is self-transpose.
    b_l, _, t_l, _ = q_l.shape

    # The full-sequence side of both all_to_alls is [B_l, H/S, T, D] for
    # `attention` and [B_l, T, H/S x D] for `flash_attention`: the same
    # permutes, ending in the layout the inner kernel takes.
    flash = inner == "flash"

    def seq_to_heads(x):
      # [B_l,H,T_l,D] -> [S,B_l,H/S,T_l,D] -(a2a)-> src-major ->
      # [B_l,H/S,T,D] (flash: [B_l,T,H/S x D]); source order == sequence
      # order, so the merge reassembles the global sequence.
      x = x.reshape(b_l, s, h // s, t_l, d)
      x = jnp.moveaxis(x, 1, 0)
      x = jax.lax.all_to_all(x, axis_name, 0, 0)   # [S(src),B_l,H/S,T_l,D]
      if flash:
        x = x.transpose(1, 0, 3, 2, 4)             # [B_l,S,T_l,H/S,D]
        return x.reshape(b_l, s * t_l, h // s * d)
      x = x.transpose(1, 2, 0, 3, 4)               # [B_l,H/S,S,T_l,D]
      return x.reshape(b_l, h // s, s * t_l, d)

    def heads_to_seq(x):
      # inverse: [B_l,H/S,T,D] (flash: [B_l,T,H/S x D]) ->
      # [S,B_l,H/S,T_l,D] -(a2a)-> head-group-major -> [B_l,H,T_l,D]
      if flash:
        x = x.reshape(b_l, s, t_l, h // s, d).transpose(1, 0, 3, 2, 4)
      else:
        x = x.reshape(b_l, h // s, s, t_l, d).transpose(2, 0, 1, 3, 4)
      x = jax.lax.all_to_all(x, axis_name, 0, 0)   # [S(grp),B_l,H/S,T_l,D]
      x = jnp.moveaxis(x, 0, 1)                    # [B_l,S,H/S,T_l,D]
      return x.reshape(b_l, h, t_l, d)

    q_g, k_g, v_g = seq_to_heads(q_l), seq_to_heads(k_l), seq_to_heads(v_l)
    if flash:
      out = flash_attention(q_g, k_g, v_g, h // s, causal=causal,
                            interpret=flash_interpret)
    else:
      out = attention(q_g, k_g, v_g, causal=causal)
    return heads_to_seq(out)

  sharded = mesh_lib.shard_map(
      local_fn, mesh=mesh,
      in_specs=(io_spec, io_spec, io_spec),
      out_specs=io_spec)
  return sharded(q, k, v)


# -- ring attention (context parallelism) ------------------------------------


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   mesh: Mesh,
                   axis_name: str = "sp",
                   causal: bool = False,
                   batch_axis: Optional[str] = "data",
                   block_k: Optional[int] = None) -> jnp.ndarray:
  """Exact attention with the sequence dim sharded over `axis_name`.

  Inputs are global [B, H, T, D] arrays (T divisible by the axis size).
  Each device keeps its Q shard resident and absorbs one rotating K/V
  block per ring hop; `ppermute` rides the ICI ring. Returns the global
  [B, H, T, D] output with the same sharding.

  `block_k` additionally chunks each hop's K/V block through the online
  softmax (a lax.scan), bounding per-hop score memory at
  [B, H, Tq_local, block_k] instead of [B, H, Tq_local, Tk_local] —
  flash-style streaming inside the ring, useful when the per-device
  shard is itself long. Must divide the local block length.
  """
  axis_size = mesh.shape[axis_name]
  if block_k is not None and (k.shape[2] // axis_size) % block_k:
    raise ValueError(
        f"block_k={block_k} must divide the per-device K length "
        f"{k.shape[2] // axis_size} (T={k.shape[2]} over "
        f"{axis_size} '{axis_name}' shards)")
  io_spec = PartitionSpec(batch_axis, None, axis_name, None)

  def local_fn(q_local, k_local, v_local):
    idx = jax.lax.axis_index(axis_name)
    tq = q_local.shape[2]
    m, l, o = _online_init(q_local)
    k_blk, v_blk = k_local, v_local

    def absorb(src, m, l, o, k_blk, v_blk):
      q_pos = idx * tq + jnp.arange(tq)
      if block_k is None:
        mask = None
        if causal:
          k_pos = src * tq + jnp.arange(tq)
          mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
        return _online_block_update(q_local, k_blk, v_blk, m, l, o, mask)
      num_chunks = k_blk.shape[2] // block_k  # divisibility checked above
      # [C, B, H, block_k, D] chunk-major for the scan.
      k_chunks = jnp.moveaxis(
          k_blk.reshape(k_blk.shape[:2] + (num_chunks, block_k, -1)),
          2, 0)
      v_chunks = jnp.moveaxis(
          v_blk.reshape(v_blk.shape[:2] + (num_chunks, block_k, -1)),
          2, 0)

      def chunk_step(carry, chunk):
        m, l, o = carry
        c_idx, k_c, v_c = chunk
        mask = None
        if causal:
          k_pos = src * tq + c_idx * block_k + jnp.arange(block_k)
          mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
        return _online_block_update(q_local, k_c, v_c, m, l, o, mask), None

      (m, l, o), _ = jax.lax.scan(
          chunk_step, (m, l, o),
          (jnp.arange(num_chunks), k_chunks, v_chunks))
      return m, l, o

    for step in range(axis_size):
      src = (idx - step) % axis_size  # whose shard we currently hold
      m, l, o = absorb(src, m, l, o, k_blk, v_blk)
      if step + 1 < axis_size:
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    return _normalize(l, o, q_local.shape[-1])[0].astype(q_local.dtype)

  sharded = mesh_lib.shard_map(
      local_fn, mesh=mesh,
      in_specs=(io_spec, io_spec, io_spec),
      out_specs=io_spec)
  return sharded(q, k, v)
