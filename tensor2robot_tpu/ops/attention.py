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

All functions take [batch, heads, seq, head_dim] ("BHTD") arrays.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec

from tensor2robot_tpu.parallel import mesh as mesh_lib

__all__ = ["attention", "cached_attention", "flash_attention",
           "ring_attention", "ulysses_attention"]


def _mask_value(dtype) -> jnp.ndarray:
  return jnp.asarray(jnp.finfo(dtype).min / 2, dtype)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = False) -> jnp.ndarray:
  """Reference softmax attention, [B, H, T, D]."""
  scale = 1.0 / math.sqrt(q.shape[-1])
  scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
  if causal:
    tq, tk = scores.shape[-2], scores.shape[-1]
    mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
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
# logsumexp needed by the backward. Backward: FlashAttention-2 style
# recompute kernels (one producing dQ over the q-block grid, one
# producing dK/dV over the k-block grid) — the [T, T] score matrix never
# materializes in HBM in either direction. Sequences that don't tile are
# PADDED to the block size and masked (never a silent O(T^2) fallback).
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
# elementwise passes they had and lose what crosses lanes: dK/dV works
# on the transposed tile, the forward's row sum rides the p.v product
# where a head leaves it idle columns (`_sum_rides`), and `delta` no
# longer travels as a lane-padded column.


def _valid_mask(q_start, k_start, q_block, k_block, causal: bool,
                valid_len: int, padded_len: int, q_axis: int = 0):
  """Score-entry validity: causal triangle + key/query padding. The tile
  is [q_block, k_block], or its transpose where `q_axis` is 1."""
  if not causal and valid_len == padded_len:
    return None
  shape = (q_block, k_block) if q_axis == 0 else (k_block, q_block)
  q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
  k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
  mask = jnp.ones(shape, bool)
  if causal:
    mask &= q_pos >= k_pos
  if valid_len != padded_len:
    mask &= (k_pos < valid_len) & (q_pos < valid_len)
  return mask


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      block_k: int, causal: bool, q_block: int,
                      valid_len: int):
  """One (batch*head, q_block) program: stream K/V blocks through VMEM."""
  q = q_ref[:]  # [block_q, D]
  tq_idx = pl.program_id(1)
  seq_len = k_ref.shape[0]
  num_k_blocks = seq_len // block_k
  if causal:
    # Future blocks are fully masked: stop the stream at the diagonal.
    num_k_blocks = jnp.minimum(
        num_k_blocks,
        ((tq_idx + 1) * q_block + block_k - 1) // block_k)

  def body(kb, carry):
    k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
    v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
    mask = _valid_mask(tq_idx * q_block, kb * block_k, q_block, block_k,
                       causal, valid_len, seq_len)
    return _online_block_update(q, k_blk, v_blk, *carry, mask)

  m, l, o = jax.lax.fori_loop(0, num_k_blocks, body, _online_init(q))
  out, l = _normalize(l, o, q.shape[-1])
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
  lse_ref[:] = jnp.where(row_valid, m + jnp.log(l), 0.0)


def _delta(do, o):
  """delta_i = sum_d dO_id * O_id (FlashAttention-2's backward precompute),
  float32 [..., T, 1]. The one definition both backward kernels use: dQ
  calls it on its own block, `_flash_bwd` on the whole for dK/dV."""
  return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                 keepdims=True)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         dq_ref, *, block_k: int, causal: bool,
                         q_block: int, valid_len: int):
  """dQ for one q block: dS = P * (dO.V^T - delta); dQ = scale * dS.K.

  `delta` is taken here from the block's own rows of dO and O, once a
  program (+0.29 ms a call): as an operand it would be a [T, 1] column,
  which XLA keeps padded to 128 lanes (1 GB written a call at the
  benchmark's shape, 1.42 ms).
  """
  scale = 1.0 / math.sqrt(q_ref.shape[-1])
  q = q_ref[:]
  do = do_ref[:].astype(jnp.float32)
  lse = lse_ref[:]      # [block_q, 1]
  delta = _delta(do, o_ref[:])
  tq_idx = pl.program_id(1)
  seq_len = k_ref.shape[0]
  num_k_blocks = seq_len // block_k
  if causal:
    num_k_blocks = jnp.minimum(
        num_k_blocks,
        ((tq_idx + 1) * q_block + block_k - 1) // block_k)

  def body(kb, dq):
    k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
    v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
    s = jnp.matmul(q, k_blk.T,
                   preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse)
    mask = _valid_mask(tq_idx * q_block, kb * block_k, q_block, block_k,
                       causal, valid_len, seq_len)
    if mask is not None:
      p = jnp.where(mask, p, 0.0)
    dp = jnp.matmul(do, v_blk.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return dq + jnp.matmul(ds, k_blk,
                           preferred_element_type=jnp.float32)

  dq0 = jnp.zeros((q_block, q.shape[-1]), jnp.float32)
  dq_ref[:] = jax.lax.fori_loop(0, num_k_blocks, body, dq0).astype(
      dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          k_block: int, valid_len: int):
  """dK/dV for one k block: dV = P^T.dO; dK = scale * dS^T.Q.

  Works on the transposed tile: S^T = K.Q^T and dP^T = V.dO^T come out of
  the MXU as [k_block, block_q], so P^T and dS^T feed the two
  accumulating products as they are (transposing P and dS cost two
  passes through the XLU a tile, a fifth of the kernel). `lse` and
  `delta` arrive as lane-dense rows, [T // block_q, 1, block_q]: a
  sublane broadcast a tile, and 16 KB of VMEM at T 2048 where the [T, 1]
  columns, padded to 128 lanes, held 1 MB each.
  """
  scale = 1.0 / math.sqrt(q_ref.shape[-1])
  k_blk = k_ref[:]
  v_blk = v_ref[:]
  tk_idx = pl.program_id(1)
  seq_len = q_ref.shape[0]
  num_q_blocks = seq_len // block_q
  start_q = 0
  if causal:
    # Blocks strictly above the diagonal see no unmasked entries.
    start_q = (tk_idx * k_block) // block_q

  def body(qb, carry):
    dk, dv = carry
    q_blk = q_ref[pl.ds(qb * block_q, block_q), :]
    do_blk = do_ref[pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
    st = jnp.matmul(k_blk, q_blk.T,
                    preferred_element_type=jnp.float32) * scale
    pt = jnp.exp(st - lse_ref[qb])                    # row [1, block_q]
    mask = _valid_mask(qb * block_q, tk_idx * k_block, block_q, k_block,
                       causal, valid_len, seq_len, q_axis=1)
    if mask is not None:
      pt = jnp.where(mask, pt, 0.0)
    dv = dv + jnp.matmul(pt, do_blk, preferred_element_type=jnp.float32)
    dpt = jnp.matmul(v_blk, do_blk.T, preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta_ref[qb]) * scale
    dk = dk + jnp.matmul(dst, q_blk, preferred_element_type=jnp.float32)
    return dk, dv

  dk0 = jnp.zeros((k_block, k_blk.shape[-1]), jnp.float32)
  dv0 = jnp.zeros((k_block, v_blk.shape[-1]), jnp.float32)
  dk, dv = jax.lax.fori_loop(start_q, num_q_blocks, body, (dk0, dv0))
  dk_ref[:] = dk.astype(dk_ref.dtype)
  dv_ref[:] = dv.astype(dv_ref.dtype)


def _flash_forward(q3, k3, v3, causal, block_q, block_k, valid_len,
                   interpret):
  bh, t, d = q3.shape
  kernel = functools.partial(
      _flash_fwd_kernel, block_k=block_k, causal=causal, q_block=block_q,
      valid_len=valid_len)
  out, lse = pl.pallas_call(
      kernel,
      grid=(bh, t // block_q),
      in_specs=[
          pl.BlockSpec((None, block_q, d), lambda b, qb: (b, qb, 0)),
          pl.BlockSpec((None, t, d), lambda b, qb: (b, 0, 0)),
          pl.BlockSpec((None, t, d), lambda b, qb: (b, 0, 0)),
      ],
      out_specs=[
          pl.BlockSpec((None, block_q, d), lambda b, qb: (b, qb, 0)),
          pl.BlockSpec((None, block_q, 1), lambda b, qb: (b, qb, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
          jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
      ],
      interpret=interpret,
      name="flash_fwd",
  )(q3, k3, v3)
  return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash(causal: bool, block_q: int, block_k: int, valid_len: int,
           interpret: bool, q3, k3, v3):
  out, _ = _flash_forward(q3, k3, v3, causal, block_q, block_k,
                          valid_len, interpret)
  return out


def _flash_fwd(causal, block_q, block_k, valid_len, interpret, q3, k3, v3):
  out, lse = _flash_forward(q3, k3, v3, causal, block_q, block_k,
                            valid_len, interpret)
  return out, (q3, k3, v3, out, lse)


def _flash_bwd(causal, block_q, block_k, valid_len, interpret, residuals,
               g):
  q3, k3, v3, out, lse = residuals
  bh, t, d = q3.shape
  # One row a q block for the dK/dV kernel (the block is the whole of the
  # last two dims, so every block_q lowers, sub-128 ones too).
  rows = (bh, t // block_q, 1, block_q)
  # For dK/dV, which needs every q block's `delta` as a row and cannot
  # take it from its own block as dQ does.
  delta = _delta(g, out).reshape(rows)
  dq_kernel = functools.partial(
      _flash_bwd_dq_kernel, block_k=block_k, causal=causal,
      q_block=block_q, valid_len=valid_len)
  dq = pl.pallas_call(
      dq_kernel,
      grid=(bh, t // block_q),
      in_specs=[
          pl.BlockSpec((None, block_q, d), lambda b, qb: (b, qb, 0)),
          pl.BlockSpec((None, t, d), lambda b, qb: (b, 0, 0)),
          pl.BlockSpec((None, t, d), lambda b, qb: (b, 0, 0)),
          pl.BlockSpec((None, block_q, d), lambda b, qb: (b, qb, 0)),
          pl.BlockSpec((None, block_q, d), lambda b, qb: (b, qb, 0)),
          pl.BlockSpec((None, block_q, 1), lambda b, qb: (b, qb, 0)),
      ],
      out_specs=pl.BlockSpec((None, block_q, d), lambda b, qb: (b, qb, 0)),
      out_shape=jax.ShapeDtypeStruct((bh, t, d), q3.dtype),
      interpret=interpret,
      name="flash_bwd_dq",
  )(q3, k3, v3, g, out, lse)
  dkv_kernel = functools.partial(
      _flash_bwd_dkv_kernel, block_q=block_q, causal=causal,
      k_block=block_k, valid_len=valid_len)
  rows_spec = pl.BlockSpec((None,) + rows[1:], lambda b, kb: (b, 0, 0, 0))
  dk, dv = pl.pallas_call(
      dkv_kernel,
      grid=(bh, t // block_k),
      in_specs=[
          pl.BlockSpec((None, t, d), lambda b, kb: (b, 0, 0)),
          pl.BlockSpec((None, block_k, d), lambda b, kb: (b, kb, 0)),
          pl.BlockSpec((None, block_k, d), lambda b, kb: (b, kb, 0)),
          pl.BlockSpec((None, t, d), lambda b, kb: (b, 0, 0)),
          rows_spec,
          rows_spec,
      ],
      out_specs=[
          pl.BlockSpec((None, block_k, d), lambda b, kb: (b, kb, 0)),
          pl.BlockSpec((None, block_k, d), lambda b, kb: (b, kb, 0)),
      ],
      out_shape=[
          jax.ShapeDtypeStruct((bh, t, d), k3.dtype),
          jax.ShapeDtypeStruct((bh, t, d), v3.dtype),
      ],
      interpret=interpret,
      name="flash_bwd_dkv",
  )(q3, k3, v3, g, lse.reshape(rows), delta)
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
# compiles at (v5e, 2026-10-01, PERF.md section 6, PR 27): the whole train
# step of `configs/train_longcontext_flash.gin` (hidden 512, 8 heads x 64,
# bf16), milliseconds a step by (block_q, block_k).
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
_DEFAULT_BLOCKS = (512, 512)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
  """Pallas flash attention, [B, H, T, D]. Fully differentiable
  (custom FlashAttention-2 backward kernels).

  Sequences that don't tile the block size are padded to the next block
  multiple and masked — never a silent O(T^2) fallback. `interpret=None`
  auto-selects PER LOWERING PLATFORM: real kernels in TPU-target
  programs, the interpreter elsewhere (CPU tests). Cross-attention
  (Tq != Tk) falls back to the reference implementation (the kernels
  assume self-attention layout). `block_q`/`block_k` default to the
  on-chip measured winners (`_DEFAULT_BLOCKS`).
  """
  b, h, t, d = q.shape
  block_q = _DEFAULT_BLOCKS[0] if block_q is None else block_q
  block_k = _DEFAULT_BLOCKS[1] if block_k is None else block_k
  if k.shape[2] != t:
    return attention(q, k, v, causal=causal)
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
        tpu=functools.partial(flash_attention, causal=causal,
                              block_q=block_q, block_k=block_k,
                              interpret=False),
        default=functools.partial(flash_attention, causal=causal,
                                  block_q=block_q, block_k=block_k,
                                  interpret=True)))
  # Normalize blocks to powers of two in [_MIN_BLOCK, next_pow2(T)]: the
  # padding arithmetic below relies on lcm(bq, bk) == max(bq, bk), which
  # only holds for powers of two.
  eff_bq = max(_MIN_BLOCK, min(_pow2_floor(block_q), _next_pow2(t)))
  eff_bk = max(_MIN_BLOCK, min(_pow2_floor(block_k), _next_pow2(t)))
  tile = max(eff_bq, eff_bk)
  t_pad = ((t + tile - 1) // tile) * tile
  assert t_pad % eff_bq == 0 and t_pad % eff_bk == 0
  q3 = q.reshape(b * h, t, d)
  k3 = k.reshape(b * h, t, d)
  v3 = v.reshape(b * h, t, d)
  if t_pad != t:
    pad = ((0, 0), (0, t_pad - t), (0, 0))
    q3 = jnp.pad(q3, pad)
    k3 = jnp.pad(k3, pad)
    v3 = jnp.pad(v3, pad)
  if not interpret:
    # XLA:TPU fuses surrounding layout ops (the model layer's
    # BTHD->BHTD head-split transposes, the non-tiling-T pads above)
    # into the custom-call's scoped-VMEM region; at long T the fused
    # operands/results exceed VMEM and compilation fails with
    # RESOURCE_EXHAUSTED "allocating on stack" (found at T=8192/h512 by
    # the round-5 seqattn duel — interpret mode hid it, like the
    # round-4 lse blocker). The barrier — placed directly on the kernel
    # operands, AFTER any padding — pins them to plain HBM buffers;
    # since its transpose rule is itself a barrier, the backward
    # kernels get the same protection. Pinned by TestFlashMosaicLowering
    # test_long_context_train_graph_compiles.
    q3, k3, v3 = jax.lax.optimization_barrier((q3, k3, v3))
  out = _flash(causal, eff_bq, eff_bk, t, interpret, q3, k3, v3)
  if not interpret:
    out = jax.lax.optimization_barrier(out)  # see the entry barrier
  if t_pad != t:
    out = out[:, :t]
  return out.reshape(b, h, t, d)


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

    def seq_to_heads(x):
      # [B_l,H,T_l,D] -> [S,B_l,H/S,T_l,D] -(a2a)-> src-major ->
      # [B_l,H/S,T,D]; source order == sequence order, so the merge
      # reassembles the global sequence.
      x = x.reshape(b_l, s, h // s, t_l, d)
      x = jnp.moveaxis(x, 1, 0)
      x = jax.lax.all_to_all(x, axis_name, 0, 0)   # [S(src),B_l,H/S,T_l,D]
      x = x.transpose(1, 2, 0, 3, 4)               # [B_l,H/S,S,T_l,D]
      return x.reshape(b_l, h // s, s * t_l, d)

    def heads_to_seq(x):
      # inverse: [B_l,H/S,T,D] -> [S,B_l,H/S,T_l,D] -(a2a)->
      # head-group-major -> [B_l,H,T_l,D]
      x = x.reshape(b_l, h // s, s, t_l, d)
      x = x.transpose(2, 0, 1, 3, 4)               # [S,B_l,H/S,T_l,D]
      x = jax.lax.all_to_all(x, axis_name, 0, 0)   # [S(grp),B_l,H/S,T_l,D]
      x = jnp.moveaxis(x, 0, 1)                    # [B_l,S,H/S,T_l,D]
      return x.reshape(b_l, h, t_l, d)

    q_g, k_g, v_g = seq_to_heads(q_l), seq_to_heads(k_l), seq_to_heads(v_l)
    if inner == "flash":
      out = flash_attention(q_g, k_g, v_g, causal=causal,
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
