"""Multi-head attention module over the fused/parallel attention ops.

Model-facing wrapper for `ops/attention`: QKV/output projections as flax
params, with the core score/softmax/combine delegated to the reference
jnp implementation, the Pallas flash kernel, or ring attention over a
sequence-parallel mesh axis — selected by a constructor argument so the
same module scales from one chip to a long-context pod.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp
from jax.sharding import Mesh

from tensor2robot_tpu.ops import attention as attention_ops

__all__ = ["MultiHeadAttention"]


class MultiHeadAttention(nn.Module):
  """[B, T, F] -> [B, T, F] self-attention (or cross via `kv`)."""

  num_heads: int = 4
  head_dim: int = 32
  causal: bool = False
  dropout_rate: float = 0.0
  backend: str = "reference"  # 'reference'|'flash'|'ring'|'ulysses'
  mesh: Optional[Mesh] = None  # required for 'ring'/'ulysses'
  sp_axis: str = "sp"
  ulysses_inner: str = "reference"  # per-device kernel under 'ulysses'
  # Pallas interpret mode for the flash paths. Models that know their
  # target pass it STATICALLY (device_type != 'tpu') — the None
  # auto-select emits a lax.platform_dependent switch whose branch
  # buffers XLA:TPU stack-allocates in scoped VMEM at long T (the
  # round-5 T=8192 compile blocker).
  flash_interpret: Optional[bool] = None
  dtype: Optional[jnp.dtype] = None  # compute dtype for the projections

  @nn.compact
  def __call__(self, x: jnp.ndarray,
               kv: Optional[jnp.ndarray] = None,
               train: bool = False) -> jnp.ndarray:
    kv = x if kv is None else kv
    b, t, _ = x.shape
    proj = self.num_heads * self.head_dim
    # Explicit dtype: keeps direct module.apply in the intended compute
    # dtype (the policy wrapper's param downcast covers the trained
    # path; standalone use has no wrapper).
    q = nn.Dense(proj, dtype=self.dtype, name="q_proj")(x)
    k = nn.Dense(proj, dtype=self.dtype, name="k_proj")(kv)
    v = nn.Dense(proj, dtype=self.dtype, name="v_proj")(kv)

    if self.backend == "flash":
      # The flash kernels read and write the projections' own layout.
      out = attention_ops.flash_attention(
          q, k, v, self.num_heads, causal=self.causal,
          interpret=self.flash_interpret)
    else:
      def heads(y):
        return y.reshape(b, -1, self.num_heads,
                         self.head_dim).transpose(0, 2, 1, 3)

      q, k, v = heads(q), heads(k), heads(v)  # [B, H, T, D]
      if self.backend == "ring":
        if self.mesh is None:
          raise ValueError("ring backend requires a mesh.")
        out = attention_ops.ring_attention(
            q, k, v, self.mesh, axis_name=self.sp_axis, causal=self.causal)
      elif self.backend == "ulysses":
        if self.mesh is None:
          raise ValueError("ulysses backend requires a mesh.")
        out = attention_ops.ulysses_attention(
            q, k, v, self.mesh, axis_name=self.sp_axis, causal=self.causal,
            inner=self.ulysses_inner,
            flash_interpret=self.flash_interpret)
      else:
        out = attention_ops.attention(q, k, v, causal=self.causal)
      out = out.transpose(0, 2, 1, 3).reshape(b, t, proj)
    if self.dropout_rate:
      out = nn.Dropout(self.dropout_rate, name="dropout")(
          out, deterministic=not train)
    return nn.Dense(x.shape[-1], dtype=self.dtype, name="out_proj")(out)
