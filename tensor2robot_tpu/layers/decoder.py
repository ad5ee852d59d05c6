"""A decoder block built from a layer-type list: a mixer slot (linear
attention by the gated delta rule, or gated softmax attention) and a
feed-forward slot (routed experts with a shared expert), each behind a
zero-centred RMSNorm and added to the residual stream.

The equations are those of the public `qwen3_next` modelling code
(huggingface transformers, `modeling_qwen3_next.py`: `Qwen3NextRMSNorm`,
`Qwen3NextRMSNormGated`, `Qwen3NextAttention`, `Qwen3NextGatedDeltaNet`,
`Qwen3NextDecoderLayer`), written for this repository's layouts:

* `ZeroCentredRMSNorm`: x / sqrt(mean(x^2) + eps) * (1 + w), w from 0.
* `GatedAttention`: q with an output gate from one projection, per-head
  RMSNorm of q and k, rotary embedding on the leading `rotary_fraction` of
  each head (half-split pairing), grouped-query causal softmax attention,
  the result times sigmoid(gate). The attention itself is
  `ops/attention.flash_attention` on [B, T, H x D]; its kernels take as
  many key/value heads as query heads, so k and v are repeated to the
  query heads ahead of it (their gradients sum over the group): a
  departure in layout, not in mathematics.
* `GatedDeltaNet`: one projection to [q, k, v, z] and one to [b, a], a
  causal depthwise convolution and SiLU over [q, k, v], the gated delta
  rule per value head (`ops/linear_attention`), RMSNorm of the result
  times SiLU(z), the output projection. The source interleaves q, k, v, z
  per key head inside its projection; here they lie one after the other
  (a permutation of the projection's columns).

Norms, gates, the rule's state and the softmax are float32; projections
and products take `dtype`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tensor2robot_tpu.layers import moe as moe_lib
from tensor2robot_tpu.ops import attention as attention_ops
from tensor2robot_tpu.ops import linear_attention

__all__ = ["DecoderConfig", "ZeroCentredRMSNorm", "GatedAttention",
           "GatedDeltaNet", "HybridDecoderBlock", "rotary_tables",
           "apply_partial_rotary", "matrix_init"]

INIT_STDDEV = 0.02


def matrix_init():
  """Normal(0, 0.02), the source's `initializer_range`, for every matrix."""
  return nn.initializers.normal(INIT_STDDEV)


def a_log_init(key, shape, dtype=jnp.float32):
  """A_log = log U(0, 16), as the source's module draws it."""
  return jnp.log(jnp.maximum(
      jax.random.uniform(key, shape, dtype, 0.0, 16.0), 1e-6))


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
  """Every size of the block, under the source config's own names where it
  has one. `layer_types` gives the mixer of each layer."""

  hidden_size: int = 2048
  layer_types: Tuple[str, ...] = ("linear", "linear", "linear", "full")
  rms_norm_eps: float = 1e-6
  # gated softmax attention
  num_attention_heads: int = 16
  num_key_value_heads: int = 2
  head_dim: int = 256
  partial_rotary_factor: float = 0.25
  rope_theta: float = 1e7
  # gated delta rule
  linear_num_key_heads: int = 16
  linear_num_value_heads: int = 32
  linear_key_head_dim: int = 128
  linear_value_head_dim: int = 128
  linear_conv_kernel_dim: int = 4
  # experts
  num_experts: int = 512            # the router's width
  experts_held: Tuple[int, int] = (0, 32)   # (first, count) held here
  num_experts_per_tok: int = 10
  moe_intermediate_size: int = 512
  shared_expert_intermediate_size: int = 512
  expert_buffer_factor: float = 2.0
  # Pallas interpreted (off the TPU) or not; None: by lowering platform.
  flash_interpret: Optional[bool] = None


def _dense(features: int, dtype, name: str):
  return nn.Dense(features, use_bias=False, dtype=dtype,
                  kernel_init=matrix_init(), name=name)


def _rms(x, eps: float):
  x = x.astype(jnp.float32)
  return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


class ZeroCentredRMSNorm(nn.Module):
  eps: float = 1e-6

  @nn.compact
  def __call__(self, x):
    weight = self.param("weight", nn.initializers.zeros, (x.shape[-1],))
    y = _rms(x, self.eps) * (1.0 + weight.astype(jnp.float32))
    return y.astype(x.dtype)


def rotary_tables(length: int, rotary_dim: int, theta: float):
  """(cos, sin) [length, rotary_dim / 2] for positions 0..length-1."""
  inv_freq = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                       / rotary_dim)
  angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
  return jnp.cos(angles), jnp.sin(angles)


def apply_partial_rotary(x, cos, sin):
  """Rotates the leading 2 x cos.shape[-1] dimensions of each head of x
  [B, T, H, D], pairing dimension i with i + half (the source's
  `rotate_half`), and passes the rest through."""
  half = cos.shape[-1]
  x32 = x.astype(jnp.float32)
  x1, x2, rest = x32[..., :half], x32[..., half:2 * half], x32[..., 2 * half:]
  cos, sin = cos[None, :, None, :], sin[None, :, None, :]
  out = jnp.concatenate(
      [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
  return out.astype(x.dtype)


class GatedAttention(nn.Module):
  config: DecoderConfig
  dtype: Optional[Any] = None

  @nn.compact
  def __call__(self, x):
    cfg = self.config
    b, t, _ = x.shape
    heads, kv_heads, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
    with jax.named_scope("attn_gated"):
      q, gate = jnp.split(
          _dense(2 * heads * d, self.dtype, "q_proj")(x), 2, axis=-1)
      k = _dense(kv_heads * d, self.dtype, "k_proj")(x)
      v = _dense(kv_heads * d, self.dtype, "v_proj")(x)
      q = ZeroCentredRMSNorm(cfg.rms_norm_eps, name="q_norm")(
          q.reshape(b, t, heads, d))
      k = ZeroCentredRMSNorm(cfg.rms_norm_eps, name="k_norm")(
          k.reshape(b, t, kv_heads, d))
      cos, sin = rotary_tables(t, int(d * cfg.partial_rotary_factor),
                               cfg.rope_theta)
      q = apply_partial_rotary(q, cos, sin)
      k = apply_partial_rotary(k, cos, sin)
      # Key/value head j serves query heads j x group .. (j + 1) x group - 1.
      group = heads // kv_heads
      k = jnp.repeat(k, group, axis=2)
      v = jnp.repeat(v.reshape(b, t, kv_heads, d), group, axis=2)
      out = attention_ops.flash_attention(
          q.reshape(b, t, heads * d), k.reshape(b, t, heads * d),
          v.reshape(b, t, heads * d), heads, causal=True,
          interpret=cfg.flash_interpret)
      out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
      return _dense(cfg.hidden_size, self.dtype, "o_proj")(out)


class GatedDeltaNet(nn.Module):
  config: DecoderConfig
  dtype: Optional[Any] = None

  @nn.compact
  def __call__(self, x):
    cfg = self.config
    b, t, _ = x.shape
    k_heads, v_heads = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    d_k, d_v = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim, value_dim = k_heads * d_k, v_heads * d_v
    width = cfg.linear_conv_kernel_dim
    qkvz = _dense(2 * key_dim + 2 * value_dim, self.dtype, "in_proj_qkvz")(x)
    ba = _dense(2 * v_heads, self.dtype, "in_proj_ba")(x)
    conv_kernel = self.param("conv_kernel", matrix_init(),
                             (width, 2 * key_dim + value_dim))
    a_log = self.param("A_log", a_log_init, (v_heads,))
    dt_bias = self.param("dt_bias", nn.initializers.ones, (v_heads,))
    norm_weight = self.param("norm_weight", nn.initializers.ones, (d_v,))

    with jax.named_scope("gdn_conv"):
      # Causal depthwise convolution over [q, k, v], then SiLU.
      mixed = qkvz[..., :2 * key_dim + value_dim].astype(jnp.float32)
      padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
      taps = conv_kernel.astype(jnp.float32)
      mixed = sum(padded[:, j:j + t] * taps[j] for j in range(width))
      mixed = jax.nn.silu(mixed).astype(qkvz.dtype)
    q = mixed[..., :key_dim].reshape(b, t, k_heads, d_k)
    k = mixed[..., key_dim:2 * key_dim].reshape(b, t, k_heads, d_k)
    v = mixed[..., 2 * key_dim:].reshape(b, t, v_heads, d_v)
    z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, v_heads, d_v)
    beta = jax.nn.sigmoid(ba[..., :v_heads].astype(jnp.float32))
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        ba[..., v_heads:].astype(jnp.float32) + dt_bias.astype(jnp.float32))
    # Value head h reads key head h // (v_heads / k_heads).
    q = jnp.repeat(q, v_heads // k_heads, axis=2)
    k = jnp.repeat(k, v_heads // k_heads, axis=2)
    with jax.named_scope("gdn_scan"):
      o, _ = linear_attention.gated_delta_rule_chunked(
          q, k, v, g, beta, matmul_dtype=self.dtype,
          interpret=cfg.flash_interpret)
    o = _rms(o, cfg.rms_norm_eps) * norm_weight.astype(jnp.float32)
    o = o * jax.nn.silu(z.astype(jnp.float32))
    o = o.astype(qkvz.dtype).reshape(b, t, value_dim)
    return _dense(cfg.hidden_size, self.dtype, "out_proj")(o)


class HybridDecoderBlock(nn.Module):
  """x + mixer(norm(x)), then x + experts(norm(x)); returns the stream and
  the expert layer's counters."""

  config: DecoderConfig
  mixer: str = "linear"   # 'linear' | 'full'
  dtype: Optional[Any] = None

  @nn.compact
  def __call__(self, x):
    cfg = self.config
    if self.mixer not in ("linear", "full"):
      raise ValueError(f"unknown mixer {self.mixer!r}")
    mixer_cls = GatedDeltaNet if self.mixer == "linear" else GatedAttention
    y = ZeroCentredRMSNorm(cfg.rms_norm_eps, name="norm_mixer")(x)
    x = x + mixer_cls(cfg, self.dtype, name="mixer")(y)
    y = ZeroCentredRMSNorm(cfg.rms_norm_eps, name="norm_moe")(x)
    y, counters = moe_lib.ShardedExpertsMoE(
        num_experts=cfg.num_experts, experts_held=cfg.experts_held,
        top_k=cfg.num_experts_per_tok, expert_width=cfg.moe_intermediate_size,
        shared_width=cfg.shared_expert_intermediate_size,
        buffer_factor=cfg.expert_buffer_factor, dtype=self.dtype,
        name="moe")(y)
    return x + y, counters
