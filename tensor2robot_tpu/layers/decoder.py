"""A decoder block built from a layer-type list. A layer's kind says which
slots it has, each behind an RMSNorm and added to the residual stream:

  kind         slots                                      follows
  `linear`     gated delta rule, then routed experts      `qwen3_next`
  `full`       gated softmax attention, then experts      `qwen3_next`
  `mamba`      the Mamba-2 state-space mixer              `nemotron_h`
  `attention`  plain grouped-query softmax attention      `nemotron_h`
  `experts`    routed experts with a shared expert        `nemotron_h`

The two-slot kinds follow the public `qwen3_next` modelling code
(huggingface transformers, `modeling_qwen3_next.py`: `Qwen3NextRMSNorm`,
`Qwen3NextRMSNormGated`, `Qwen3NextAttention`, `Qwen3NextGatedDeltaNet`,
`Qwen3NextDecoderLayer`), the one-slot kinds the public `nemotron_h`
modelling code (`modeling_nemotron_h.py`: `NemotronHRMSNorm`,
`MambaRMSNormGated`, `NemotronHMamba2Mixer`, `NemotronHAttention`,
`NemotronHMOE`, `NemotronHBlock`); each kind reads the sizes of
`DecoderConfig` that its source's config names. Written for this
repository's layouts:

* `ZeroCentredRMSNorm`: x / sqrt(mean(x^2) + eps) * (1 + w), w from 0
  (`linear`, `full`). `RMSNorm`: x / sqrt(mean(x^2) + eps) * w, w from 1
  (the one-slot kinds).
* `GatedAttention`: q with an output gate from one projection, per-head
  RMSNorm of q and k, rotary embedding on the leading `rotary_fraction` of
  each head (half-split pairing), grouped-query causal softmax attention,
  the result times sigmoid(gate). The attention itself is
  `ops/attention.flash_attention` on [B, T, H x D]; its kernels take as
  many key/value heads as query heads, so k and v are repeated to the
  query heads ahead of it (their gradients sum over the group): a
  departure in layout, not in mathematics.
* `PlainAttention`: q, k, v projections, the same grouped-query causal
  attention, the output projection: no gate, no q/k norm and no positional
  embedding (the source's attention module applies none; its state-space
  layers carry position).
* `GatedDeltaNet`: one projection to [q, k, v, z] and one to [b, a], a
  causal depthwise convolution and SiLU over [q, k, v] (`ops/short_conv`,
  which reads the projection's columns in place), the gated delta rule
  per value head (`ops/linear_attention`), RMSNorm of the result times
  SiLU(z), the output projection. The source interleaves q, k, v, z
  per key head inside its projection; here they lie one after the other
  (a permutation of the projection's columns).
* `Mamba2Mixer`: one projection to [z | x, B, C | dt], a causal depthwise
  convolution with a bias and SiLU over [x, B, C] (`ops/short_conv`),
  dt = softplus(dt + dt_bias), the state-space scan per head
  (`ops/state_space.ssd_scan`, which reads x, B and C from the
  convolution's result in place), the result times SiLU(z) and then
  RMSNorm over each of `n_groups` groups of channels, the output
  projection.

Norms, gates, the mixers' states and the softmax are float32; projections
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
from tensor2robot_tpu.ops import short_conv
from tensor2robot_tpu.ops import state_space

__all__ = ["DecoderConfig", "ZeroCentredRMSNorm", "RMSNorm", "GatedAttention",
           "PlainAttention", "GatedDeltaNet", "Mamba2Mixer",
           "HybridDecoderBlock", "final_norm", "has_experts", "rotary_tables",
           "apply_partial_rotary", "matrix_init", "TWO_SLOT_KINDS",
           "ONE_SLOT_KINDS"]

INIT_STDDEV = 0.02
TWO_SLOT_KINDS = ("linear", "full")
ONE_SLOT_KINDS = ("mamba", "attention", "experts")


def has_experts(kind: str) -> bool:
  return kind in TWO_SLOT_KINDS or kind == "experts"


def matrix_init():
  """Normal(0, 0.02), the source's `initializer_range`, for every matrix."""
  return nn.initializers.normal(INIT_STDDEV)


def a_log_init(key, shape, dtype=jnp.float32):
  """A_log = log U(0, 16), as the source's module draws it."""
  return jnp.log(jnp.maximum(
      jax.random.uniform(key, shape, dtype, 0.0, 16.0), 1e-6))


def dt_bias_init(key, shape, dtype=jnp.float32):
  """The inverse softplus of dt = exp(U(log 0.001, log 0.1)) floored at
  1e-4, as the source's Mamba-2 module draws it (its config's
  `time_step_min`, `time_step_max`, `time_step_floor`)."""
  dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(0.001),
                                  jnp.log(0.1)))
  dt = jnp.maximum(dt, 1e-4)
  return dt + jnp.log(-jnp.expm1(-dt))


def conv_init(key, shape, dtype=jnp.float32):
  """U(-1/2, 1/2): torch's `Conv1d` at a fan-in of 4 (depthwise, 4 taps)."""
  return jax.random.uniform(key, shape, dtype, -0.5, 0.5)


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
  """Every size of the block, under the source configs' own names where
  they have one. `layer_types` gives the kind of each layer."""

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
  # the one-slot kinds: the plain norm, the Mamba-2 mixer, sigmoid-routed
  # relu^2 experts (the attention and the experts' other sizes are above)
  norm_eps: float = 1e-5
  mamba_num_heads: int = 64
  mamba_head_dim: int = 64
  ssm_state_size: int = 128
  n_groups: int = 8
  conv_kernel: int = 4
  chunk_size: int = 128
  n_routed_experts: int = 128       # the router's width
  moe_shared_expert_intermediate_size: int = 3712
  routed_scaling_factor: float = 2.5
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


class RMSNorm(nn.Module):
  eps: float = 1e-5

  @nn.compact
  def __call__(self, x):
    weight = self.param("weight", nn.initializers.ones, (x.shape[-1],))
    return (_rms(x, self.eps) * weight.astype(jnp.float32)).astype(x.dtype)


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


def _grouped_flash(q, k, v, cfg: DecoderConfig):
  """Causal attention of q [B, T, heads, d] over k, v [B, T, kv_heads, d];
  key/value head j serves query heads j x group .. (j + 1) x group - 1.
  Returns [B, T, heads x d]."""
  b, t, heads, d = q.shape
  group = heads // k.shape[2]
  k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
  return attention_ops.flash_attention(
      q.reshape(b, t, heads * d), k.reshape(b, t, heads * d),
      v.reshape(b, t, heads * d), heads, causal=True,
      interpret=cfg.flash_interpret)


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
      out = _grouped_flash(q, k, v.reshape(b, t, kv_heads, d), cfg)
      out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
      return _dense(cfg.hidden_size, self.dtype, "o_proj")(out)


class PlainAttention(nn.Module):
  config: DecoderConfig
  dtype: Optional[Any] = None

  @nn.compact
  def __call__(self, x):
    cfg = self.config
    b, t, _ = x.shape
    heads, kv_heads, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
    with jax.named_scope("attn_plain"):
      q = _dense(heads * d, self.dtype, "q_proj")(x)
      k = _dense(kv_heads * d, self.dtype, "k_proj")(x)
      v = _dense(kv_heads * d, self.dtype, "v_proj")(x)
      out = _grouped_flash(q.reshape(b, t, heads, d),
                           k.reshape(b, t, kv_heads, d),
                           v.reshape(b, t, kv_heads, d), cfg)
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
      mixed = short_conv.causal_conv_silu(
          qkvz, conv_kernel, interpret=cfg.flash_interpret)
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


class Mamba2Mixer(nn.Module):
  config: DecoderConfig
  dtype: Optional[Any] = None

  @nn.compact
  def __call__(self, x):
    cfg = self.config
    b, t, _ = x.shape
    heads, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    groups, state = cfg.n_groups, cfg.ssm_state_size
    inner, width = heads * p, cfg.conv_kernel
    conv_dim = inner + 2 * groups * state
    zxbcdt = _dense(inner + conv_dim + heads, self.dtype, "in_proj")(x)
    conv_kernel = self.param("conv_kernel", conv_init, (width, conv_dim))
    conv_bias = self.param("conv_bias", conv_init, (conv_dim,))
    a_log = self.param(
        "A_log", lambda *_: jnp.log(jnp.arange(1, heads + 1,
                                               dtype=jnp.float32)))
    dt_bias = self.param("dt_bias", dt_bias_init, (heads,))
    skip = self.param("D", nn.initializers.ones, (heads,))
    norm_weight = self.param("norm_weight", nn.initializers.ones, (inner,))

    with jax.named_scope("ssm_conv"):
      mixed = short_conv.causal_conv_silu(
          zxbcdt, conv_kernel, conv_bias, start=inner,
          interpret=cfg.flash_interpret)
    z = zxbcdt[..., :inner].astype(jnp.float32)
    dt = jax.nn.softplus(zxbcdt[..., inner + conv_dim:].astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    with jax.named_scope("ssm_scan"):
      y, _ = state_space.ssd_scan(
          mixed, dt, a_log, skip, groups, state, chunk_size=cfg.chunk_size,
          matmul_dtype=self.dtype, interpret=cfg.flash_interpret)
    # The gate first, then the norm over each group of inner / groups.
    y = (y * jax.nn.silu(z)).reshape(b, t, groups, -1)
    y = _rms(y, cfg.norm_eps).reshape(b, t, inner) * norm_weight.astype(
        jnp.float32)
    return _dense(cfg.hidden_size, self.dtype, "out_proj")(
        y.astype(zxbcdt.dtype))


def _experts(cfg: DecoderConfig, kind: str, dtype):
  """The expert layer as the kind's source states it."""
  shared = dict(
      experts_held=cfg.experts_held, top_k=cfg.num_experts_per_tok,
      expert_width=cfg.moe_intermediate_size,
      buffer_factor=cfg.expert_buffer_factor, dtype=dtype, name="moe")
  if kind == "experts":
    return moe_lib.ShardedExpertsMoE(
        num_experts=cfg.n_routed_experts,
        shared_width=cfg.moe_shared_expert_intermediate_size,
        router_scoring="sigmoid",
        routed_scaling_factor=cfg.routed_scaling_factor,
        expert_form="relu2", shared_gate=False, **shared)
  return moe_lib.ShardedExpertsMoE(
      num_experts=cfg.num_experts,
      shared_width=cfg.shared_expert_intermediate_size, **shared)


_MIXERS = {"linear": GatedDeltaNet, "full": GatedAttention,
           "mamba": Mamba2Mixer, "attention": PlainAttention}


def final_norm(cfg: DecoderConfig, name: str):
  """The norm after the last layer: that of the layers' kinds."""
  if cfg.layer_types[-1] in ONE_SLOT_KINDS:
    return RMSNorm(cfg.norm_eps, name=name)
  return ZeroCentredRMSNorm(cfg.rms_norm_eps, name=name)


class HybridDecoderBlock(nn.Module):
  """One layer of kind `mixer`. `linear`, `full`: x + mixer(norm(x)), then
  x + experts(norm(x)). `mamba`, `attention`, `experts`: x + f(norm(x)), f
  the one slot the kind names. Returns the stream and the expert layer's
  counters (None where the layer has no experts)."""

  config: DecoderConfig
  mixer: str = "linear"   # a kind of TWO_SLOT_KINDS or ONE_SLOT_KINDS
  dtype: Optional[Any] = None

  @nn.compact
  def __call__(self, x):
    cfg, kind = self.config, self.mixer
    if kind in TWO_SLOT_KINDS:
      norm = lambda slot: ZeroCentredRMSNorm(  # noqa: E731
          cfg.rms_norm_eps, name=f"norm_{slot}")
    elif kind in ONE_SLOT_KINDS:
      norm = lambda slot: RMSNorm(cfg.norm_eps, name="norm")  # noqa: E731
    else:
      raise ValueError(f"unknown layer kind {kind!r}")
    counters = None
    if kind in _MIXERS:
      x = x + _MIXERS[kind](cfg, self.dtype, name="mixer")(norm("mixer")(x))
    if has_experts(kind):
      y, counters = _experts(cfg, kind, self.dtype)(norm("moe")(x))
      x = x + y
    return x, counters
