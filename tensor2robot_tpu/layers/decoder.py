"""A decoder block built from a layer-type list. A layer's kind says which
slots it has, each behind an RMSNorm and added to the residual stream:

  kind         slots                                      follows
  `linear`     gated delta rule, then routed experts      `qwen3_next`
  `full`       gated softmax attention, then experts      `qwen3_next`
  `mamba`      the Mamba-2 state-space mixer              `nemotron_h`
  `attention`  plain grouped-query softmax attention      `nemotron_h`
  `experts`    routed experts with a shared expert        `nemotron_h`
  `global`     gated causal attention, then a feed-forward  `laguna`
  `sliding`    gated attention over a window, then a        `laguna`
               feed-forward

The two-slot kinds follow the public `qwen3_next` modelling code
(huggingface transformers, `modeling_qwen3_next.py`: `Qwen3NextRMSNorm`,
`Qwen3NextRMSNormGated`, `Qwen3NextAttention`, `Qwen3NextGatedDeltaNet`,
`Qwen3NextDecoderLayer`), the one-slot kinds the public `nemotron_h`
modelling code (`modeling_nemotron_h.py`: `NemotronHRMSNorm`,
`MambaRMSNormGated`, `NemotronHMamba2Mixer`, `NemotronHAttention`,
`NemotronHMOE`, `NemotronHBlock`), the `laguna` kinds the public config of
poolside's Laguna-XS.2 (`layer_types`, `sliding_window`,
`num_attention_heads_per_layer`, `rope_parameters`, `mlp_layer_types`; where
it leaves a form open the benchmark's configuration file states the one taken,
under `assumed`); each kind reads the sizes of `DecoderConfig` that its
source's config names. Written for this repository's layouts:

* `ZeroCentredRMSNorm`: x / sqrt(mean(x^2) + eps) * (1 + w), w from 0
  (`linear`, `full`). `RMSNorm`: x / sqrt(mean(x^2) + eps) * w, w from 1
  (the one-slot kinds, eps `norm_eps`; the `laguna` kinds, eps
  `rms_norm_eps`).
* `GatedAttention`: q with an output gate from one projection, per-head
  RMSNorm of q and k, rotary embedding on the leading `rotary_fraction` of
  each head (half-split pairing), grouped-query causal softmax attention,
  the result times sigmoid(gate). The `laguna` kinds take the same layer
  with plain RMSNorms of q and k, their layer's head count
  (`num_attention_heads_per_layer`) and their kind's rotary
  (`rope_parameters`): YaRN on the leading half of each head on `global`
  layers, the plain rotary on the whole head on `sliding` ones, whose
  attention sees `sliding_window` keys a row. The attention itself is
  `ops/attention.flash_attention` on q [B, T, H x D] and k, v [B, T,
  H_kv x D] as the projections wrote them: its kernels read key/value head
  h // (H / H_kv) for query head h through their index maps and sum dK and
  dV over each group inside the backward.
* `DenseMLP`: (silu(x W_gate) * (x W_up)) W_down at `intermediate_size`,
  the feed-forward of a `laguna` layer that `mlp_layer_types` calls `dense`
  (a `sparse` one has the routed experts).
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
import math
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
           "PlainAttention", "DenseMLP", "GatedDeltaNet", "Mamba2Mixer",
           "HybridDecoderBlock", "final_norm", "has_experts", "rotary_tables",
           "yarn_rotary_tables", "apply_partial_rotary", "matrix_init",
           "TWO_SLOT_KINDS", "ONE_SLOT_KINDS", "LAGUNA_KINDS"]

INIT_STDDEV = 0.02
TWO_SLOT_KINDS = ("linear", "full")
ONE_SLOT_KINDS = ("mamba", "attention", "experts")
LAGUNA_KINDS = ("global", "sliding")
# Each `laguna` kind's entry of `rope_parameters`, and its scope.
ROPE_ENTRY = {"global": "full_attention", "sliding": "sliding_attention"}
_ATTENTION_SCOPE = {"full": lambda: jax.named_scope("attn_gated"),
                    "global": lambda: jax.named_scope("attn_global"),
                    "sliding": lambda: jax.named_scope("attn_window")}


def has_experts(cfg: "DecoderConfig", layer: int) -> bool:
  kind = cfg.layer_types[layer]
  if kind in LAGUNA_KINDS:
    return cfg.mlp_layer_types[layer] == "sparse"
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
  # the `laguna` kinds (norms at `rms_norm_eps`; experts as the two-slot
  # kinds size them, routed by sigmoid scores and scaled by
  # `routed_scaling_factor`): a head count a layer, the window of the
  # `sliding` layers, each layer's feed-forward (`dense` or `sparse`), the
  # dense one's width, and each kind's rotary as
  # ((entry, ((key, value), ...)), ...) of the config's `rope_parameters`
  num_attention_heads_per_layer: Optional[Tuple[int, ...]] = None
  sliding_window: Optional[int] = None
  mlp_layer_types: Optional[Tuple[str, ...]] = None
  intermediate_size: int = 8192
  rope_parameters: Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...] = ()
  # Pallas interpreted (off the TPU) or not; None: by lowering platform.
  flash_interpret: Optional[bool] = None

  def rope(self, kind: str) -> dict:
    """The `rope_parameters` entry of a `laguna` kind."""
    return dict(dict(self.rope_parameters)[ROPE_ENTRY[kind]])


def freeze_rope_parameters(rope_parameters) -> tuple:
  """A config's `rope_parameters` mapping as `DecoderConfig` holds it: its
  per-kind entries, each a sorted tuple of items (the rest is dropped)."""
  return tuple(sorted(
      (entry, tuple(sorted(values.items())))
      for entry, values in dict(rope_parameters or {}).items()
      if isinstance(values, dict)))


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


def yarn_rotary_tables(length: int, head_dim: int, rope: dict):
  """(cos, sin) [length, rotary_dim / 2] of YaRN, as the public
  `_compute_yarn_parameters` of huggingface transformers computes them,
  from one entry of a config's `rope_parameters` (`rope_theta`, `factor`,
  `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
  `attention_factor`, `partial_rotary_factor`): on rotary_dim =
  head_dim x partial_rotary_factor, inv_freq_extra = theta^(-2j / dim),
  inv_freq_inter = inv_freq_extra / factor, the correction range [low, high]
  = floor / ceil of dim ln(original / (beta 2 pi)) / (2 ln theta) at
  beta_fast / beta_slow, clamped to [0, dim - 1], ramp = clip((j - low) /
  (high - low), 0, 1), inv_freq = inv_freq_inter ramp + inv_freq_extra
  (1 - ramp); cos and sin times `attention_factor`."""
  dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
  base, factor = float(rope["rope_theta"]), float(rope["factor"])
  original = float(rope["original_max_position_embeddings"])

  def correction(rotations):
    return dim * math.log(original / (rotations * 2 * math.pi)) / (
        2 * math.log(base))

  low = max(math.floor(correction(float(rope["beta_fast"]))), 0)
  high = min(math.ceil(correction(float(rope["beta_slow"]))), dim - 1)
  if low == high:
    high += 0.001
  extra = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
  ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                  / (high - low), 0.0, 1.0)
  inv_freq = extra / factor * ramp + extra * (1.0 - ramp)
  angles = jnp.arange(length, dtype=jnp.float32)[:, None] * inv_freq[None]
  scale = float(rope["attention_factor"])
  return jnp.cos(angles) * scale, jnp.sin(angles) * scale


def laguna_rotary_tables(cfg: DecoderConfig, kind: str, length: int):
  """The rotary of a `laguna` kind, from its `rope_parameters` entry."""
  rope = cfg.rope(kind)
  if rope.get("rope_type", "default") == "yarn":
    return yarn_rotary_tables(length, cfg.head_dim, rope)
  if rope.get("rope_type", "default") != "default":
    raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
  return rotary_tables(
      length, int(cfg.head_dim * rope.get("partial_rotary_factor", 1.0)),
      rope["rope_theta"])


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


def _grouped_flash(q, k, v, cfg: DecoderConfig, window=None):
  """Causal attention of q [B, T, heads, d] over k, v [B, T, kv_heads, d],
  over the last `window` keys a row where one is given; key/value head j
  serves query heads j x group .. (j + 1) x group - 1. k and v go to the
  kernels at their own head count. Returns [B, T, heads x d]."""
  b, t, heads, d = q.shape
  kv = k.shape[2] * d
  return attention_ops.flash_attention(
      q.reshape(b, t, heads * d), k.reshape(b, t, kv), v.reshape(b, t, kv),
      heads, causal=True, interpret=cfg.flash_interpret, window=window)


class GatedAttention(nn.Module):
  """Gated attention of a `full` layer, or of a `laguna` kind (`global`,
  `sliding`) with `heads` query heads."""

  config: DecoderConfig
  dtype: Optional[Any] = None
  kind: str = "full"
  heads: Optional[int] = None

  @nn.compact
  def __call__(self, x):
    cfg, kind = self.config, self.kind
    b, t, _ = x.shape
    heads = cfg.num_attention_heads if self.heads is None else self.heads
    kv_heads, d = cfg.num_key_value_heads, cfg.head_dim
    norm, window = ZeroCentredRMSNorm, None
    if kind in LAGUNA_KINDS:
      norm = RMSNorm
      window = cfg.sliding_window if kind == "sliding" else None
    with _ATTENTION_SCOPE[kind]():
      q, gate = jnp.split(
          _dense(2 * heads * d, self.dtype, "q_proj")(x), 2, axis=-1)
      k = _dense(kv_heads * d, self.dtype, "k_proj")(x)
      v = _dense(kv_heads * d, self.dtype, "v_proj")(x)
      q = norm(cfg.rms_norm_eps, name="q_norm")(q.reshape(b, t, heads, d))
      k = norm(cfg.rms_norm_eps, name="k_norm")(k.reshape(b, t, kv_heads, d))
      if kind in LAGUNA_KINDS:
        cos, sin = laguna_rotary_tables(cfg, kind, t)
      else:
        cos, sin = rotary_tables(t, int(d * cfg.partial_rotary_factor),
                                 cfg.rope_theta)
      q = apply_partial_rotary(q, cos, sin)
      k = apply_partial_rotary(k, cos, sin)
      out = _grouped_flash(q, k, v.reshape(b, t, kv_heads, d), cfg, window)
      out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
      return _dense(cfg.hidden_size, self.dtype, "o_proj")(out)


class DenseMLP(nn.Module):
  config: DecoderConfig
  dtype: Optional[Any] = None

  @nn.compact
  def __call__(self, x):
    cfg = self.config
    with jax.named_scope("mlp_dense"):
      width = cfg.intermediate_size
      hidden = jax.nn.silu(_dense(width, self.dtype, "gate_proj")(x).astype(
          jnp.float32)) * _dense(width, self.dtype, "up_proj")(x).astype(
              jnp.float32)
      return _dense(cfg.hidden_size, self.dtype, "down_proj")(
          hidden.astype(x.dtype))


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
    z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, v_heads, d_v)
    beta = jax.nn.sigmoid(ba[..., :v_heads].astype(jnp.float32))
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        ba[..., v_heads:].astype(jnp.float32) + dt_bias.astype(jnp.float32))
    with jax.named_scope("gdn_scan"):
      # q, k and v read from `mixed` in place; value head h reads key head
      # h // (v_heads / k_heads).
      o, _ = linear_attention.gated_delta_rule(
          mixed, g, beta, k_heads, d_k, matmul_dtype=self.dtype,
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
  if kind in LAGUNA_KINDS:
    return moe_lib.ShardedExpertsMoE(
        num_experts=cfg.num_experts,
        shared_width=cfg.shared_expert_intermediate_size,
        router_scoring="sigmoid",
        routed_scaling_factor=cfg.routed_scaling_factor,
        expert_form="gated_silu", shared_gate=False, **shared)
  return moe_lib.ShardedExpertsMoE(
      num_experts=cfg.num_experts,
      shared_width=cfg.shared_expert_intermediate_size, **shared)


_MIXERS = {"linear": GatedDeltaNet, "full": GatedAttention,
           "mamba": Mamba2Mixer, "attention": PlainAttention}


def final_norm(cfg: DecoderConfig, name: str):
  """The norm after the last layer: that of the layers' kinds."""
  if cfg.layer_types[-1] in ONE_SLOT_KINDS:
    return RMSNorm(cfg.norm_eps, name=name)
  if cfg.layer_types[-1] in LAGUNA_KINDS:
    return RMSNorm(cfg.rms_norm_eps, name=name)
  return ZeroCentredRMSNorm(cfg.rms_norm_eps, name=name)


class HybridDecoderBlock(nn.Module):
  """Layer `layer`, of kind `mixer`. `linear`, `full`: x + mixer(norm(x)),
  then x + experts(norm(x)). `global`, `sliding`: x + attention(norm(x)),
  then x + ffn(norm(x)), ffn the dense feed-forward or the experts as
  `mlp_layer_types` gives it. `mamba`, `attention`, `experts`: x +
  f(norm(x)), f the one slot the kind names. Returns the stream and the
  expert layer's counters (None where the layer has no experts)."""

  config: DecoderConfig
  # a kind of TWO_SLOT_KINDS, LAGUNA_KINDS or ONE_SLOT_KINDS
  mixer: str = "linear"
  dtype: Optional[Any] = None
  layer: int = 0

  @nn.compact
  def __call__(self, x):
    cfg, kind = self.config, self.mixer
    if kind in TWO_SLOT_KINDS:
      norm = lambda slot: ZeroCentredRMSNorm(  # noqa: E731
          cfg.rms_norm_eps, name=f"norm_{slot}")
    elif kind in LAGUNA_KINDS:
      norm = lambda slot: RMSNorm(  # noqa: E731
          cfg.rms_norm_eps, name=f"norm_{slot}")
    elif kind in ONE_SLOT_KINDS:
      norm = lambda slot: RMSNorm(cfg.norm_eps, name="norm")  # noqa: E731
    else:
      raise ValueError(f"unknown layer kind {kind!r}")
    counters = None
    if kind in LAGUNA_KINDS:
      x = x + GatedAttention(
          cfg, self.dtype, kind, cfg.num_attention_heads_per_layer[self.layer],
          name="mixer")(norm("mixer")(x))
      if cfg.mlp_layer_types[self.layer] == "dense":
        return x + DenseMLP(cfg, self.dtype, name="mlp")(norm("mlp")(x)), None
    elif kind in _MIXERS:
      x = x + _MIXERS[kind](cfg, self.dtype, name="mixer")(norm("mixer")(x))
    if kind in TWO_SLOT_KINDS + LAGUNA_KINDS or kind == "experts":
      y, counters = _experts(cfg, kind, self.dtype)(norm("moe")(x))
      x = x + y
    return x, counters
