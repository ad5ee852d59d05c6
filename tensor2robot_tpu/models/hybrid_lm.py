"""A token-in, logits-out T2RModel over `layers/decoder.HybridDecoderBlock`:
an embedding, a stack of blocks whose kinds follow a layer-type list, the
final RMSNorm of those kinds and an untied head onto the vocabulary held
here. Two-slot kinds (`linear`: the gated delta rule, `full`: gated softmax
attention, each followed by routed experts with a shared expert; `global`,
`sliding`: gated softmax attention over the whole causal past or over a
window, each followed by a dense feed-forward or sigmoid-routed experts as
`mlp_layer_types` says) and one-slot kinds (`mamba`: the Mamba-2 state-space
mixer, `attention`: plain softmax attention, `experts`: sigmoid-routed relu^2
experts with a shared expert); `layers/decoder.py` has the equations.

Built for one chip's share of an expert-parallel, vocabulary-parallel
training job (`configs/train_qwen3next_ep16share.gin`,
`configs/train_nemotron3nano_ep16share.gin`,
`configs/train_laguna_xs2_ep16share.gin`): an expert layer holds
`experts_held` of its router's width, and `vocab_size` is the slice of the
vocabulary this chip embeds and scores; token ids and targets are ids of
the slice. Trained through `train_eval_model` like any model.

Loss: next-token cross-entropy as the batch gives it: `tokens` and
`targets` [T] int32 and one float `weight` a row; mean over rows of
weight x mean over positions of the cross-entropy over the slice's
logits. The logits are never held whole in float32: in TRAIN and EVAL the
module hands the loss the normed hidden states and the head's kernel, and
the loss scores `loss_chunk` tokens at a time under `jax.checkpoint`.
PREDICT returns the logits. Each block is rematerialised
(`jax.checkpoint`) so that a step at T 4096 fits beside 10 GB of state.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tensor2robot_tpu import modes as modes_lib
from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.layers import decoder
from tensor2robot_tpu.models import abstract as abstract_model
from tensor2robot_tpu.specs import SpecStruct, TensorSpec
from tensor2robot_tpu.utils import config

__all__ = ["HybridDecoderLM", "chunked_cross_entropy"]

COUNTERS = ("moe_rows_held", "moe_buffer_fill", "moe_rows_dropped",
            "moe_load_max_over_mean")


class _HybridDecoder(nn.Module):
  config: decoder.DecoderConfig
  vocab_size: int = 1024
  dtype: Optional[object] = None

  @nn.compact
  def __call__(self, features, mode: str = modes_lib.TRAIN,
               train: bool = False):
    cfg = self.config
    tokens = features["tokens"]  # [B, T] int32, ids of the slice held here
    x = nn.Embed(self.vocab_size, cfg.hidden_size, dtype=self.dtype,
                 embedding_init=decoder.matrix_init(), name="embed")(tokens)
    # Every block is made again in the backward pass: what a block keeps for
    # its own backward is GBs at T 4096, the stream between blocks 16 MB.
    block_cls = nn.remat(decoder.HybridDecoderBlock)
    counters = []
    for i, kind in enumerate(cfg.layer_types):
      x, layer_counters = block_cls(cfg, kind, self.dtype, i,
                                    name=f"layer_{i}")(x)
      if layer_counters is not None:
        counters.append(layer_counters)
    x = decoder.final_norm(cfg, "norm_final")(x)
    head = self.param("head", decoder.matrix_init(),
                      (cfg.hidden_size, self.vocab_size))
    out = specs_lib.SpecStruct()
    for name in COUNTERS:
      # one entry a layer that has experts, in the layers' order
      out[name] = jnp.stack([c[name] for c in counters])
    if mode == modes_lib.PREDICT:
      logits = jnp.dot(x, head.astype(x.dtype))
      out["logits"] = logits
      out["inference_output"] = logits
    else:
      out["hidden"] = x
      out["head"] = head
    return out


def chunked_cross_entropy(hidden, head, targets, chunk: int):
  """Cross-entropy of every position, [B, T] float32, the logits made
  `chunk` positions at a time and made again in the backward pass."""
  b, t, width = hidden.shape
  chunk = min(int(chunk), t)
  if t % chunk:
    raise ValueError(f"loss_chunk {chunk} does not divide the length {t}")

  @jax.checkpoint
  def piece(_, xs):
    h, y = xs  # [B, chunk, width], [B, chunk]
    logits = jnp.dot(h, head, preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
    return None, lse - picked

  split = lambda x: jnp.moveaxis(  # noqa: E731
      x.reshape((b, t // chunk, chunk) + x.shape[2:]), 1, 0)
  _, ce = jax.lax.scan(piece, None, (split(hidden), split(targets)))
  return jnp.moveaxis(ce, 0, 1).reshape(b, t)


@config.configurable
class HybridDecoderLM(abstract_model.T2RModel):
  """[B, T] token ids -> next-token loss (TRAIN/EVAL) or logits (PREDICT)
  through a stack of hybrid decoder blocks; sizes under the names of the
  public `qwen3_next`, `nemotron_h` and `laguna` configs where they have one
  (each layer kind reads its own source's: `layers/decoder.py`).
  `rope_parameters` is the `laguna` config's mapping as published."""

  def __init__(self,
               sequence_length: int = 128,
               vocab_size: int = 1024,
               hidden_size: int = 64,
               layer_types: Sequence[str] = ("linear", "linear", "linear",
                                             "full"),
               rms_norm_eps: float = 1e-6,
               num_attention_heads: int = 2,
               num_key_value_heads: int = 1,
               head_dim: int = 32,
               partial_rotary_factor: float = 0.25,
               rope_theta: float = 1e7,
               linear_num_key_heads: int = 2,
               linear_num_value_heads: int = 4,
               linear_key_head_dim: int = 16,
               linear_value_head_dim: int = 16,
               linear_conv_kernel_dim: int = 4,
               num_experts: int = 8,
               experts_held: Tuple[int, int] = (0, 4),
               num_experts_per_tok: int = 2,
               moe_intermediate_size: int = 32,
               shared_expert_intermediate_size: int = 32,
               expert_buffer_factor: float = 2.0,
               norm_eps: float = 1e-5,
               mamba_num_heads: int = 4,
               mamba_head_dim: int = 16,
               ssm_state_size: int = 16,
               n_groups: int = 2,
               conv_kernel: int = 4,
               chunk_size: int = 128,
               n_routed_experts: int = 8,
               moe_shared_expert_intermediate_size: int = 32,
               routed_scaling_factor: float = 2.5,
               num_attention_heads_per_layer: Optional[Sequence[int]] = None,
               sliding_window: Optional[int] = None,
               mlp_layer_types: Optional[Sequence[str]] = None,
               intermediate_size: int = 128,
               rope_parameters: Optional[dict] = None,
               loss_chunk: int = 1024,
               **kwargs):
    super().__init__(**kwargs)
    self._sequence_length = int(sequence_length)
    self._vocab_size = int(vocab_size)
    self._loss_chunk = int(loss_chunk)
    self._decoder_config = decoder.DecoderConfig(
        hidden_size=hidden_size, layer_types=tuple(layer_types),
        rms_norm_eps=rms_norm_eps, num_attention_heads=num_attention_heads,
        num_key_value_heads=num_key_value_heads, head_dim=head_dim,
        partial_rotary_factor=partial_rotary_factor, rope_theta=rope_theta,
        linear_num_key_heads=linear_num_key_heads,
        linear_num_value_heads=linear_num_value_heads,
        linear_key_head_dim=linear_key_head_dim,
        linear_value_head_dim=linear_value_head_dim,
        linear_conv_kernel_dim=linear_conv_kernel_dim,
        num_experts=num_experts,
        experts_held=tuple(experts_held),
        num_experts_per_tok=num_experts_per_tok,
        moe_intermediate_size=moe_intermediate_size,
        shared_expert_intermediate_size=shared_expert_intermediate_size,
        expert_buffer_factor=expert_buffer_factor,
        norm_eps=norm_eps, mamba_num_heads=mamba_num_heads,
        mamba_head_dim=mamba_head_dim, ssm_state_size=ssm_state_size,
        n_groups=n_groups, conv_kernel=conv_kernel, chunk_size=chunk_size,
        n_routed_experts=n_routed_experts,
        moe_shared_expert_intermediate_size=(
            moe_shared_expert_intermediate_size),
        routed_scaling_factor=routed_scaling_factor,
        num_attention_heads_per_layer=(
            None if num_attention_heads_per_layer is None
            else tuple(int(h) for h in num_attention_heads_per_layer)),
        sliding_window=None if sliding_window is None else int(sliding_window),
        mlp_layer_types=(None if mlp_layer_types is None
                         else tuple(mlp_layer_types)),
        intermediate_size=int(intermediate_size),
        rope_parameters=decoder.freeze_rope_parameters(rope_parameters),
        # The model knows its target platform (as SequenceRegressionModel).
        flash_interpret=self.device_type != "tpu")

  @property
  def step_counter_prefixes(self) -> Tuple[str, ...]:
    return ("moe_",)

  def get_feature_specification(self, mode):
    return SpecStruct({
        "tokens": TensorSpec(shape=(self._sequence_length,), dtype=np.int32,
                             name="tokens"),
    })

  def get_label_specification(self, mode):
    return SpecStruct({
        "targets": TensorSpec(shape=(self._sequence_length,), dtype=np.int32,
                              name="targets"),
        "weight": TensorSpec(shape=(1,), dtype=np.float32, name="weight"),
    })

  def create_module(self):
    return _HybridDecoder(
        config=self._decoder_config, vocab_size=self._vocab_size,
        dtype=self.compute_dtype if self.use_bfloat16 else None)

  def model_train_fn(self, features, labels, inference_outputs, mode):
    with jax.named_scope("lm_loss"):
      hidden, head = inference_outputs["hidden"], inference_outputs["head"]
      if self.use_bfloat16:
        # The step hands bfloat16 outputs on as float32; the product takes
        # them as the module made them.
        hidden, head = (x.astype(self.compute_dtype) for x in (hidden, head))
      ce = chunked_cross_entropy(hidden, head, labels["targets"],
                                 self._loss_chunk)
      weight = labels["weight"].astype(jnp.float32).reshape(-1)
      loss = jnp.mean(weight * jnp.mean(ce, axis=-1))
    cfg = self._decoder_config
    with_experts = [i for i in range(len(cfg.layer_types))
                    if decoder.has_experts(cfg, i)]
    scalars = {}
    for name in COUNTERS:
      for at, i in enumerate(with_experts):
        scalars[f"{name}/layer_{i}"] = inference_outputs[name][at]
    return loss, scalars
