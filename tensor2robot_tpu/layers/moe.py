"""Mixture-of-experts layer with expert parallelism.

Beyond the reference (SURVEY.md §2.5: EP absent there): a top-k routed
MoE whose expert parameters carry a leading expert dim sharded over a
mesh axis — expert parallelism falls out of the sharding annotation, with
XLA inserting the dispatch/combine collectives (all_to_all-class traffic
over ICI when experts and tokens live on different axes).

Design notes for TPU:
* three dispatch modes, all static-shaped and MXU-friendly:
  - `dense`: every expert computes every token, the gate zeroes the rest.
    Exact, collective-free, right for few-expert robot-scale models.
  - `sparse`: GShard/Switch-style capacity routing. Tokens are packed into
    per-expert [capacity] slots via one-hot dispatch/combine einsums;
    expert FLOPs are O(E * capacity) = O(N * capacity_factor) instead of
    O(E * N), and over-capacity tokens are dropped (their gate mass
    renormalizes away). With `experts_*` sharded over a mesh axis the
    ecf/eco einsums become all_to_all-class traffic — but GSPMD chooses
    the collectives.
  - `alltoall`: the same capacity routing with the collectives made
    explicit: a `shard_map` over `ep_axis` in which each device packs its
    LOCAL tokens' slots, a `lax.all_to_all` ships each expert-group's
    slots to the device that owns those experts, local experts run, and a
    second all_to_all ships results home (Switch-Transformer §2.2 token
    routing). Per-device dispatch traffic is exactly 2 * E * C_local * F
    instead of whatever GSPMD infers — requires `experts_*` sharded over
    the SAME axis as the tokens (`expert_parallel_rules(axis="data")`)
    and `set_mesh`-style mesh plumbing. Capacity is per source shard, so
    drop behavior is per-shard rather than global (documented delta vs
    `sparse`).
* router in float32 for numerics, experts in the compute dtype;
* auxiliary load-balancing loss (Switch-style) returned alongside.

`ShardedExpertsMoE` is one chip's share of an expert-parallel layer at the
sizes of today's sparse language models (hundreds of experts, ten a token):
it is told which experts it holds, routes over all of them, and computes
the part of the result its own experts give, through grouped products over
the (token, expert) pairs sorted by expert. No per-expert capacity, no
[N, E, C] tensor.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec

from tensor2robot_tpu.ops import grouped_matmul
from tensor2robot_tpu.parallel import mesh as mesh_lib

__all__ = ["MixtureOfExperts", "ShardedExpertsMoE", "EXPERT_AXIS_PARAM_RULE",
           "expert_axis_param_rule"]

def expert_axis_param_rule(axis: str = "model"):
  """Partition rule: expert-major params shard their leading dim over
  `axis` (EP = expert dim sharded). Pass to make_train_step's rules.

  `dispatch='alltoall'` wants experts sharded over the SAME axis as the
  tokens (classically the data axis) so the all_to_all rides that axis;
  pass `expert_axis_param_rule("data")` to the step factory's rules.
  """
  return (r"experts_", (axis, None, None))


# The default 'model'-axis rule (GSPMD sparse/dense dispatch layouts).
EXPERT_AXIS_PARAM_RULE = expert_axis_param_rule()


class MixtureOfExperts(nn.Module):
  """Top-k routed MLP experts over [batch, features] (or [B, T, F])."""

  num_experts: int = 4
  hidden_size: int = 64
  output_size: int = 64
  top_k: int = 1
  router_noise: float = 0.0
  dispatch: str = "dense"  # 'dense' | 'sparse' | 'alltoall'
  capacity_factor: float = 1.25  # sparse/alltoall only
  mesh: Optional[Mesh] = None  # alltoall only
  ep_axis: str = "data"  # alltoall only: axis sharding tokens AND experts
  # Compute dtype for the EXPERT einsums (the FLOPs bulk — where EP's
  # MXU time goes); router/gates/aux stay f32 by design (the softmax
  # and load statistics are numerics-sensitive and tiny). On the
  # trained path the policy wrapper (abstract.py inference_network_fn)
  # already downcasts f32 params before apply; this attr makes the
  # module correct STANDALONE too (direct module.apply has no wrapper)
  # and states the intended compute dtype explicitly.
  dtype: Optional[Any] = None

  @nn.compact
  def __call__(self, x: jnp.ndarray, train: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output, aux_load_balancing_loss)."""
    if self.dispatch not in ("dense", "sparse", "alltoall"):
      raise ValueError(f"Unknown dispatch mode {self.dispatch!r}")
    leading = x.shape[:-1]
    features = x.shape[-1]
    tokens = x.reshape(-1, features)

    router_logits = nn.Dense(self.num_experts, name="router")(
        tokens.astype(jnp.float32))
    if train and self.router_noise:
      noise_key = self.make_rng("dropout")
      router_logits = router_logits + self.router_noise * jax.random.normal(
          noise_key, router_logits.shape)
    probs = jax.nn.softmax(router_logits, axis=-1)  # [N, E]
    top_probs, top_idx = jax.lax.top_k(probs, self.top_k)

    # Expert-major params: [E, in, hidden], [E, hidden, out] — the leading
    # expert dim is what EP shards.
    w1 = self.param("experts_w1", nn.initializers.lecun_normal(),
                    (self.num_experts, features, self.hidden_size))
    b1 = self.param("experts_b1", nn.initializers.zeros,
                    (self.num_experts, 1, self.hidden_size))
    w2 = self.param("experts_w2", nn.initializers.lecun_normal(),
                    (self.num_experts, self.hidden_size, self.output_size))
    b2 = self.param("experts_b2", nn.initializers.zeros,
                    (self.num_experts, 1, self.output_size))
    if self.dtype is not None:
      # Cast the expert params once: every dispatch branch reads its
      # compute dtype from w1.dtype, so the expert einsums follow.
      w1, b1, w2, b2 = (p.astype(self.dtype) for p in (w1, b1, w2, b2))

    if self.dispatch == "dense":
      gates = jnp.zeros_like(probs)
      gates = jax.vmap(lambda g, i, p: g.at[i].set(p))(gates, top_idx,
                                                       top_probs)
      gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
      hidden = jnp.einsum("nf,efh->enh", tokens.astype(w1.dtype), w1) + b1
      hidden = nn.relu(hidden)
      expert_out = jnp.einsum("enh,eho->eno", hidden, w2) + b2  # [E, N, O]
      combined = jnp.einsum("eno,ne->no", expert_out,
                            gates.astype(expert_out.dtype))
      load = gates.astype(jnp.float32).mean(0)
    elif self.dispatch == "sparse":
      combined, load = self._sparse_dispatch(
          tokens, top_probs, top_idx, w1, b1, w2, b2)
    else:
      combined, load = self._alltoall_dispatch(
          tokens, top_probs, top_idx, w1, b1, w2, b2)

    # Switch-transformer load-balancing auxiliary.
    importance = probs.mean(0)  # mean router prob per expert
    aux_loss = self.num_experts * (importance * load).sum()

    return combined.reshape(leading + (self.output_size,)), aux_loss

  def _capacity(self, n_tokens: int) -> int:
    return max(1, int(math.ceil(
        self.top_k * n_tokens / self.num_experts * self.capacity_factor)))

  def _pack_combine(self, top_probs, top_idx, capacity):
    """Packs top-k choices into per-expert slots: combine [N, E, C].

    Tokens earlier in the batch (and earlier slots) claim lower slot
    positions; over-capacity choices are dropped and the kept gate mass
    renormalizes (matches dense top-k renorm; fully-dropped tokens
    produce zero output).
    """
    n = top_probs.shape[0]
    e = self.num_experts
    combine = jnp.zeros((n, e, capacity), jnp.float32)
    counts = jnp.zeros((e,), jnp.float32)  # slots already claimed per e
    kept_gate_sum = jnp.zeros((n,), jnp.float32)
    for slot in range(self.top_k):
      expert = top_idx[:, slot]                      # [N]
      oh = jax.nn.one_hot(expert, e)                 # [N, E]
      # Position of each token within its expert's buffer.
      pos_within = jnp.cumsum(oh, axis=0) - oh       # [N, E]
      pos = ((pos_within + counts[None, :]) * oh).sum(-1)  # [N]
      keep = (pos < capacity).astype(jnp.float32)
      gate = top_probs[:, slot] * keep
      combine = combine + (
          gate[:, None, None] * oh[:, :, None]
          * jax.nn.one_hot(pos.astype(jnp.int32), capacity)[:, None, :])
      counts = counts + (oh * keep[:, None]).sum(0)
      kept_gate_sum = kept_gate_sum + gate
    return combine / jnp.maximum(kept_gate_sum, 1e-9)[:, None, None]

  def _sparse_dispatch(self, tokens, top_probs, top_idx, w1, b1, w2, b2):
    """Capacity-bounded routing via one-hot dispatch/combine einsums."""
    combine = self._pack_combine(top_probs, top_idx,
                                 self._capacity(tokens.shape[0]))
    dispatch = (combine > 0).astype(w1.dtype)        # [N, E, C]

    expert_inputs = jnp.einsum("nec,nf->ecf", dispatch,
                               tokens.astype(w1.dtype))
    hidden = nn.relu(jnp.einsum("ecf,efh->ech", expert_inputs, w1) + b1)
    expert_out = jnp.einsum("ech,eho->eco", hidden, w2) + b2
    combined = jnp.einsum("nec,eco->no",
                          combine.astype(expert_out.dtype), expert_out)
    # Renormalized kept gate mass per expert — the same statistic the
    # dense branch feeds the aux loss, so dispatch mode doesn't change
    # the meaning of moe_aux_loss.
    load = combine.sum(-1).mean(0)
    return combined, load

  def _alltoall_dispatch(self, tokens, top_probs, top_idx, w1, b1, w2, b2):
    """Explicit token routing: shard_map + all_to_all over `ep_axis`.

    Layout: tokens [N, F] and the expert dim of `experts_*` are both
    sharded over `ep_axis` (size S, E % S == 0). Each device packs its
    n_local tokens into [E, C_local] slots, an all_to_all ships each
    expert-group's slots to its owner (-> [E_local, S*C_local]), local
    experts run, and the transpose all_to_all ships results home. The
    backward pass is the transposed schedule (all_to_all is its own
    transpose), derived by autodiff through shard_map.
    """
    if self.mesh is None:
      raise ValueError("dispatch='alltoall' requires a mesh (set the "
                       "`mesh` attr, e.g. via the model's set_mesh hook)")
    axis = self.ep_axis
    s = self.mesh.shape[axis]
    e = self.num_experts
    n = tokens.shape[0]
    if e % s:
      raise ValueError(f"num_experts={e} must be divisible by the "
                       f"'{axis}' axis size {s}")
    if n % s:
      raise ValueError(f"token count {n} must be divisible by the "
                       f"'{axis}' axis size {s}")
    e_local = e // s
    capacity = self._capacity(n // s)  # per SOURCE shard (doc delta)
    compute_dtype = w1.dtype

    def local_fn(tokens_l, top_probs_l, top_idx_l, w1_l, b1_l, w2_l, b2_l):
      combine = self._pack_combine(top_probs_l, top_idx_l, capacity)
      dispatch = (combine > 0).astype(compute_dtype)   # [n_l, E, C]
      slots = jnp.einsum("nec,nf->ecf", dispatch,
                         tokens_l.astype(compute_dtype))
      # [E, C, F] -> [S, E_l, C, F]; all_to_all scatters dim 0 and
      # gathers the source dim in its place: on the receiver, dim 0
      # indexes the SOURCE shard and E_l are its own experts.
      slots = slots.reshape(s, e_local, capacity, -1)
      slots = jax.lax.all_to_all(slots, axis, 0, 0)    # [S, E_l, C, F]
      slots = jnp.moveaxis(slots, 0, 1).reshape(e_local, s * capacity, -1)
      hidden = nn.relu(jnp.einsum("ekf,efh->ekh", slots, w1_l) + b1_l)
      out = jnp.einsum("ekh,eho->eko", hidden, w2_l) + b2_l
      # Ship results back to the token owners (transpose of the inbound
      # schedule), landing as [E, C, O] in global-expert order.
      out = jnp.moveaxis(out.reshape(e_local, s, capacity, -1), 1, 0)
      out = jax.lax.all_to_all(out, axis, 0, 0)        # [S, E_l, C, O]
      out = out.reshape(e, capacity, -1)
      combined = jnp.einsum("nec,eco->no",
                            combine.astype(out.dtype), out)
      load = jax.lax.pmean(combine.sum(-1).mean(0), axis)
      return combined, load

    spec_tok = PartitionSpec(axis, None)
    spec_exp = PartitionSpec(axis, None, None)
    sharded = mesh_lib.shard_map(
        local_fn, mesh=self.mesh,
        in_specs=(spec_tok, spec_tok, spec_tok,
                  spec_exp, spec_exp, spec_exp, spec_exp),
        out_specs=(spec_tok, PartitionSpec()))
    return sharded(tokens, top_probs, top_idx, w1, b1, w2, b2)


class ShardedExpertsMoE(nn.Module):
  """The experts `experts_held = (first, count)` of a layer of `num_experts`
  experts, with the layer's shared expert. As the fields stand:

    p = softmax_float32(x W_r) over all `num_experts`; the `top_k` largest;
    weights p_i / sum of the top_k (over all of them, held here or not);
    expert e: (silu(x W_g^e) * (x W_u^e)) W_d^e, no biases;
    shared: the same form, times sigmoid(x w_s);
    result = shared + the weighted terms of the experts held here.

  Three fields change the form and nothing of the mechanism below.
  `router_scoring='sigmoid'`: s = sigmoid_float32(x W_r); the picks are the
  `top_k` largest of s + b, b the buffer `e_score_correction_bias` (zeros;
  collection `buffers`, so no gradient reaches it); the weights are
  `routed_scaling_factor` x s_i / (sum of the picks' s + 1e-20), from s and
  not from s + b. `expert_form='relu2'`: relu(x W_u^e)^2 W_d^e, one up
  product (`experts_up` [count, hidden, width]) where the gated form has
  gate and up in one (`experts_gate_up`); the shared expert takes the same
  form. `shared_gate=False`: the shared expert is added as it is.

  What the absent experts would add is left out: on a deployment the other
  chips add it. Mechanism: the N x top_k (token, expert) pairs get the held
  expert's local number as key, or `count` where the expert lives elsewhere;
  one sort over all the keys brings the held pairs to the front, grouped by
  expert; the first `rows` of them (a static buffer: `buffer_factor` x the
  balanced load N x top_k x count / num_experts, rounded up to 128 rows)
  are gathered from the tokens, pass two grouped products
  (`ops/grouped_matmul.grouped_matmul`, float32 results: up, or gate and up
  in one, then down) and are scatter-added back by token. Held pairs beyond
  the buffer are dropped and counted. The rows of the buffer that hold no
  pair are zero and are given to the last group, so the group sizes always
  add up to the buffer: the products visit every row tile whatever the
  router picked, and a step's device work does not depend on the weights or
  the batch.

  Returns (result, counters): `moe_rows_held` (held pairs), `moe_buffer_fill`
  (held pairs / buffer rows), `moe_rows_dropped`, `moe_load_max_over_mean`
  (over the experts held).
  """

  num_experts: int = 8
  experts_held: Tuple[int, int] = (0, 8)
  top_k: int = 2
  expert_width: int = 64
  shared_width: int = 64   # 0: no shared expert
  buffer_factor: float = 2.0
  router_scoring: str = "softmax"   # 'softmax' | 'sigmoid'
  routed_scaling_factor: float = 1.0   # sigmoid scoring only
  expert_form: str = "gated_silu"   # 'gated_silu' | 'relu2'
  shared_gate: bool = True
  dtype: Optional[Any] = None

  ROW_TILE = 128  # the buffer is a whole number of these rows

  def buffer_rows(self, n_tokens: int) -> int:
    pairs = n_tokens * self.top_k
    balanced = pairs * self.experts_held[1] / self.num_experts
    tiles = max(1, math.ceil(self.buffer_factor * balanced / self.ROW_TILE))
    return min(tiles, -(-pairs // self.ROW_TILE)) * self.ROW_TILE

  @nn.compact
  def __call__(self, x: jnp.ndarray):
    first, count = self.experts_held
    if not (0 <= first and count > 0 and first + count <= self.num_experts):
      raise ValueError(f"experts_held {self.experts_held} outside the "
                       f"layer's {self.num_experts} experts")
    if self.router_scoring not in ("softmax", "sigmoid"):
      raise ValueError(f"unknown router_scoring {self.router_scoring!r}")
    if self.expert_form not in ("gated_silu", "relu2"):
      raise ValueError(f"unknown expert_form {self.expert_form!r}")
    gated = self.expert_form == "gated_silu"
    features = x.shape[-1]
    tokens = x.reshape(-1, features)
    n = tokens.shape[0]
    rows = self.buffer_rows(n)
    init = nn.initializers.normal(0.02)
    dense = lambda width, name: nn.Dense(  # noqa: E731
        width, use_bias=False, dtype=self.dtype, kernel_init=init, name=name)
    if gated:
      w_up = self.param("experts_gate_up", init,
                        (count, features, 2 * self.expert_width))
    else:
      w_up = self.param("experts_up", init,
                        (count, features, self.expert_width))
    w_down = self.param("experts_down", init,
                        (count, self.expert_width, features))
    if self.dtype is not None:
      w_up, w_down = w_up.astype(self.dtype), w_down.astype(self.dtype)

    def activation(up):
      if gated:
        gate, up = jnp.split(up, 2, axis=-1)
        return jax.nn.silu(gate) * up
      return jnp.square(jax.nn.relu(up))

    with jax.named_scope("moe_route"):
      logits = dense(self.num_experts, "router")(tokens).astype(jnp.float32)
      if self.router_scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        top_probs, top_idx = jax.lax.top_k(probs, self.top_k)
        top_probs = top_probs / jnp.sum(top_probs, axis=-1, keepdims=True)
      else:
        scores = jax.nn.sigmoid(logits)
        bias = self.variable("buffers", "e_score_correction_bias", jnp.zeros,
                             (self.num_experts,), jnp.float32).value
        _, top_idx = jax.lax.top_k(scores + bias, self.top_k)
        top_probs = jnp.take_along_axis(scores, top_idx, axis=-1)
        top_probs = self.routed_scaling_factor * top_probs / (
            jnp.sum(top_probs, axis=-1, keepdims=True) + 1e-20)
      local = top_idx.reshape(-1) - first
      held = (local >= 0) & (local < count)
      keys = jnp.where(held, local, count).astype(jnp.int32)
      keys, pair = jax.lax.sort(
          (keys, jnp.arange(n * self.top_k, dtype=jnp.int32)), num_keys=1)
      keys, pair = keys[:rows], pair[:rows]
      filled = keys < count
      token = jnp.where(filled, pair // self.top_k, 0)
      weight = jnp.where(filled, top_probs.reshape(-1)[pair], 0.0)
      sizes = jnp.sum(keys[:, None] == jnp.arange(count)[None, :], axis=0,
                      dtype=jnp.int32)
      load = jnp.sum(
          (top_idx.reshape(-1)[:, None] - first) == jnp.arange(count)[None, :],
          axis=0, dtype=jnp.int32)
      rows_held = jnp.sum(load)
      # Rows that hold no pair go to the last group: zero rows in, zero
      # rows out, and the same tiles visited in every step.
      sizes = sizes.at[count - 1].add(rows - jnp.sum(sizes))
      buffer = jnp.where(filled[:, None], tokens[token], 0).astype(
          w_up.dtype)

    with jax.named_scope("moe_experts"):
      up = grouped_matmul.grouped_matmul(buffer, w_up, sizes)
      out = grouped_matmul.grouped_matmul(
          activation(up).astype(w_down.dtype), w_down, sizes)

    with jax.named_scope("moe_route"):
      routed = jnp.zeros((n, features), jnp.float32).at[token].add(
          out * weight[:, None])

    result = routed
    if self.shared_width:
      with jax.named_scope("moe_shared"):
        if gated:
          hidden = jax.nn.silu(dense(self.shared_width, "shared_gate_proj")(
              tokens)) * dense(self.shared_width, "shared_up_proj")(tokens)
        else:
          hidden = activation(dense(self.shared_width, "shared_up_proj")(
              tokens))
        shared = dense(features, "shared_down_proj")(hidden).astype(
            jnp.float32)
        if self.shared_gate:
          shared = shared * jax.nn.sigmoid(
              dense(1, "shared_expert_gate")(tokens).astype(jnp.float32))
        result = result + shared
    counters = {
        "moe_rows_held": rows_held.astype(jnp.float32),
        "moe_buffer_fill": rows_held.astype(jnp.float32) / rows,
        "moe_rows_dropped": jnp.maximum(rows_held - rows, 0).astype(
            jnp.float32),
        "moe_load_max_over_mean": jnp.max(load).astype(jnp.float32)
        / jnp.maximum(jnp.mean(load.astype(jnp.float32)), 1e-9),
    }
    return result.astype(x.dtype).reshape(x.shape), counters
