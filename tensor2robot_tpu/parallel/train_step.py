"""SPMD train/eval step factory.

This module replaces the reference's entire TPU execution layer —
`model_fn` assembly (/root/reference/models/abstract_model.py:662-834),
`create_train_op`, `TPUT2RModelWrapper` and `CrossShardOptimizer`
(/root/reference/models/tpu_model_wrapper.py:127-322) — with one jitted
function over a device mesh:

* the global batch is sharded over the `data` axis; computing the mean
  loss over it makes XLA insert the gradient all-reduce over ICI that
  CrossShardOptimizer provided by hand;
* parameters/optimizer state are replicated by default, or sharded over
  the `fsdp` axis via partition rules (ZeRO — beyond the reference);
* per-leaf `TensorSpec.sharding` annotations give tensor parallelism on
  the `model` axis;
* bfloat16 compute with float32 params, EMA shadow params, mutable
  batch-stats threading, and per-step PRNG folding are all part of the
  step.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tensor2robot_tpu import modes as modes_lib
from tensor2robot_tpu import specs as specs_lib

__all__ = ["TrainState", "create_train_state", "make_train_step",
           "make_train_loop", "loop_batch_spec", "make_eval_step",
           "make_eval_loop", "make_predict_fn", "fsdp_rules",
           "state_shardings"]

PartitionRules = Sequence[Tuple[str, PartitionSpec]]


class TrainState(flax.struct.PyTreeNode):
  """The complete training state — one pytree, checkpointable by orbax."""

  step: jnp.ndarray
  params: Any
  opt_state: Any
  mutable_state: Any  # flax mutable collections (batch_stats, ...)
  ema_params: Any  # None when EMA disabled
  rng: jax.Array

  def eval_params(self, use_ema: bool = True):
    """Params for eval/export: EMA shadow when present (the reference's
    swapping-saver semantics, /root/reference/models/optimizers.py:132-159).
    """
    if use_ema and self.ema_params is not None:
      return self.ema_params
    return self.params


def _split_variables(variables: Mapping) -> Tuple[Any, Dict]:
  params = variables["params"]
  mutable = {k: v for k, v in variables.items() if k != "params"}
  return params, mutable


def _optimizer_for(model):
  """The optimizer the step actually uses: `build_optimizer` (framework
  wrappers, e.g. gradient accumulation) when the model provides it —
  subclasses override `create_optimizer`, so calling that directly here
  would silently drop the wrappers."""
  builder = getattr(model, "build_optimizer", None)
  return builder() if builder is not None else model.create_optimizer()


def fsdp_rules(axis: str = "fsdp") -> PartitionRules:
  """Default FSDP rules: shard the largest dim of every >=2D param over
  the fsdp axis (applied only where divisible)."""
  return ((r".*", ("__largest__", axis)),)


def _leaf_partition(path: str, shape: Tuple[int, ...],
                    rules: Optional[PartitionRules],
                    mesh: Mesh) -> PartitionSpec:
  if rules is None or len(shape) < 1:
    return PartitionSpec()
  for pattern, spec in rules:
    if re.search(pattern, path):
      if spec and spec[0] == "__largest__":
        axis_name = spec[1]
        axis_size = mesh.shape[axis_name]
        if axis_size <= 1 or len(shape) < 2:
          return PartitionSpec()
        largest = max(range(len(shape)), key=lambda i: shape[i])
        if shape[largest] % axis_size:
          return PartitionSpec()
        out = [None] * len(shape)
        out[largest] = axis_name
        return PartitionSpec(*out)
      if len(spec) != len(shape):
        return PartitionSpec()
      return PartitionSpec(*spec)
  return PartitionSpec()


def _path_str(path) -> str:
  parts = []
  for entry in path:
    if hasattr(entry, "key"):
      parts.append(str(entry.key))
    elif hasattr(entry, "name"):
      parts.append(str(entry.name))
    elif hasattr(entry, "idx"):
      parts.append(str(entry.idx))
  return "/".join(parts)


def state_shardings(abstract_state: Any, mesh: Mesh,
                    rules: Optional[PartitionRules] = None) -> Any:
  """NamedSharding tree for a TrainState: params (and the param-shaped
  optimizer moments, whose tree paths embed the same param names) follow
  the partition rules; everything else is replicated."""

  def _shard(path, leaf):
    path = _path_str(path)
    shape = getattr(leaf, "shape", ())
    return NamedSharding(mesh, _leaf_partition(path, tuple(shape), rules,
                                               mesh))

  return jax.tree_util.tree_map_with_path(_shard, abstract_state)


def create_train_state(model,
                       rng: jax.Array,
                       sample_features,
                       mesh: Optional[Mesh] = None,
                       rules: Optional[PartitionRules] = None,
                       mode: str = modes_lib.TRAIN) -> Tuple[TrainState, Any]:
  """Initializes a (sharded) TrainState; returns (state, shardings).

  With a mesh, init runs under jit with out_shardings so large params are
  *born sharded* — never materialized replicated on one device.
  """
  optimizer = _optimizer_for(model)

  def _init(rng, features):
    init_rng, state_rng = jax.random.split(rng)
    variables = model.init_variables(init_rng, features, mode=mode)
    params, mutable = _split_variables(variables)
    opt_state = optimizer.init(params)
    # Fresh buffers for the EMA shadow: aliasing params would make the
    # donated train-step receive the same buffer twice.
    ema = (jax.tree_util.tree_map(jnp.copy, params)
           if model.use_ema else None)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt_state, mutable_state=mutable,
                      ema_params=ema, rng=state_rng)

  if mesh is None:
    return _init(rng, sample_features), None
  abstract = jax.eval_shape(_init, rng, sample_features)
  shardings = state_shardings(abstract, mesh, rules)
  init_fn = jax.jit(_init, out_shardings=shardings)
  with jax.transfer_guard_device_to_host("allow"):
    state = init_fn(rng, sample_features)
  return state, shardings


def _batch_shardings(mesh: Mesh, batch, batch_axis: str = "data"):
  def _one(x):
    return NamedSharding(mesh, PartitionSpec(batch_axis))

  return jax.tree_util.tree_map(_one, batch)


def _build_step_fn(model) -> Callable:
  """The un-jitted train-step body shared by `make_train_step` (one step
  per dispatch) and `make_train_loop` (a `lax.scan` of it)."""
  optimizer = _optimizer_for(model)
  accum_steps = int(getattr(model, "gradient_accumulation_steps", 1) or 1)
  ema_decay = model.ema_decay
  # Multi-task gradient surgery (QT-Opt PCGrad,
  # /root/reference/research/qtopt/pcgrad.py): when the model exposes
  # model_task_losses_fn and enables use_pcgrad, per-task gradients are
  # computed via jacrev and combined with conflict projection.
  use_pcgrad = bool(getattr(model, "use_pcgrad", False)) and (
      getattr(model, "model_task_losses_fn", None) is not None)

  def step_fn(state: TrainState, features, labels):
    step_rng = jax.random.fold_in(state.rng, state.step)

    def _forward_impl(params, features):
      variables = {"params": params, **state.mutable_state}
      compute_features = model.cast_features_for_compute(features)
      outputs, new_mutable = model.inference_network_fn(
          variables, compute_features, modes_lib.TRAIN, rng=step_rng,
          train=True)
      outputs = jax.tree_util.tree_map(
          lambda x: x.astype(jnp.float32)
          if hasattr(x, "dtype") and x.dtype == jnp.bfloat16 else x, outputs)
      return outputs, new_mutable

    if getattr(model, "remat", False):
      # Recompute the forward in the backward pass instead of storing
      # activations (jax.checkpoint): HBM for FLOPs, the standard knob
      # for fitting reference-scale batches on one chip.
      _forward_impl = jax.checkpoint(_forward_impl)

    def _forward(params):
      return _forward_impl(params, features)

    if use_pcgrad:
      from tensor2robot_tpu.ops import pcgrad as pcgrad_lib

      def losses_vec(params):
        outputs, new_mutable = _forward(params)
        task_losses = model.model_task_losses_fn(
            features, labels, outputs, modes_lib.TRAIN)
        stacked = jnp.stack([task_losses[k] for k in sorted(task_losses)])
        return stacked, (task_losses, new_mutable)

      with jax.named_scope("loss"):
        task_grads_tree, (task_losses, new_mutable) = jax.jacrev(
            losses_vec, has_aux=True)(state.params)
      n_tasks = len(task_losses)
      task_grads = [
          jax.tree_util.tree_map(lambda g, i=i: g[i], task_grads_tree)
          for i in range(n_tasks)]
      grads = pcgrad_lib.pcgrad_combine(
          task_grads,
          use_flat_projection=getattr(model, "pcgrad_flat_projection",
                                      False),
          allowlist=getattr(model, "pcgrad_allowlist", None),
          denylist=getattr(model, "pcgrad_denylist", None))
      loss = sum(task_losses.values())
      scalars = {f"task_loss/{k}": v for k, v in task_losses.items()}
    else:
      def loss_fn(params):
        outputs, new_mutable = _forward(params)
        loss, scalars = model.model_train_fn(
            features, labels, outputs, modes_lib.TRAIN)
        return loss, (scalars, new_mutable)

      with jax.named_scope("loss"):
        (loss, (scalars, new_mutable)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
    with jax.named_scope("optimizer"):
      updates, new_opt_state = optimizer.update(grads, state.opt_state,
                                                state.params)
      new_params = optax.apply_updates(state.params, updates)
    new_ema = state.ema_params
    if new_ema is not None:
      if accum_steps > 1:
        # Under gradient accumulation the EMA must move once per APPLIED
        # update, not per micro-step — otherwise the effective decay is
        # decay^k and eval/export EMA params diverge from an equivalent
        # large-batch run. MultiSteps resets mini_step to 0 on apply.
        applied = new_opt_state.mini_step == 0
        move = lambda e, p: jnp.where(  # noqa: E731
            applied, e * ema_decay + (1.0 - ema_decay) * p, e)
      else:
        move = lambda e, p: e * ema_decay + (1.0 - ema_decay) * p  # noqa: E731
      with jax.named_scope("ema"):
        new_ema = jax.tree_util.tree_map(move, new_ema, new_params)
    new_state = state.replace(
        step=state.step + 1,
        params=new_params,
        opt_state=new_opt_state,
        mutable_state=new_mutable if new_mutable else state.mutable_state,
        ema_params=new_ema)
    # A scope of its own (`obs.xray.DEVICE_SCOPES`): the norm reads every
    # gradient once more, outside `loss` and `optimizer`.
    with jax.named_scope("metrics"):
      gradient_norm = optax.global_norm(grads)
    metrics = {"loss": loss, "global_gradient_norm": gradient_norm,
               **scalars}
    return new_state, metrics

  return step_fn


def make_train_step(model,
                    mesh: Optional[Mesh] = None,
                    shardings: Any = None,
                    batch_axis: str = "data",
                    batch_spec: Optional[PartitionSpec] = None,
                    donate: bool = True) -> Callable:
  """Builds the jitted SPMD train step: (state, features, labels) ->
  (state, scalars).

  `batch_spec` overrides the default batch-dim-only sharding for
  features/labels — e.g. PartitionSpec('data', 'sp') commits sequence
  batches [B, T, ...] sharded over BOTH the data and sequence-parallel
  axes at infeed (models expose it via `batch_partition_spec`)."""
  step_fn = _build_step_fn(model)
  # The name the device trace shows: `jit_t2r_train_step` on its module line.
  step_fn.__name__ = step_fn.__qualname__ = "t2r_train_step"
  if mesh is None:
    return jax.jit(step_fn, donate_argnums=(0,) if donate else ())
  batch_ns = NamedSharding(mesh, batch_spec or PartitionSpec(batch_axis))
  replicated_ns = NamedSharding(mesh, PartitionSpec())
  return jax.jit(
      step_fn,
      in_shardings=(shardings, batch_ns, batch_ns),
      # replicated_ns is a pytree prefix covering the whole metrics dict
      out_shardings=(shardings, replicated_ns),
      donate_argnums=(0,) if donate else ())


def loop_batch_spec(batch_spec: Optional[PartitionSpec] = None,
                    batch_axis: str = "data") -> PartitionSpec:
  """The PartitionSpec for a staged [K, B, ...] loop batch: the per-step
  batch sharding with the scan axis unsharded. The ONE derivation shared
  by `make_train_loop`'s in_shardings and the trainer's `place_batch`
  call, so placement can never silently desync from the jit's committed
  shardings."""
  return PartitionSpec(None, *(batch_spec if batch_spec is not None
                               else PartitionSpec(batch_axis)))


def make_train_loop(model,
                    num_steps: int,
                    mesh: Optional[Mesh] = None,
                    shardings: Any = None,
                    batch_axis: str = "data",
                    batch_spec: Optional[PartitionSpec] = None,
                    donate: bool = True) -> Callable:
  """Builds a jitted K-step train LOOP: (state, features, labels) ->
  (state, stacked scalars), with features/labels carrying a leading
  `num_steps` axis of pre-staged batches and the step body running under
  `lax.scan` entirely on device.

  This is the TPU-idiomatic host-training-loop: the reference amortizes
  host round-trips with TPUEstimator `iterations_per_loop`
  (/root/reference/models/abstract_model.py:662-834 runs under
  TPUEstimatorSpec; the estimator loops on-device between session
  calls). Over a remote-dispatch transport every per-step host round
  trip costs wall-clock that the chip spends idle; scanning K real
  train steps per dispatch divides that overhead by K. Semantics are
  pinned identical to K sequential `make_train_step` calls (metrics are
  returned per-step, stacked on a leading axis)."""
  if num_steps < 1:
    raise ValueError(f"num_steps must be >= 1, got {num_steps}")
  step_fn = _build_step_fn(model)

  def loop_fn(state: TrainState, features, labels):
    def body(carry, batch):
      f, l = batch
      new_state, metrics = step_fn(carry, f, l)
      return new_state, metrics

    state, metrics = jax.lax.scan(body, state, (features, labels),
                                  length=num_steps)
    return state, metrics

  loop_fn.__name__ = loop_fn.__qualname__ = f"t2r_train_loop_k{num_steps}"
  if mesh is None:
    return jax.jit(loop_fn, donate_argnums=(0,) if donate else ())
  loop_ns = NamedSharding(mesh, loop_batch_spec(batch_spec, batch_axis))
  replicated_ns = NamedSharding(mesh, PartitionSpec())
  return jax.jit(
      loop_fn,
      in_shardings=(shardings, loop_ns, loop_ns),
      out_shardings=(shardings, replicated_ns),
      donate_argnums=(0,) if donate else ())


def _build_eval_fn(model, use_ema: bool) -> Callable:
  """The un-jitted eval body shared by `make_eval_step` and
  `make_eval_loop`."""

  def eval_fn(state: TrainState, features, labels):
    params = state.eval_params(use_ema=use_ema)
    variables = {"params": params, **state.mutable_state}
    compute_features = model.cast_features_for_compute(features)
    outputs, _ = model.inference_network_fn(
        variables, compute_features, modes_lib.EVAL, train=False)
    outputs = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if hasattr(x, "dtype") and x.dtype == jnp.bfloat16 else x, outputs)
    return model.model_eval_fn(features, labels, outputs)

  return eval_fn


def make_eval_step(model,
                   mesh: Optional[Mesh] = None,
                   shardings: Any = None,
                   batch_axis: str = "data",
                   batch_spec: Optional[PartitionSpec] = None,
                   use_ema: bool = True) -> Callable:
  """Jitted eval step: (state, features, labels) -> metric scalars."""
  eval_fn = _build_eval_fn(model, use_ema)
  if mesh is None:
    return jax.jit(eval_fn)
  batch_ns = NamedSharding(mesh, batch_spec or PartitionSpec(batch_axis))
  return jax.jit(eval_fn, in_shardings=(shardings, batch_ns, batch_ns))


def make_eval_loop(model,
                   num_steps: int,
                   mesh: Optional[Mesh] = None,
                   shardings: Any = None,
                   batch_axis: str = "data",
                   batch_spec: Optional[PartitionSpec] = None,
                   use_ema: bool = True) -> Callable:
  """Jitted K-batch eval LOOP: (state, features, labels) -> metric
  scalars SUMMED over the K batches (divide by K for the mean), with
  features/labels carrying a leading `num_steps` axis.

  The eval twin of `make_train_loop`: in iterations_per_loop training
  the ~8 ms per-dispatch transport floor (PERFORMANCE.md round 5)
  would otherwise make a 100-batch eval cost more wall-clock than the
  500 train steps between evals. Summing on device keeps the host
  transfer to one scalar dict per K batches."""
  if num_steps < 1:
    raise ValueError(f"num_steps must be >= 1, got {num_steps}")
  eval_fn = _build_eval_fn(model, use_ema)

  def loop_fn(state: TrainState, features, labels):
    def body(carry, batch):
      f, l = batch
      return carry, eval_fn(state, f, l)

    _, metrics = jax.lax.scan(body, None, (features, labels),
                              length=num_steps)
    return jax.tree_util.tree_map(lambda x: jnp.sum(x, axis=0), metrics)

  if mesh is None:
    return jax.jit(loop_fn)
  loop_ns = NamedSharding(mesh, loop_batch_spec(batch_spec, batch_axis))
  replicated_ns = NamedSharding(mesh, PartitionSpec())
  return jax.jit(loop_fn,
                 in_shardings=(shardings, loop_ns, loop_ns),
                 out_shardings=replicated_ns)


def make_predict_fn(model, use_ema: bool = True) -> Callable:
  """Jitted predict: (state, features) -> export outputs (the PREDICT
  branch + create_export_outputs_fn,
  /root/reference/models/abstract_model.py:714-736)."""

  def predict_fn(state: TrainState, features):
    params = state.eval_params(use_ema=use_ema)
    variables = {"params": params, **state.mutable_state}
    compute_features = model.cast_features_for_compute(features)
    outputs, _ = model.inference_network_fn(
        variables, compute_features, modes_lib.PREDICT, train=False)
    outputs = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if hasattr(x, "dtype") and x.dtype == jnp.bfloat16 else x, outputs)
    return model.create_export_outputs_fn(features, outputs)

  return jax.jit(predict_fn)
