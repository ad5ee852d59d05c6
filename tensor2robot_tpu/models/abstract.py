"""Model protocol: the heart of the framework.

Re-design of the reference's `ModelInterface`/`AbstractT2RModel`
(/root/reference/models/model_interface.py:47-145,
/root/reference/models/abstract_model.py:161-981). The reference assembles
a TF1 EstimatorSpec from `inference_network_fn` + `model_train_fn` +
`model_eval_fn` inside `model_fn`; here the same pieces are pure functions
over pytrees, and a generic SPMD step factory
(`tensor2robot_tpu.parallel.train_step`) builds the jitted train/eval steps
— replacing model_fn, create_train_op, TPUT2RModelWrapper and
CrossShardOptimizer in one stroke.

A model provides:
* `get_feature_specification(mode)` / `get_label_specification(mode)` —
  the spec contract consumed by data/export/serving layers;
* `create_module()` — a flax.linen Module whose `__call__(features,
  mode, train)` returns a SpecStruct/dict of inference outputs (the
  reference's `inference_network_fn`);
* `model_train_fn(features, labels, inference_outputs, mode)` ->
  `(loss, scalars)`;
* `model_eval_fn(features, labels, inference_outputs)` -> metric scalars;
* `create_optimizer()` -> optax transformation (gin-injected factory);
* optional `create_export_outputs_fn` for serving signatures.

bfloat16 policy: `use_bfloat16 == True` wraps the preprocessor in
`Bfloat16DevicePolicy` (infeed cast) and the step factory runs the forward
pass in bfloat16 with float32 params — the JAX equivalent of the
reference's bfloat16_scope + TPUPreprocessorWrapper
(/root/reference/models/tpu_model_wrapper.py:107-191).
"""

from __future__ import annotations

import abc
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from tensor2robot_tpu import modes as modes_lib
from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.models import optimizers as optimizers_lib
from tensor2robot_tpu.preprocessors import base as preprocessors_lib
from tensor2robot_tpu.utils import config

__all__ = ["ModelInterface", "T2RModel"]


class ModelInterface(abc.ABC):
  """Minimal contract used by all infra: train_eval, input generators,
  exporters, predictors (reference model_interface.py:47-145)."""

  @abc.abstractmethod
  def get_feature_specification(self, mode: str) -> specs_lib.SpecStruct:
    ...

  @abc.abstractmethod
  def get_label_specification(self, mode: str) -> specs_lib.SpecStruct:
    ...

  @property
  @abc.abstractmethod
  def preprocessor(self) -> preprocessors_lib.AbstractPreprocessor:
    ...


class T2RModel(ModelInterface):
  """Base model: specs + flax module + loss/metrics + optimizer factory."""

  def __init__(self,
               preprocessor_cls: Optional[Callable] = None,
               optimizer_fn: Optional[Callable] = None,
               device_type: str = "tpu",
               use_bfloat16: bool = False,
               use_ema: bool = False,
               ema_decay: float = 0.9999,
               remat: bool = False,
               gradient_accumulation_steps: int = 1,
               init_checkpoint: Optional[str] = None,
               init_checkpoint_filter: Optional[Callable[[str], bool]] = None,
               use_summaries: bool = True):
    self._preprocessor_cls = preprocessor_cls
    self._optimizer_fn = optimizer_fn
    self._device_type = device_type
    self._use_bfloat16 = use_bfloat16
    self._use_ema = use_ema
    self._ema_decay = ema_decay
    # Rematerialization: recompute the forward during the backward
    # instead of keeping activations live — trades MXU FLOPs for HBM,
    # the standard fit-bigger-batches knob on TPU (jax.checkpoint).
    self._remat = remat
    # Gradient accumulation: average grads over k micro-batches and
    # apply every k-th step (optax.MultiSteps) — the other
    # fit-bigger-effective-batches knob; composes with remat.
    if gradient_accumulation_steps < 1:
      raise ValueError("gradient_accumulation_steps must be >= 1, got "
                       f"{gradient_accumulation_steps}")
    self._gradient_accumulation_steps = int(gradient_accumulation_steps)
    self._init_checkpoint = init_checkpoint
    self._init_checkpoint_filter = init_checkpoint_filter
    self._use_summaries = use_summaries and device_type != "tpu"
    self._preprocessor: Optional[preprocessors_lib.AbstractPreprocessor] = None
    self._module: Optional[nn.Module] = None

  # -- properties -----------------------------------------------------------

  @property
  def device_type(self) -> str:
    return self._device_type

  @property
  def use_bfloat16(self) -> bool:
    return self._use_bfloat16

  @property
  def use_ema(self) -> bool:
    return self._use_ema

  @property
  def remat(self) -> bool:
    return self._remat

  @property
  def ema_decay(self) -> float:
    return self._ema_decay

  @property
  def init_checkpoint(self) -> Optional[str]:
    return self._init_checkpoint

  @property
  def init_checkpoint_filter(self):
    return self._init_checkpoint_filter

  @property
  def use_summaries(self) -> bool:
    return self._use_summaries

  @property
  def preprocessor(self) -> preprocessors_lib.AbstractPreprocessor:
    """Preprocessor wired to this model's specs; bfloat16-wrapped on TPU
    (reference tpu_model_wrapper.py:122-125)."""
    if self._preprocessor is None:
      cls = self._preprocessor_cls or preprocessors_lib.NoOpPreprocessor
      preprocessor = cls(
          model_feature_specification_fn=self.get_feature_specification,
          model_label_specification_fn=self.get_label_specification)
      if self._use_bfloat16:
        preprocessor = preprocessors_lib.Bfloat16DevicePolicy(preprocessor)
      self._preprocessor = preprocessor
    return self._preprocessor

  @property
  def module(self) -> nn.Module:
    if self._module is None:
      self._module = self.create_module()
    return self._module

  # -- mesh plumbing (models that specialize their module on the mesh) ------

  def _set_mesh_guarded(self, mesh, validate=None) -> None:
    """Shared `set_mesh` plumbing: enforces the call-before-build
    contract (the module is specialized on the mesh at create_module
    time, so changing it afterwards would silently be ignored), runs the
    model's extra `validate(mesh)` checks, then stores the mesh on
    `self._mesh`. One implementation for every mesh-aware model
    (pipelined/sequence/BCZ/Grasp2Vec) so a change to the staleness rule
    lands everywhere at once."""
    if self._module is not None and getattr(self, "_mesh", None) is not mesh:
      raise ValueError("set_mesh must be called before the module is "
                       "built (create_train_state / first forward).")
    if mesh is not None and validate is not None:
      validate(mesh)
    self._mesh = mesh

  @staticmethod
  def _validate_pp_stage_count(mesh, pp_axis: str, num_stages: int,
                               what: str = "trunk",
                               num_virtual_stages: int = 1) -> None:
    """A >1 `pp_axis` must match the pipelined trunk's stage count —
    the pipeline schedules place `num_virtual_stages` stage chunks per
    pp rank (one for GPipe, v for interleaved 1F1B)."""
    if pp_axis in mesh.shape and mesh.shape[pp_axis] > 1 \
        and mesh.shape[pp_axis] * num_virtual_stages != num_stages:
      raise ValueError(
          f"mesh axis {pp_axis!r} has size {mesh.shape[pp_axis]} and "
          f"num_virtual_stages={num_virtual_stages} but the {what} has "
          f"{num_stages} stages; stages must match ranks x virtual "
          "chunks.")

  # -- abstract model surface ----------------------------------------------

  @abc.abstractmethod
  def create_module(self) -> nn.Module:
    """The network as a flax module; `__call__(features, mode, train)`
    returns a mapping of inference outputs."""

  @abc.abstractmethod
  def model_train_fn(self, features, labels, inference_outputs,
                     mode: str) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Loss + scalar outputs (reference abstract_model.py model_train_fn)."""

  def model_eval_fn(self, features, labels, inference_outputs
                    ) -> Dict[str, jnp.ndarray]:
    """Eval metric scalars; defaults to the train loss (reference
    model_eval_fn)."""
    loss, scalars = self.model_train_fn(
        features, labels, inference_outputs, modes_lib.EVAL)
    return {"loss": loss, **scalars}

  def create_export_outputs_fn(self, features, inference_outputs
                               ) -> Dict[str, jnp.ndarray]:
    """Serving outputs; defaults to all inference outputs (reference
    create_export_outputs_fn / PredictOutput signatures)."""
    if isinstance(inference_outputs, Mapping):
      return dict(inference_outputs.items())
    return {"output": inference_outputs}

  # -- session-decode seam (ISSUE 11: stateful serving sessions) ------------

  @property
  def supports_sessions(self) -> bool:
    """True when the model exposes the O(1)-per-tick decode seam below
    (`serving.session.SessionEngine` checks this before building decode
    executables). Sequential models override all three members."""
    return False

  def init_session_state(self, batch_size: int):
    """Fresh per-session recurrent/KV state as a HOST pytree of numpy
    zeros with leading dim `batch_size` — one row per session, including
    an `index` leaf ([batch] int32, the session's current tick). The
    serving arena stacks these rows device-side; backend-free by
    contract (no jax import on this path)."""
    raise NotImplementedError(
        f"{type(self).__name__} has no session-decode seam; set "
        "supports_sessions/init_session_state/decode_step_fn to serve "
        "it through stateful sessions.")

  def decode_step_fn(self):
    """A PURE `fn(state, session_state, features) -> (new_session_state,
    outputs)` advancing every session row ONE tick: `features` holds
    model-layout per-tick slices (e.g. observation [B, obs]), and the
    returned state must be rebound by the caller — the graftlint
    `session-state-leak` rule flags call sites that drop it. Jitted and
    bucket-compiled by `serving.session.SessionEngine`."""
    raise NotImplementedError(
        f"{type(self).__name__} has no session-decode seam.")

  @property
  def supports_decode_kernel(self) -> bool:
    """True when the model exposes `decode_arena_step_fn` below — the
    graftkern fused-arena decode seam (ISSUE 20). False (the default)
    auto-gates `SessionEngine(use_decode_kernel=None)` onto the plain
    jitted `decode_step_fn` path: carry-based models (LSTM) have no KV
    arena layout for the kernel to stream."""
    return False

  def decode_arena_step_fn(self):
    """A PURE `fn(state, arena, slots, features, mask) -> (new_arena,
    outputs)` advancing the masked lanes ONE tick directly against the
    WHOLE session arena (leaves [max_sessions + 1, ...], slot 0 the
    null slot) — the fused alternative to gather -> `decode_step_fn`
    -> scatter: KV leaves ride `ops.decode_kernels.fused_decode_attention`
    (one kernel launch per leaf family, O(index) HBM traffic, in-place
    append), tiny leaves (the tick index) update via XLA scatters.
    Must be tick-for-tick numerics-equivalent to the `decode_step_fn`
    composition on live lanes — `SessionEngine` keeps that path as the
    semantics-pinned fallback and tests pin parity at every T."""
    raise NotImplementedError(
        f"{type(self).__name__} has no fused-arena decode seam; set "
        "supports_decode_kernel/decode_arena_step_fn to serve it "
        "through the graftkern decode-kernel tier.")

  def create_optimizer(self) -> optax.GradientTransformation:
    """Optax chain; gin-injected factory wins (reference create_optimizer +
    MovingAverage wrapping, abstract_model.py:836-871). Subclasses may
    override; the train-step factories consume `build_optimizer`, which
    applies framework wrappers on top of whatever this returns."""
    fn = self._optimizer_fn or optimizers_lib.create_adam_optimizer
    return fn()

  def build_optimizer(self) -> optax.GradientTransformation:
    """`create_optimizer` plus framework wrappers — the method the step
    factories call. Do NOT override this one (override create_optimizer
    instead), or subclass optimizer choices would silently drop the
    wrappers. With `gradient_accumulation_steps=k`, gradients average
    over k micro-batch steps and apply on every k-th
    (optax.MultiSteps): k steps at batch B train exactly like one step
    at batch k*B for linear-in-grad optimizers, without holding k*B
    activations."""
    optimizer = self.create_optimizer()
    if self._gradient_accumulation_steps > 1:
      optimizer = optax.MultiSteps(
          optimizer, every_k_schedule=self._gradient_accumulation_steps)
    return optimizer

  @property
  def gradient_accumulation_steps(self) -> int:
    return self._gradient_accumulation_steps

  # -- functional init / apply ---------------------------------------------

  def init_variables(self, rng: jax.Array, features,
                     mode: str = modes_lib.TRAIN) -> Any:
    """Initializes flax variables from a (possibly abstract) batch."""
    init_rng, dropout_rng = jax.random.split(rng)
    return self.module.init(
        {"params": init_rng, "dropout": dropout_rng}, features, mode=mode,
        train=(mode == modes_lib.TRAIN))

  def inference_network_fn(self,
                           variables: Any,
                           features,
                           mode: str,
                           rng: Optional[jax.Array] = None,
                           train: bool = False,
                           **module_kwargs) -> Tuple[Any, Any]:
    """Pure forward pass; returns (outputs, updated_mutable_state).

    The reference's inference_network_fn
    (/root/reference/models/abstract_model.py:703) with flax mutable
    collections (batch_stats) threaded explicitly. Extra `module_kwargs`
    are forwarded to the module call — the analogue of the reference's
    `params` plumbing (e.g. `params['is_inner_loop']`,
    vrgripper_env_models.py:377) for modules whose behavior depends on
    static flags.
    """
    rngs = {"dropout": rng} if rng is not None else {}
    mutable = ["batch_stats"] if train else False
    if self._use_bfloat16:
      # Mixed precision: float32 master params, bfloat16 compute. Flax
      # modules promote to the widest input dtype, so bf16 activations
      # against f32 params would silently compute in f32 — cast the
      # params down for the forward; gradients flow back through the
      # cast to the f32 masters.
      # A scope of its own (`obs.xray.DEVICE_SCOPES`): XLA does not fuse
      # the casts of large leaves, it writes a bfloat16 copy of each, in
      # no module's name (5.4 ms a step at 667 M parameters: PERF.md, PR 37).
      variables = dict(variables)
      with jax.named_scope("param_cast"):
        variables["params"] = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if hasattr(x, "dtype") and x.dtype == jnp.float32 else x,
            variables["params"])
    out = self.module.apply(variables, features, mode=mode, train=train,
                            rngs=rngs, mutable=mutable, **module_kwargs)
    if mutable:
      outputs, new_state = out
      return outputs, new_state
    return out, {}

  # -- dtype policy ---------------------------------------------------------

  @property
  def compute_dtype(self):
    return jnp.bfloat16 if self._use_bfloat16 else jnp.float32

  def cast_features_for_compute(self, features):
    """float32 -> bfloat16 on the way into the network when the bfloat16
    policy is active (reference tpu_model_wrapper.py:179-191)."""
    if not self._use_bfloat16:
      return features

    def _cast(x):
      if hasattr(x, "dtype") and x.dtype == jnp.float32:
        return x.astype(jnp.bfloat16)
      return x

    return jax.tree_util.tree_map(_cast, features)
