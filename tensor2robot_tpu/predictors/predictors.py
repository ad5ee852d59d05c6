"""Predictors: load trained artifacts, serve `predict(features) -> dict`.

Reference surface (/root/reference/predictors/):
* `AbstractPredictor` (abstract_predictor.py:26-81) — the robot-side
  contract: predict / get_feature_specification / restore / close;
* `ExportedSavedModelPredictor` (exported_savedmodel_predictor.py:53-359)
  — polls timestamped export dirs, validates them, loads assets, serves;
* `CheckpointPredictor` (checkpoint_predictor.py:37-215) — rebuilds the
  PREDICT graph from the model and restores raw training checkpoints;
* `EnsembleExportedSavedModelPredictor`
  (ensemble_exported_savedmodel_predictor.py:32-180) — random sub-sampled
  mean over several exports.

TPU-native redesign: a predictor holds a jitted predict function plus a
restored variables pytree; "loading an export" = reading the bundle's
assets + orbax params and (when no model object is supplied)
reconstructing the model from the bundle's operative config.
"""

from __future__ import annotations

import abc
import glob
import importlib
import json
import os
import threading
import time
from typing import (Any, Callable, Dict, List, Mapping, NamedTuple, Optional,
                    Sequence)

import jax
import numpy as np
import orbax.checkpoint as ocp

from tensor2robot_tpu import checkpoints as checkpoints_lib
from tensor2robot_tpu import modes as modes_lib
from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.export import export_generator as export_lib
from tensor2robot_tpu.obs import metrics as obs_metrics
from tensor2robot_tpu.obs import sentinel as obs_sentinel
from tensor2robot_tpu.obs import trace as obs_trace
from tensor2robot_tpu.obs import xray as obs_xray
from tensor2robot_tpu.parallel import train_step as ts
from tensor2robot_tpu.utils import config

__all__ = ["AbstractPredictor", "CheckpointPredictor",
           "ExportedModelPredictor", "EnsemblePredictor", "ServingBundle",
           "DecodeBundle"]


class ServingBundle(NamedTuple):
  """What `serving.BucketedEngine` needs from a predictor (see
  `_JaxPredictorBase.serving_bundle`)."""

  jit_predict: Callable      # jitted (state, model_features) -> outputs
  get_state: Callable        # () -> current TrainState (restore-aware)
  preprocess: Callable       # wire features -> model-layout features
  feature_spec: Any          # wire-layout feature spec (warmup synthesis)


class DecodeBundle(NamedTuple):
  """What `serving.session.SessionEngine` needs from a predictor (see
  `_JaxPredictorBase.decode_bundle`): the model's session-decode seam
  plus the restore-aware state getter."""

  decode_fn: Callable          # pure (state, session_state, features)
                               #   -> (new_session_state, outputs)
  init_session_state: Callable  # (batch_size) -> host numpy state rows
  get_state: Callable          # () -> current TrainState (restore-aware)
  observation_spec: Any        # per-TICK feature spec (warmup synthesis)
  max_ticks: Optional[int] = None  # decode horizon (KV capacity); None
                                   #   = unbounded (pure-carry models)
  decode_arena_fn: Optional[Callable] = None  # graftkern fused-arena
                               #   (state, arena, slots, features, mask)
                               #   -> (new_arena, outputs); None = the
                               #   model has no kernel-tier layout


class AbstractPredictor(abc.ABC):
  """The robot-side serving contract."""

  @abc.abstractmethod
  def predict(self, features: Mapping[str, Any]) -> Dict[str, np.ndarray]:
    ...

  @abc.abstractmethod
  def get_feature_specification(self) -> specs_lib.SpecStruct:
    ...

  @abc.abstractmethod
  def restore(self) -> bool:
    """Loads the newest artifact; returns True on success."""

  def init_randomly(self) -> None:
    raise NotImplementedError(
        f"{type(self).__name__} does not support random init.")

  @property
  def model_version(self) -> int:
    return self.global_step

  @property
  def global_step(self) -> int:
    return -1

  def assert_is_loaded(self) -> None:
    if self.global_step < 0:
      raise ValueError(f"{type(self).__name__} has no model loaded; call "
                       "restore() first.")

  def close(self) -> None:
    pass


class _JaxPredictorBase(AbstractPredictor):
  """Common predict plumbing: pack features by spec, run jitted fn.

  `latency_slo_ms` arms the serving SLO breach counter
  (`serve/slo_breaches`, `obs.sentinel.observe_serving_latency`):
  every predict whose END-TO-END latency (the `np.asarray` fetch is the
  barrier) exceeds it increments the counter — a latency
  regression becomes a counter delta in the graftscope report instead
  of a percentile archaeology session. None disables.

  `executable_cache_dir` arms graftcache (`obs.excache`) on the
  in-process predict path: the `serve/predict` executable persists to
  disk, so a robot-side predictor restart deserializes its warm
  executable instead of recompiling (the cold-start tax the reference's
  SavedModel reload also paid per process). None disables; serving
  never breaks on cache trouble (excache fallback contract). The
  graftserve `BucketedEngine` has its own `cache=` seam for the bucket
  ladder."""

  def __init__(self, latency_slo_ms: Optional[float] = None,
               executable_cache_dir: Optional[str] = None):
    self._model = None
    self._state: Optional[ts.TrainState] = None
    self._predict_fn: Optional[Callable] = None
    self._jit_predict: Optional[Callable] = None
    self._global_step = -1
    self._latency_slo_ms = latency_slo_ms
    self._executable_cache_dir = executable_cache_dir
    self._device = None  # replica pin (place_on_device); None = default

  def _build_predict(self) -> None:
    model = self._model
    # The raw jitted predict fn is kept separately from the xray wrapper:
    # graftserve's BucketedEngine AOT-compiles IT once per shape bucket
    # (`serving_bundle`), while the in-process predict path below wraps
    # it in compile telemetry frozen at the first live shape.
    self._jit_predict = ts.make_predict_fn(model)
    # graftscope-xray compile telemetry: the first predict AOT-compiles
    # through analyze_jit (compile time / jaxpr size / cost analysis
    # into the `serve/predict` record) and later calls reuse that
    # executable; a batch-size change or an analysis failure silently
    # degrades to the plain jitted fn (serving must never break on
    # telemetry).
    predict = obs_xray.XrayedFunction("serve/predict", self._jit_predict,
                                      cache=self._executable_cache_dir)
    preprocessor = model.preprocessor

    def fn(features):
      features, _ = preprocessor.preprocess(
          features, specs_lib.SpecStruct(), modes_lib.PREDICT)
      return predict(self._state, features)

    self._predict_fn = fn
    # Model-layout path for callers that already built post-preprocessor
    # features (e.g. WTL pack_features, whose meta layout is not the
    # preprocessor's wire format).
    self._predict_preprocessed_fn = lambda features: predict(self._state,
                                                             features)

  def serving_bundle(self) -> "ServingBundle":
    """The graftserve seam: the pieces an external serving runtime needs.

    Returns the RAW jitted predict fn (AOT-traceable per shape bucket —
    not the xray wrapper, which freezes at its first live shape), a
    state getter (so a later `restore()` hot-swap is visible to cached
    executables without re-warming: shapes/dtypes are stable across
    restores, only values change), the host-side preprocess fn that
    maps wire-layout features to the model layout, and the wire-layout
    feature spec for synthesizing warmup batches.
    """
    self.assert_is_loaded()
    model = self._model
    preprocessor = model.preprocessor

    def preprocess(features):
      features, _ = preprocessor.preprocess(
          features, specs_lib.SpecStruct(), modes_lib.PREDICT)
      return features

    return ServingBundle(
        jit_predict=self._jit_predict,
        get_state=lambda: self._state,
        preprocess=preprocess,
        feature_spec=self.get_feature_specification())

  def decode_bundle(self) -> "DecodeBundle":
    """The session-serving seam (ISSUE 11): the model's pure decode-step
    fn + session-state initializer, with the SAME restore-aware state
    getter as `serving_bundle` — a checkpoint hot-swap lands on the next
    decode tick without re-warming the session engine. Raises for models
    without the seam (`supports_sessions` is the capability flag)."""
    self.assert_is_loaded()
    model = self._model
    if not getattr(model, "supports_sessions", False):
      raise ValueError(
          f"{type(model).__name__} has no session-decode seam "
          "(supports_sessions is False); serve it through the stateless "
          "BucketedEngine instead.")
    return DecodeBundle(
        decode_fn=model.decode_step_fn(),
        init_session_state=model.init_session_state,
        get_state=lambda: self._state,
        observation_spec=model.decode_observation_spec,
        max_ticks=getattr(model, "decode_max_ticks", None),
        decode_arena_fn=(
            model.decode_arena_step_fn()
            if getattr(model, "supports_decode_kernel", False) else None))

  def get_feature_specification(self) -> specs_lib.SpecStruct:
    self.assert_is_loaded()
    return self._model.preprocessor.get_in_feature_specification(
        modes_lib.PREDICT)

  def get_label_specification(self) -> specs_lib.SpecStruct:
    self.assert_is_loaded()
    return specs_lib.flatten_spec_structure(
        self._model.get_label_specification(modes_lib.PREDICT))

  @property
  def global_step(self) -> int:
    return self._global_step

  @property
  def model(self):
    """The model this predictor serves (None before the first restore of
    a bundle-built predictor): what an on-device policy needs beside
    `serving_bundle().get_state()`."""
    return self._model

  def predict(self, features) -> Dict[str, np.ndarray]:
    self.assert_is_loaded()
    # graftscope serving latency: the np.asarray fetch inside the timed
    # window IS the barrier, so the
    # histogram measures true end-to-end latency, not dispatch.
    start = time.perf_counter()
    with obs_trace.span("serve/predict", cat="serve"):
      outputs = self._predict_fn(features)
      result = {k: np.asarray(v)
                for k, v in dict(outputs.items()).items()}
    self._observe_latency((time.perf_counter() - start) * 1e3)
    return result

  def predict_preprocessed(self, features) -> Dict[str, np.ndarray]:
    """Predict on MODEL-layout (already-preprocessed) features."""
    self.assert_is_loaded()
    start = time.perf_counter()
    with obs_trace.span("serve/predict_preprocessed", cat="serve"):
      outputs = self._predict_preprocessed_fn(features)
      result = {k: np.asarray(v)
                for k, v in dict(outputs.items()).items()}
    self._observe_latency((time.perf_counter() - start) * 1e3)
    return result

  def _observe_latency(self, elapsed_ms: float) -> None:
    obs_metrics.histogram("serve/predict_ms").record(elapsed_ms)
    obs_metrics.counter("serve/predictions").inc()
    obs_sentinel.observe_serving_latency(elapsed_ms, self._latency_slo_ms)

  def place_on_device(self, device) -> None:
    """Commits the predictor's state to `device` — the graftserve fleet's
    replica pinning seam (`serving/fleet.py` + `parallel.mesh.
    replica_device_groups`): dispatches follow committed arguments, so a
    predictor placed on replica N's lead device executes there, and the
    engine's warmup-compiled executables are built for that placement.
    The pin is sticky: both restore() implementations re-place freshly
    restored state onto this device, so a rollout hot-swap never
    migrates a replica off its device group."""
    self.assert_is_loaded()
    self._device = device
    self._state = jax.device_put(self._state, device)


@config.configurable
class CheckpointPredictor(_JaxPredictorBase):
  """Serves directly from training checkpoints (reference
  checkpoint_predictor.py:37-215): rebuilds the predict path from the
  model object and polls model_dir for new steps."""

  def __init__(self, model=None, model_dir: Optional[str] = None,
               timeout_secs: float = 0.0,
               latency_slo_ms: Optional[float] = None,
               executable_cache_dir: Optional[str] = None):
    super().__init__(latency_slo_ms=latency_slo_ms,
                     executable_cache_dir=executable_cache_dir)
    if model is None or model_dir is None:
      raise ValueError("model and model_dir are required.")
    self._model = model
    self._checkpoint_dir = os.path.join(model_dir, "checkpoints") \
        if os.path.isdir(os.path.join(model_dir, "checkpoints")) \
        or not os.path.isdir(model_dir) else model_dir
    self._timeout_secs = timeout_secs

  def init_randomly(self) -> None:
    feature_spec = self._model.preprocessor.get_out_feature_specification(
        modes_lib.PREDICT)
    sample = specs_lib.make_random_numpy(feature_spec, batch_size=1, seed=0)
    self._state, _ = ts.create_train_state(
        self._model, jax.random.PRNGKey(0), sample)
    self._global_step = 0
    self._build_predict()

  def restore(self) -> bool:
    from tensor2robot_tpu.utils import retry as retry_lib

    # Jittered appearance poll: N replica predictors waiting on one
    # model_dir de-synchronize instead of stat-ing in lockstep.
    deadline = time.time() + self._timeout_secs
    step = checkpoints_lib.latest_step(self._checkpoint_dir)
    while step is None and time.time() < deadline:
      time.sleep(retry_lib.jittered_s(1.0, jitter=0.25))
      step = checkpoints_lib.latest_step(self._checkpoint_dir)
    if step is None:
      return False
    if self._state is None:
      self.init_randomly()
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self._state)
    with checkpoints_lib.CheckpointManager(self._checkpoint_dir) as manager:
      # step=None: the graftguard verified-fallback walk — a corrupt
      # newest step (torn write racing the poll, bit rot) is
      # quarantined and the newest VERIFIED step serves instead of the
      # hot-swap raising out of a live rollout().
      self._state = manager.restore(abstract_state=abstract)
      step = manager.last_restored_step
    if self._device is not None:
      # Replica pin survives a hot-swap: the restored tree lands on the
      # default device otherwise, silently migrating this replica's
      # dispatches off its carved-out device group mid-rollout.
      self._state = jax.device_put(self._state, self._device)
    self._global_step = step
    self._build_predict()
    return True


def _valid_export_dirs(export_root: str) -> List[str]:
  """Newest-last list of complete export bundles (reference dir polling +
  validation, exported_savedmodel_predictor.py:314-353)."""
  if not os.path.isdir(export_root):
    return []
  out = []
  for path in sorted(glob.glob(os.path.join(export_root, "*"))):
    name = os.path.basename(path)
    if not name.isdigit():
      continue
    has_assets = (
        os.path.isfile(os.path.join(path, specs_lib.ASSET_FILENAME))
        # Reference-era bundles carry only the text-proto sidecar
        # (load_assets transparently falls back to it).
        or os.path.isfile(os.path.join(path, "assets.extra",
                                       specs_lib.PBTXT_ASSET_FILENAME)))
    if (has_assets
        and os.path.isfile(os.path.join(path, export_lib.SIGNATURE_FILENAME))
        and os.path.isdir(os.path.join(path, export_lib.PARAMS_DIRNAME))):
      out.append(path)
  return out


def _model_from_bundle(path: str):
  """Reconstructs the model object from a bundle's signature + config."""
  with open(os.path.join(path, export_lib.SIGNATURE_FILENAME)) as f:
    signature = json.load(f)
  config_path = os.path.join(path, "operative_config.gin")
  if os.path.isfile(config_path):
    config.parse_config_file(config_path)
  module_name, _, class_name = signature["model_class"].rpartition(".")
  module = importlib.import_module(module_name)
  cls = module
  for part in class_name.split("."):
    cls = getattr(cls, part)
  return cls()


@config.configurable
class ExportedModelPredictor(_JaxPredictorBase):
  """Serves from export bundles (reference
  exported_savedmodel_predictor.py:53-359): polls for the newest valid
  timestamped dir, loads assets + params, optional async restore."""

  def __init__(self, export_dir: Optional[str] = None, model=None,
               timeout_secs: float = 0.0,
               latency_slo_ms: Optional[float] = None,
               executable_cache_dir: Optional[str] = None):
    super().__init__(latency_slo_ms=latency_slo_ms,
                     executable_cache_dir=executable_cache_dir)
    if export_dir is None:
      raise ValueError("export_dir is required.")
    self._export_dir = export_dir
    self._model = model
    self._timeout_secs = timeout_secs
    self._loaded_path: Optional[str] = None
    self._restore_thread: Optional[threading.Thread] = None
    # Lets close() interrupt a restore() polling for exports (the wait
    # can be minutes of timeout_secs) instead of blocking the join.
    self._stop_restore = threading.Event()

  def restore(self) -> bool:
    deadline = time.time() + self._timeout_secs
    dirs = _valid_export_dirs(self._export_dir)
    while (not dirs and time.time() < deadline
           and not self._stop_restore.is_set()):
      self._stop_restore.wait(timeout=1.0)
      dirs = _valid_export_dirs(self._export_dir)
    if not dirs:
      return False
    newest = dirs[-1]
    if newest == self._loaded_path:
      return True
    assets = specs_lib.load_assets(
        os.path.join(newest, specs_lib.ASSET_FILENAME))
    if self._model is None:
      self._model = _model_from_bundle(newest)
    # Restore eval-time variables and wrap them in a TrainState shell.
    with ocp.StandardCheckpointer() as checkpointer:
      variables = checkpointer.restore(
          os.path.join(newest, export_lib.PARAMS_DIRNAME))
    self._state = ts.TrainState(
        step=np.asarray(assets.global_step or 0),
        params=variables["params"], opt_state=None,
        mutable_state=variables.get("mutable") or {},
        ema_params=None, rng=jax.random.PRNGKey(0))
    if self._device is not None:
      # Replica pin survives a bundle swap (the CheckpointPredictor
      # restore rule: restored trees land on the default device
      # otherwise, migrating this replica off its device group).
      self._state = jax.device_put(self._state, self._device)
    self._global_step = int(assets.global_step or 0)
    self._loaded_path = newest
    self._build_predict()
    return True

  def restore_async(self) -> threading.Thread:
    """Background restore (reference async restore thread,
    exported_savedmodel_predictor.py:152-159)."""
    # Backstop exemption: a one-shot restore worker with no loop —
    # it terminates by itself after one bundle load, the handle is
    # returned to the caller, and close() joins it.
    thread = threading.Thread(
        target=self.restore,
        daemon=True)  # graftlint: disable=thread-stage-missing-backstop
    thread.start()
    self._restore_thread = thread
    return thread

  def close(self) -> None:
    """Stops and joins an in-flight `restore_async` worker — the
    export-dir poll wakes on the stop event (so close() never waits
    out `timeout_secs`), and an actual bundle load touches the backend
    (device_put of restored params), so it is joined rather than
    abandoned mid-flight at interpreter shutdown (the graftlint
    `thread-stage-missing-close` discipline)."""
    self._stop_restore.set()
    if self._restore_thread is not None and self._restore_thread.is_alive():
      self._restore_thread.join()
    self._stop_restore.clear()  # a later explicit restore() still works
    super().close()

  @property
  def loaded_path(self) -> Optional[str]:
    return self._loaded_path


@config.configurable
class EnsemblePredictor(AbstractPredictor):
  """Mean aggregation over a random subsample of member predictors
  (reference ensemble_exported_savedmodel_predictor.py:97-122)."""

  def __init__(self, predictors: Optional[Sequence[AbstractPredictor]] = None,
               num_samples: Optional[int] = None, seed: int = 0):
    if not predictors:
      raise ValueError("predictors are required.")
    self._predictors = list(predictors)
    self._num_samples = num_samples or len(self._predictors)
    self._rng = np.random.RandomState(seed)

  def restore(self) -> bool:
    return all(p.restore() for p in self._predictors)

  def get_feature_specification(self) -> specs_lib.SpecStruct:
    return self._predictors[0].get_feature_specification()

  @property
  def global_step(self) -> int:
    return min(p.global_step for p in self._predictors)

  def predict(self, features) -> Dict[str, np.ndarray]:
    chosen = self._rng.choice(len(self._predictors), self._num_samples,
                              replace=False)
    outputs = [self._predictors[i].predict(features) for i in chosen]
    keys = outputs[0].keys()
    return {k: np.mean([o[k] for o in outputs], axis=0) for k in keys}

  def close(self) -> None:
    for p in self._predictors:
      p.close()
