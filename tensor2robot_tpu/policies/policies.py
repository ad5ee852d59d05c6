"""Policies: predictor-backed action selection for robot control loops.

Reference surface (/root/reference/policies/policies.py:33-364):
* `Policy` ABC — SelectAction / reset / restore + `sample_action` adapter;
* `CEMPolicy` — cross-entropy argmax over a critic's q_predicted;
* `LSTMCEMPolicy` — CEM with recurrent hidden-state threading;
* `RegressionPolicy` / `SequentialRegressionPolicy` — direct regression
  outputs (one-shot or per-timestep row);
* exploration wrappers: Ornstein-Uhlenbeck noise, scheduled exploration,
  per-episode explore/greedy switching.
"""

from __future__ import annotations

import abc
from typing import Any, Dict, Mapping, Optional, Sequence

import numpy as np

from tensor2robot_tpu.obs import metrics as obs_metrics
from tensor2robot_tpu.obs import trace as obs_trace
from tensor2robot_tpu.ops import cem as cem_lib
from tensor2robot_tpu.utils import config

__all__ = ["Policy", "CEMPolicy", "LSTMCEMPolicy", "RegressionPolicy",
           "SequentialRegressionPolicy", "SessionRegressionPolicy",
           "OUExploreRegressionPolicy",
           "ScheduledExplorationRegressionPolicy", "PerEpisodeSwitchPolicy",
           "OUNoiseProcess", "boundary_schedule_value"]


class Policy(abc.ABC):
  """Action-selection contract for env loops."""

  def __init__(self, predictor=None):
    self._predictor = predictor

  @property
  def predictor(self):
    return self._predictor

  @abc.abstractmethod
  def select_action(self, obs: Mapping[str, Any], explore_prob: float = 0.0
                    ) -> np.ndarray:
    ...

  # Reference naming (SelectAction) kept as an alias for drop-in use.
  def SelectAction(self, obs, env=None, timestep: int = 0) -> np.ndarray:  # noqa: N802
    return self.select_action(obs)

  def sample_action(self, obs, explore_prob: float = 0.0) -> np.ndarray:
    """Adapter used by collect loops (reference :95-102).

    graftscope instruments THIS adapter (not select_action, which
    subclasses override) so every env loop gets an action-latency
    histogram — the actor-side control-rate number — for free."""
    with obs_trace.span("policy/select_action", cat="serve"), \
        obs_metrics.histogram("policy/select_action_ms").time_ms():
      return self.select_action(obs, explore_prob=explore_prob)

  def reset(self) -> None:
    """Per-episode state reset."""

  def abort_episode(self) -> None:
    """Mid-episode teardown: release any serving-side episode state
    WITHOUT touching the predictor. Called by `envs.run_env` when the
    env (or the policy itself) raises mid-episode — a session-backed
    policy must close its server-side session slot here (a leaked slot
    per crashed episode is denial-of-service under shed admission);
    stateless policies have nothing to do."""

  def restore(self) -> bool:
    if self._predictor is not None:
      ok = self._predictor.restore()
      # graftserve seam: a serving-runtime predictor (BucketedEngine /
      # MicroBatcher) exposes `warmup()` — compiling its shape-bucket
      # executables HERE, before the robot loop starts, instead of on
      # the first action's critical path (a cold compile is seconds
      # the robot would spend frozen mid-episode).
      warm = getattr(self._predictor, "warmup", None)
      if ok and warm is not None:
        warm()
      return ok
    return True

  @property
  def global_step(self) -> int:
    if self._predictor is not None:
      return self._predictor.global_step
    return -1

  def close(self) -> None:
    if self._predictor is not None:
      self._predictor.close()


@config.configurable
class CEMPolicy(Policy):
  """argmax_a Q(s, a) via CEM over the critic predictor (reference
  :106-184; defaults 64 samples x 3 iters, 10 elites)."""

  def __init__(self, predictor=None, action_size: int = None,
               cem_samples: int = 64, cem_iterations: int = 3,
               cem_elites: int = 10,
               action_low: float = -1.0, action_high: float = 1.0,
               q_key: str = "q_predicted", seed: Optional[int] = None):
    super().__init__(predictor)
    if action_size is None:
      raise ValueError("action_size is required.")
    self._action_size = action_size
    self._cem = cem_lib.CrossEntropyMethod(
        num_samples=cem_samples, num_iterations=cem_iterations,
        num_elites=cem_elites, seed=seed)
    self._low = np.full(action_size, action_low, np.float32)
    self._high = np.full(action_size, action_high, np.float32)
    self._q_key = q_key
    self._num_samples = cem_samples

  def _objective(self, obs):
    def objective_fn(actions: np.ndarray) -> np.ndarray:
      features = {("state/" + k): np.repeat(
          np.asarray(v)[None], actions.shape[0], axis=0)
          for k, v in dict(obs).items()}
      features["action/action"] = actions
      return self._predictor.predict(features)[self._q_key].reshape(-1)

    return objective_fn

  def select_action(self, obs, explore_prob: float = 0.0) -> np.ndarray:
    if explore_prob > 0.0 and np.random.rand() < explore_prob:
      self.last_q_value = None  # no Q for random actions (keeps
      # actor-side Q summaries unbiased by stale greedy scores)
      return np.random.uniform(self._low, self._high).astype(np.float32)
    mean = (self._low + self._high) / 2.0
    stddev = (self._high - self._low) / 2.0
    action, score = self._cem.optimize(self._objective(obs), mean, stddev,
                                       low=self._low, high=self._high)
    # Exposed for actor-side Q-value summaries (reference run_env logs
    # the served Q alongside rewards).
    self.last_q_value = score
    return action


@config.configurable
class LSTMCEMPolicy(CEMPolicy):
  """CEM policy threading recurrent hidden state between steps (reference
  :188-218): the predictor returns `hidden_state`, fed back next call."""

  def __init__(self, hidden_state_key: str = "hidden_state", **kwargs):
    super().__init__(**kwargs)
    self._hidden_state_key = hidden_state_key
    self._hidden_state = None

  def reset(self) -> None:
    self._hidden_state = None

  def _objective(self, obs):
    base = super()._objective(obs)
    hidden = self._hidden_state
    key = self._hidden_state_key

    def objective_fn(actions):
      features = {("state/" + k): np.repeat(
          np.asarray(v)[None], actions.shape[0], axis=0)
          for k, v in dict(obs).items()}
      features["action/action"] = actions
      if hidden is not None:
        features["state/" + key] = np.repeat(hidden, actions.shape[0],
                                             axis=0)
      outputs = self._predictor.predict(features)
      self._last_outputs = outputs
      return outputs[self._q_key].reshape(-1)

    return objective_fn

  def select_action(self, obs, explore_prob: float = 0.0) -> np.ndarray:
    action = super().select_action(obs, explore_prob=explore_prob)
    outputs = getattr(self, "_last_outputs", None)
    if outputs is not None and self._hidden_state_key in outputs:
      self._hidden_state = outputs[self._hidden_state_key][:1]
    return action


@config.configurable
class RegressionPolicy(Policy):
  """Directly outputs the regression head (reference :222-236)."""

  def __init__(self, predictor=None, action_key: str = "inference_output"):
    super().__init__(predictor)
    self._action_key = action_key

  def _features(self, obs) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v)[None] for k, v in dict(obs).items()}

  def select_action(self, obs, explore_prob: float = 0.0) -> np.ndarray:
    outputs = self._predictor.predict(self._features(obs))
    return np.asarray(outputs[self._action_key])[0]


@config.configurable
class SequentialRegressionPolicy(RegressionPolicy):
  """Regression over episode-shaped outputs: select the current timestep's
  row (reference SequentialRegressionPolicy)."""

  def __init__(self, **kwargs):
    super().__init__(**kwargs)
    self._timestep = 0

  def reset(self) -> None:
    self._timestep = 0

  def select_action(self, obs, explore_prob: float = 0.0) -> np.ndarray:
    outputs = self._predictor.predict(self._features(obs))
    action_all = np.asarray(outputs[self._action_key])[0]
    if action_all.ndim >= 2:
      idx = min(self._timestep, action_all.shape[0] - 1)
      action = action_all[idx]
    else:
      action = action_all
    self._timestep += 1
    return action


@config.configurable
class SessionRegressionPolicy(Policy):
  """Regression policy riding a graftserve SESSION (ISSUE 11): each
  episode is one server-side session whose decode cache lives on device
  between control ticks — every `select_action` costs one O(1) decode
  tick instead of the `SequentialRegressionPolicy` full-prefix re-run.

  `predictor` is anything with the session surface (`open` / `step` /
  `close_session` — a `serving.SessionEngine` or `SessionBatcher`).
  `reset()` closes the previous episode's session and opens the next, so
  `envs.run_env` episodes ride sessions with no loop changes; `close()`
  also closes a live session (the engine waits out an
  in-flight dispatch before freeing the slot). An eviction under slot
  pressure surfaces as `SessionEvictedError` from `select_action` — the
  episode must restart; the policy drops its session id so a later
  `reset()` starts clean."""

  def __init__(self, predictor=None, action_key: str = "inference_output"):
    super().__init__(predictor)
    self._action_key = action_key
    self._session_id: Optional[int] = None

  @property
  def session_id(self) -> Optional[int]:
    return self._session_id

  def reset(self) -> None:
    self._close_session()
    self._session_id = self._predictor.open()

  def abort_episode(self) -> None:
    """Mid-episode teardown (env crashed under `run_env`): the episode
    will not resume, so the server-side slot must be freed NOW — the
    next `reset()` starts clean either way, but without this close the
    slot leaks until LRU pressure or engine close (one leaked slot per
    crashed episode starves admission='shed' engines)."""
    self._close_session()

  def _close_session(self) -> None:
    if self._session_id is None:
      return
    sid, self._session_id = self._session_id, None
    try:
      self._predictor.close_session(sid)
    except Exception:  # noqa: BLE001 - already evicted/closed server-side
      pass

  def select_action(self, obs, explore_prob: float = 0.0) -> np.ndarray:
    if self._session_id is None:
      self.reset()
    features = {k: np.asarray(v) for k, v in dict(obs).items()}
    try:
      outputs = self._predictor.step(self._session_id, features)
    except Exception as e:
      # Three failure classes, three dispositions. (1) The slot is
      # GONE server-side (evicted / closed / unknown): drop the id —
      # holding it would mis-route the NEXT episode's ticks. (2) The
      # episode outran the decode horizon: the session is alive and
      # still holds its slot, so CLOSE it (a leaked slot per finished
      # episode is denial-of-service under admission='shed'). (3) Any
      # transient error (queue-full shed, a concurrent-tick rejection,
      # a backend hiccup): KEEP the id — the caller can retry this
      # tick, whereas dropping it would silently reset() mid-episode
      # onto an empty decode cache (plausible-looking, wrong actions)
      # and leak the old slot.
      from tensor2robot_tpu.serving import session as session_lib

      if isinstance(e, session_lib.SessionHorizonError):
        self._close_session()
      elif isinstance(e, (session_lib.SessionEvictedError,
                          session_lib.SessionClosedError,
                          session_lib.UnknownSessionError)):
        self._session_id = None
      raise
    return np.asarray(outputs[self._action_key])

  def close(self) -> None:
    self._close_session()
    super().close()


@config.configurable
class OUNoiseProcess:
  """Ornstein-Uhlenbeck noise state machine, shared by the exploration
  policies here and in meta_learning.meta_policies."""

  def __init__(self, action_size: int, theta: float = 0.15,
               sigma: float = 0.2, seed: Optional[int] = None):
    self._theta = theta
    self._sigma = sigma
    self._action_size = action_size
    self._rng = np.random.RandomState(seed)
    self._noise = np.zeros(action_size, np.float32)

  def reset(self) -> None:
    self._noise = np.zeros(self._action_size, np.float32)

  def sample(self) -> np.ndarray:
    self._noise += (-self._theta * self._noise
                    + self._sigma * self._rng.randn(self._action_size))
    return self._noise


def boundary_schedule_value(boundaries: Sequence[int],
                            values: Sequence[float], step: int) -> float:
  """Step-boundary schedule lookup (last boundary <= step wins)."""
  step = max(step, 0)
  value = values[0]
  for boundary, v in zip(boundaries, values):
    if step >= boundary:
      value = v
  return value


class OUExploreRegressionPolicy(RegressionPolicy):
  """Ornstein-Uhlenbeck exploration noise on top of regression actions
  (reference :258-291)."""

  def __init__(self, theta: float = 0.15, sigma: float = 0.2,
               action_size: int = None, seed: Optional[int] = None,
               **kwargs):
    super().__init__(**kwargs)
    if action_size is None:
      raise ValueError("action_size is required.")
    self._ou = OUNoiseProcess(action_size, theta=theta, sigma=sigma,
                              seed=seed)

  def reset(self) -> None:
    self._ou.reset()

  def select_action(self, obs, explore_prob: float = 0.0) -> np.ndarray:
    action = super().select_action(obs)
    return action + explore_prob * self._ou.sample()


@config.configurable
class ScheduledExplorationRegressionPolicy(OUExploreRegressionPolicy):
  """Exploration magnitude annealed by the policy's global step (reference
  :295-320)."""

  def __init__(self, schedule_boundaries: Sequence[int] = (0,),
               schedule_values: Sequence[float] = (1.0,), **kwargs):
    super().__init__(**kwargs)
    if len(schedule_boundaries) != len(schedule_values):
      raise ValueError("boundaries and values must align.")
    self._boundaries = list(schedule_boundaries)
    self._values = list(schedule_values)

  def _scheduled_value(self) -> float:
    return boundary_schedule_value(self._boundaries, self._values,
                                   self.global_step)

  def select_action(self, obs, explore_prob: float = 0.0) -> np.ndarray:
    return super().select_action(obs,
                                 explore_prob=self._scheduled_value())


@config.configurable
class PerEpisodeSwitchPolicy(Policy):
  """Chooses an explore or greedy sub-policy once per episode (reference
  :324-364)."""

  def __init__(self, explore_policy: Policy = None,
               greedy_policy: Policy = None,
               explore_prob: float = 0.1, seed: Optional[int] = None):
    super().__init__()
    if explore_policy is None or greedy_policy is None:
      raise ValueError("Both sub-policies are required.")
    self._explore_policy = explore_policy
    self._greedy_policy = greedy_policy
    self._explore_prob = explore_prob
    self._rng = np.random.RandomState(seed)
    self._active = greedy_policy

  def reset(self) -> None:
    self._active = (self._explore_policy
                    if self._rng.rand() < self._explore_prob
                    else self._greedy_policy)
    self._active.reset()

  def restore(self) -> bool:
    return self._explore_policy.restore() and self._greedy_policy.restore()

  @property
  def global_step(self) -> int:
    return self._greedy_policy.global_step

  def select_action(self, obs, explore_prob: float = 0.0) -> np.ndarray:
    return self._active.select_action(obs, explore_prob=explore_prob)
