"""Tests for utils/backend.py: CPU pinning, the TPU requirement, timers.

These run inside the conftest-pinned CPU process, so pin_cpu/assert here are
exercising idempotent paths; the env-merge logic is tested directly on
os.environ copies via monkeypatching.
"""

import os
import subprocess
import sys

import pytest

from tensor2robot_tpu.utils import backend

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_pin_cpu_sets_env_and_config(monkeypatch):
  monkeypatch.setenv("JAX_PLATFORMS", "tpu")
  monkeypatch.setenv("XLA_FLAGS", "")
  backend.pin_cpu(n_devices=8)
  assert os.environ["JAX_PLATFORMS"] == "cpu"
  assert "--xla_force_host_platform_device_count=8" in os.environ["XLA_FLAGS"]


def test_pin_cpu_replaces_existing_device_count(monkeypatch):
  monkeypatch.setenv(
      "XLA_FLAGS", "--foo=1 --xla_force_host_platform_device_count=2 --bar=2")
  backend.pin_cpu(n_devices=8)
  flags = os.environ["XLA_FLAGS"]
  assert "--xla_force_host_platform_device_count=8" in flags
  assert "device_count=2" not in flags
  assert "--foo=1" in flags and "--bar=2" in flags


def test_pin_cpu_preserves_other_flags(monkeypatch):
  monkeypatch.setenv("XLA_FLAGS", "--some_flag=true")
  backend.pin_cpu(n_devices=4)
  assert "--some_flag=true" in os.environ["XLA_FLAGS"]
  assert "--xla_force_host_platform_device_count=4" in os.environ["XLA_FLAGS"]


def test_pin_cpu_raises_when_another_backend_is_already_up(monkeypatch):
  """A pin that comes too late is an error: the caller must not go on
  'on CPU' over whatever backend is live."""
  import jax

  class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"

  jax.devices()  # the backend is up
  monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
  with pytest.raises(RuntimeError, match="not CPU"):
    backend.pin_cpu()


def test_pin_cpu_before_backend_init_pins_a_fresh_process():
  code = ("from tensor2robot_tpu.utils import backend\n"
          "backend.pin_cpu(n_devices=2)\n"
          "import jax\n"
          "assert jax.devices()[0].platform == 'cpu'\n"
          "assert len(jax.devices()) == 2\n"
          "backend.pin_cpu()  # idempotent once the CPU backend is up\n"
          "print('PINNED_OK')\n")
  env = {**os.environ, "PYTHONPATH": REPO_ROOT}
  env.pop("XLA_FLAGS", None)
  env.pop("JAX_PLATFORMS", None)
  done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                        text=True, timeout=300, cwd=REPO_ROOT, env=env)
  assert done.returncode == 0 and "PINNED_OK" in done.stdout, done.stderr


def test_require_tpu_refuses_a_cpu_process():
  """The measurement scripts' first call: no chip, no number."""
  with pytest.raises(RuntimeError, match="needs a TPU"):
    backend.require_tpu()


def test_require_tpu_returns_the_device(monkeypatch):
  import jax

  class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"

  monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
  assert backend.require_tpu().device_kind == "TPU v5 lite"


def test_assert_cpu_backend_passes_here():
  # conftest pinned this process to CPU, so the live backend is CPU.
  backend.assert_cpu_backend()


def test_time_train_steps_runs_warmup_plus_iters_with_barriers():
  """The shared timing helper executes warmup+iters steps and fetches a
  param leaf as the barrier (the discipline every bench/tuning script
  must share)."""
  import numpy as np

  calls = []

  class _State:
    params = {"w": np.zeros(3), "b": np.zeros(1)}

  def step(state, features, labels):
    calls.append((features, labels))
    return state, {}

  sec, out = backend.time_train_steps(step, _State(), "f", "l",
                                      iters=4, warmup=2)
  assert len(calls) == 6
  assert calls[0] == ("f", "l")
  assert sec >= 0
  assert isinstance(out, _State)


def test_time_train_steps_halves_reports_steady_state_separately():
  """The split-halves timer must run exactly warmup+iters steps, split
  the timed window into two barrier-separated halves, and report the
  second (steady-state) half independently — the round-5 discipline
  that keeps one-time remote allocation effects out of the headline
  number. Semantic check: with a step whose first timed call is slow,
  the first-half rate must come out slower than the second half."""
  import time as _time

  import numpy as np

  calls = []

  class _State:
    params = {"w": np.zeros(3)}

  def step(state, features, labels):
    calls.append(1)
    if len(calls) == 3:  # first TIMED step (after warmup=2)
      _time.sleep(0.05)
    return state, {}

  h1, h2, out = backend.time_train_steps_halves(
      step, _State(), "f", "l", iters=6, warmup=2)
  assert len(calls) == 8
  assert h1 > h2 > 0
  assert isinstance(out, _State)


def test_time_train_steps_halves_single_iter_degrades_gracefully():
  import numpy as np

  class _State:
    params = {"w": np.zeros(1)}

  h1, h2, _ = backend.time_train_steps_halves(
      lambda s, f, l: (s, {}), _State(), "f", "l", iters=1, warmup=0)
  assert h1 >= 0 and h2 == h1


def test_state_barrier_fetches_smallest_param_leaf():
  import numpy as np

  class _State:
    params = {"big": np.arange(8.0), "small": np.array([7.0])}

  fetched = backend.state_barrier(_State())
  np.testing.assert_array_equal(fetched, [7.0])


def test_time_train_steps_halves_clamps_barrier_dominated_windows():
  """ADVICE round 5: when the estimated barrier cost swallows a half's
  window, the fallback must be max(residual, 0.2*window)/n — NOT the
  full window (which re-includes the whole barrier and reads high) —
  and out_flags must flag the record so autotune/sentinel treat the
  number as an upper bound."""
  import time as _time

  import numpy as np

  class _SlowLeaf:
    """Param leaf whose host fetch (the barrier) dominates the window."""
    size = 1
    shape = (1,)

    def __array__(self, *a, **kw):
      _time.sleep(0.03)
      return np.zeros(1)

  class _State:
    params = {"w": _SlowLeaf()}

  flags = {}
  h1, h2, _ = backend.time_train_steps_halves(
      lambda s, f, l: (s, {}), _State(), "f", "l", iters=4, warmup=0,
      out_flags=flags)
  assert flags.get("barrier_dominated") is True
  # The clamp: a near-instant step under a ~30 ms barrier must come out
  # far below the naive window/n fallback (which would be >= ~15 ms),
  # yet strictly positive (downstream divides by it).
  assert 0.0 < h1 < 0.015
  assert 0.0 < h2 < 0.015


def test_time_train_steps_halves_leaves_flags_unset_when_clean():
  import numpy as np

  class _State:
    params = {"w": np.zeros(3)}

  flags = {}
  def step(state, features, labels):
    import time as _time
    _time.sleep(0.005)
    return state, {}

  backend.time_train_steps_halves(step, _State(), "f", "l", iters=4,
                                  warmup=0, out_flags=flags)
  assert "barrier_dominated" not in flags
