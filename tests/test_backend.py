"""Tests for utils/backend.py: CPU pinning, the barriers, the v5e peaks.

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


def test_assert_cpu_backend_passes_here():
  # conftest pinned this process to CPU, so the live backend is CPU.
  backend.assert_cpu_backend()


def test_state_barrier_fetches_smallest_param_leaf():
  import numpy as np

  class _State:
    params = {"big": np.arange(8.0), "small": np.array([7.0])}

  fetched = backend.state_barrier(_State())
  np.testing.assert_array_equal(fetched, [7.0])


def test_sync_fetches_to_host_numpy():
  """`sync` is the barrier every timed window in the repo closes on: it
  returns the device value on the host, as numpy, so the computation
  that produced it has finished (and a device error has surfaced)."""
  import jax.numpy as jnp
  import numpy as np

  device_value = jnp.arange(6.0).reshape(2, 3) * 2.0
  fetched = backend.sync(device_value)
  assert type(fetched) is np.ndarray
  np.testing.assert_array_equal(fetched, np.arange(6.0).reshape(2, 3) * 2.0)


@pytest.mark.parametrize("program,benchmark", [
    ("V5E_PEAK_BF16_FLOPS", "bf16_flops_per_s"),
    ("V5E_PEAK_HBM_BW", "hbm_bytes_per_s")])
def test_program_peaks_match_the_benchmarks(program, benchmark):
  """`obs/xray.py` prices its roofline with this module's two peaks and
  the benchmark prices `step_mfu` with its own table: two copies that
  may not drift apart."""
  from benchmarks.harness import peaks

  assert getattr(backend, program) == peaks.PEAKS["TPU v5 lite"][benchmark]


def test_backend_exports_no_timer():
  """One way to time: a barrier and a clock, where the measurement is
  made. The module holds the barriers and no clock of its own."""
  import inspect

  public = {name for name, value in vars(backend).items()
            if not name.startswith("_") and inspect.isfunction(value)}
  assert public == {"pin_cpu", "assert_cpu_backend", "sync", "state_barrier",
                    "device_memory_stats"}
  assert "perf_counter" not in inspect.getsource(backend)
