"""Test configuration: force an 8-device virtual CPU mesh before JAX import.

Mirrors the reference's "TPUEstimator-on-CPU" test strategy
(/root/reference/utils/train_eval.py:136,149-151): all sharding / pjit tests
run against a virtual 8-device CPU topology so they validate multi-chip
sharding without hardware.
"""

import atexit
import os
import shutil
import tempfile

# Hard-override: tests run on the CPU backend, whatever the environment
# says.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
# Hard-override too: the compile cache is placed from outside
# (obs/excache.py), one fresh directory per test process (each xdist
# worker imports this file), so a run neither loads what an earlier run
# compiled nor writes into the checkout's .graftcache, and no two
# workers write one file. Processes a test starts inherit it.
_cache_dir = tempfile.mkdtemp(prefix="t2r_test_jaxcache_")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

import jax  # noqa: E402  (import after env setup)
import pytest  # noqa: E402

# A rule for test loops on the virtual mesh: keep ONE multi-device step in
# flight (`jax.block_until_ready` on something the step returns, every
# iteration). XLA's CPU client has exactly one thread per virtual device;
# with several 8-way collectives queued on a loaded host (six test
# workers) 7 of 8 threads reach a rendezvous, XLA aborts the whole worker
# process after 40 s, and every test still queued in it is lost. Measured
# with six copies of one such loop at once: 2 of 6 abort without the
# barrier, 0 of 16 with it. The chip has no such pool; this is about the
# CPU stand-in only.


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs[:8]
