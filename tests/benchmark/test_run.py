"""`run.py` end to end on the CPU stand-in: the last line's keys, and the
refusal without a TPU."""

import os
import subprocess
import sys

import pytest

from benchmarks import run as run_lib
from benchmarks.harness import manifest
from tests.benchmark.rehearse import CELLS, rehearse

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_last_line(capsys, cell):
  result, err = rehearse(capsys, cell, trace=1)
  assert RESULT_KEYS <= set(result)
  assert list(result)[-1] == "checks"
  assert result["device"]["platform"] == "cpu"
  assert result["metrics"] == {}          # counts only, no device number
  assert result["counts"]["steps"] == result["attempted"] > 0
  assert result["failed"] == 0
  # every number compared is printed beside its limit, on both streams
  for name, check in result["checks"].items():
    assert f"check {name}:" in err
    assert set(check) == {"value", "limit"}
  assert err.strip().splitlines()[-1].startswith("correct:")
  assert result["checks"]["initial_weights"]["value"] == 0.0


def test_same_seed_gives_the_same_inputs():
  import numpy as np

  from benchmarks.harness import traffic

  specs = {"features/x": ((3,), np.float32), "labels/y": ((2, 2), np.uint8)}
  a = traffic.make_pool(specs, 4, 2, 2**31 + 5)
  b = traffic.make_pool(specs, 4, 2, 2**31 + 5)
  c = traffic.make_pool(specs, 4, 2, 2**31 + 6)
  for x, y, z in zip(a, b, c):
    assert all(np.array_equal(x[k], y[k]) for k in x)
    assert not np.array_equal(x["features/x"], z["features/x"])
    assert x["features/x"].shape == (4, 3) == z["features/x"].shape
  rows = a[0]["features/x"]
  assert len({tuple(r) for r in rows}) == len(rows)  # rows all differ


def test_stream_ends_so_that_the_window_lasts_its_seconds():
  from benchmarks.harness.traffic import WindowClock

  clock = WindowClock(seconds=10.0, warmup_steps=5)
  assert not clock.stream_done(now=100.0)          # not open yet
  clock.open(100.0)
  clock.handed_out = 45                            # the host ran 40 ahead
  assert not clock.stream_done(now=100.5)          # no pace known yet
  clock.note_finished(15, 102.0)                   # 10 steps in 2 s: 0.2 s
  assert not clock.stream_done(now=102.0)          # 2 + 30 x 0.2 = 8 s
  clock.handed_out = 56
  assert clock.stream_done(now=102.0)              # 2 + 41 x 0.2 = 10.2 s
  clock.note_finished(14, 999.0)                   # never goes backwards
  assert clock.finished_step == 15
  late = WindowClock(seconds=10.0, warmup_steps=5)
  late.open(0.0)
  assert late.stream_done(now=10.0)                # a stalled device: time
  rehearsal = WindowClock(seconds=10.0, warmup_steps=5, window_steps=3)
  rehearsal.handed_out = 8
  assert rehearsal.stream_done()


def test_without_a_tpu_it_fails_and_prints_no_result():
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  done = subprocess.run(
      [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
       "--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace",
       "0"], env=env, capture_output=True, text=True, timeout=300)
  assert done.returncode != 0
  assert "TPU" in done.stderr
  assert not any(line.startswith("{") for line in done.stdout.splitlines())


def test_without_the_program_it_fails_and_prints_no_result(tmp_path):
  """A directory that holds only BENCHMARK.json and the files under `paths`."""
  import shutil

  benchmark = manifest.load_benchmark()
  shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
  for path in benchmark["paths"]:
    shutil.copytree(os.path.join(manifest.ROOT, path), tmp_path / path,
                    ignore=shutil.ignore_patterns("__pycache__"))
  done = subprocess.run(
      [sys.executable, str(tmp_path / benchmark["command"][1]), "--workload",
       CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
      env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
      text=True, timeout=300, cwd=tmp_path)
  assert done.returncode != 0
  assert done.stdout.strip() == ""


def test_rehearsal_refuses_to_stand_in_for_an_unknown_cell():
  with pytest.raises(manifest.ManifestError):
    run_lib.main(["--workload", "nope", "--rehearse"])
