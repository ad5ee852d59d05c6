"""The control of "How `correct` is decided", at a size a test run can hold:
the plain reference put in the program's place and computed in the nearest
precision below the configuration's (fp8 for bf16) has to come out as not
correct under the cell's own limits, and so has the planted fault. The chip
readings at the cells' own sizes are in PERF.md; `benchmarks/calibrate.py`
takes them."""

import json

import pytest

from benchmarks import calibrate
from benchmarks.harness import compare
from benchmarks.harness import manifest
from benchmarks.harness import refmath

CELLS = [w["name"] for w in manifest.load_benchmark()["workloads"]]


@pytest.fixture(scope="module", params=CELLS)
def readings(request):
  import contextlib
  import io

  out = io.StringIO()
  with contextlib.redirect_stdout(out):
    code = calibrate.main(["--workload", request.param, "--seeds", "1",
                           "--controls", "1", "--rehearse",
                           "--first-seed", "2147483777"])
  assert code == 0
  return manifest.Cell(request.param), json.loads(out.getvalue())


def _as_numbers(values):
  return {k: {"value": v} for k, v in values.items()}


def test_control_is_not_correct_under_the_cells_limits(readings):
  cell, out = readings
  assert cell.config["control_precision"] == "fp8"
  assert cell.config["precision"] == "bfloat16"
  (control,) = out["control"].values()
  correct, checks = compare.decide(_as_numbers(control), cell.limits)
  assert not correct, checks


def test_half_batch_fault_is_not_correct_under_the_cells_limits(readings):
  cell, out = readings
  (fault,) = out["half_batch"].values()
  correct, checks = compare.decide(_as_numbers(fault), cell.limits)
  assert not correct, checks


def test_program_is_correct_under_the_cells_limits(readings):
  cell, out = readings
  (program,) = out["program"].values()
  correct, checks = compare.decide(_as_numbers(program), cell.limits)
  assert correct, checks
  assert program["initial_weights"] == 0.0


def test_control_reads_at_least_three_times_the_program(readings):
  _, out = readings
  (program,), (control,) = out["program"].values(), out["control"].values()
  assert any(control[n] >= 3 * program[n] > 0 for n in program
             if n != "initial_weights"), (program, control)


def test_quantizers():
  import jax.numpy as jnp
  import numpy as np

  x = jnp.asarray(np.linspace(-3, 3, 1001), jnp.float32)
  assert np.array_equal(refmath.quantizer("float32")(x), x)
  bf16 = np.abs(np.asarray(refmath.quantizer("bfloat16")(x)) - x).max()
  fp8 = np.abs(np.asarray(refmath.quantizer("fp8")(x)) - x).max()
  assert 0 < bf16 < 2 ** -7 < fp8 < 2 ** -2
  with pytest.raises(ValueError):
    refmath.quantizer("int3")
