"""The trainer's driver for a model whose parameters the comparison cannot
hold six times over in float64.

`drivers/trainer.py` hands `harness/compare.training_numbers` the program's
and the reference's parameters, first gradient and parameters after three
steps as whole host trees; it turns each into a float64 copy and keeps all of
them, and their two differences, until it returns: 88 bytes a parameter on
the host beside the float32 originals. The one-chip machine has 40 GiB, of
which the TPU runtime maps 13.8 GB as soon as JAX has seen the chip (my chip
run, PR 33), so a configuration of more than about 260 M parameters ends in
the machine's out-of-memory kill (626 M: 55 GB; PERF.md section 7).

This driver is `trainer.run` itself, every line of it, with one function in
the comparison's place: `training_numbers` below computes the same numbers
under the same names from the same definitions, one leaf at a time and a
leaf in pieces, so that no whole tree is ever held in float64 (and on a few
threads: one thread takes a minute over 626 M parameters), and it takes
leaves that are still on the device (a reference may leave its parameters
there). `compare.decide`
and the limits are the harness's own. tests/benchmark/test_hybrid_cell.py
holds the two `training_numbers` to equal results on the same trees.
"""

from __future__ import annotations

import types

import numpy as np

from benchmarks.drivers import trainer
from benchmarks.harness import compare


def leaves(tree, prefix=""):
  """(path, leaf) of nested mappings, in `compare.flatten`'s order."""
  for key in sorted(tree):
    value = tree[key]
    path = f"{prefix}/{key}" if prefix else str(key)
    if hasattr(value, "keys"):
      yield from leaves(value, path)
    else:
      yield path, value


CHUNK = 1 << 22   # elements of a leaf turned to float64 at a time
WORKERS = 8


def _leaf_numbers(program: dict, reference: dict):
  """One leaf of each of the six trees -> ((gradient norms), (change norms),
  largest initial gap), each pair (program, reference). The sums run in
  float64 over pieces of `CHUNK` elements, so nothing larger is ever held."""
  flat = {side: {name: np.asarray(leaf).reshape(-1)
                 for name, leaf in trees.items()}
          for side, trees in (("program", program), ("reference", reference))}
  size = flat["reference"]["params0"].size
  squares = {(side, what): 0.0 for side in flat
             for what in ("gradient", "change")}
  init_gap = 0.0
  for lo in range(0, max(size, 1), CHUNK):
    start = {}
    for side, trees in flat.items():
      start[side] = trees["params0"][lo:lo + CHUNK].astype(np.float64)
      g = trees["first_gradient"][lo:lo + CHUNK].astype(np.float64)
      squares[side, "gradient"] += float(np.dot(g, g))
      d = trees["params"][lo:lo + CHUNK].astype(np.float64) - start[side]
      squares[side, "change"] += float(np.dot(d, d))
    if start["program"].size:
      init_gap = max(init_gap, float(np.max(np.abs(
          start["program"] - start["reference"]))))
  norm = lambda side, what: float(np.sqrt(squares[side, what]))  # noqa: E731
  return ((norm("program", "gradient"), norm("reference", "gradient")),
          (norm("program", "change"), norm("reference", "change")), init_gap)


def _gaps(prog: dict, ref: dict, keep):
  """`compare.leaf_gaps` from the leaves' norms."""
  median = float(np.median(list(ref.values())))
  gaps = {}
  for key in keep:
    gap = abs(prog[key] - ref[key]) / max(ref[key], median, 1e-300)
    gaps[key] = gap if np.isfinite(gap) else float("inf")
  return gaps


def training_numbers(program: dict, reference: dict) -> dict:
  """`compare.training_numbers`, leaf by leaf (the leaves on a few threads:
  numpy lets go of the interpreter inside its loops)."""
  import concurrent.futures

  out = {}
  for i, (lp, lr) in enumerate(zip(program["losses"], reference["losses"]),
                               start=1):
    gap = abs(float(lp) - float(lr)) / max(abs(float(lr)), 1e-300)
    out[f"loss{i}"] = {"value": gap if np.isfinite(gap) else float("inf"),
                       "program": float(lp), "reference": float(lr)}
  names = ("params0", "first_gradient", "params")
  flat = {side: {name: dict(leaves(tree[name])) for name in names}
          for side, tree in (("program", program), ("reference", reference))}
  paths = sorted(flat["reference"]["params0"])
  for side in flat.values():
    for name, tree in side.items():
      if sorted(tree) != paths:
        raise ValueError("program and reference have different leaves: "
                         f"{sorted(set(tree) ^ set(paths))[:6]} ({name})")

  def one(path):
    return _leaf_numbers(*({name: flat[side][name][path] for name in names}
                           for side in ("program", "reference")))

  with concurrent.futures.ThreadPoolExecutor(WORKERS) as pool:
    numbers = dict(zip(paths, pool.map(one, paths)))
  gradient = {side: {p: numbers[p][0][i] for p in paths}
              for i, side in enumerate(("program", "reference"))}
  change = {side: {p: numbers[p][1][i] for p in paths}
            for i, side in enumerate(("program", "reference"))}
  init_gap = max(numbers[p][2] for p in paths)
  median = float(np.median(list(gradient["reference"].values())))
  keep = sorted(k for k, n in gradient["reference"].items()
                if n >= compare.ZERO_GRADIENT_SHARE * median)
  gaps = _gaps(gradient["program"], gradient["reference"], keep)
  worst, where, middle = compare._worst_and_median(gaps)
  out["first_gradient"] = {"value": worst, "leaf": where, "leaves": gaps}
  out["first_gradient_median"] = {"value": middle}
  gaps = _gaps(change["program"], change["reference"], keep)
  worst, where, middle = compare._worst_and_median(gaps)
  out["param_change"] = {"value": worst, "leaf": where, "leaves": gaps,
                         "left_out": sorted(set(paths) - set(keep))}
  out["param_change_median"] = {"value": middle}
  out["initial_weights"] = {"value": init_gap}
  if reference.get("first_batch_stats"):
    out["batch_means"] = compare.batch_means_number(
        compare.flatten(program["first_batch_stats"]),
        compare.flatten(reference["first_batch_stats"]))
  return out


STREAMED = types.SimpleNamespace(training_numbers=training_numbers,
                                 decide=compare.decide)


def run(cell, options) -> dict:
  """`trainer.run`, with the comparison taken leaf by leaf."""
  whole = trainer.compare
  trainer.compare = STREAMED
  try:
    return trainer.run(cell, options)
  finally:
    trainer.compare = whole
