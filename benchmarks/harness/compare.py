"""The comparison that decides `correct` for a training cell: the trainer's
first three steps against the plain reference's, by each step's loss, the
first gradient as the optimizer gets it, and the parameters' change after the
three steps.

Gradient and change are taken by the worst leaf: the gap between the
program's norm and the reference's (not the norm of their difference),
measured against the reference's norm of that leaf or of the median leaf,
whichever is larger, since some gradients are all but zero. Leaves whose
reference gradient is under a thousandth of the median leaf's (a bias in
front of a batch norm, a key's bias under softmax) have no part in the
function: in bfloat16 their gradient is the round-off of a sum that cancels,
and under Adam they move by round-off alone. They are left out of both
numbers, by that rule and not by name, and each run prints which they
were.

Pure numpy on flat dicts path -> array.
"""

from __future__ import annotations

import numpy as np

ZERO_GRADIENT_SHARE = 1e-3


def flatten(tree, prefix="") -> dict:
  """Nested mappings -> {"a/b/c": array}."""
  out = {}
  for key in sorted(tree):
    value = tree[key]
    path = f"{prefix}/{key}" if prefix else str(key)
    if hasattr(value, "keys"):
      out.update(flatten(value, path))
    else:
      out[path] = np.asarray(value, np.float64)
  return out


def _norms(flat: dict) -> dict:
  return {k: float(np.linalg.norm(v)) for k, v in flat.items()}


def leaf_gaps(program: dict, reference: dict, leaves=None) -> dict:
  """leaf -> |norm_p - norm_r| / max(norm_r, median norm_r)."""
  ref = _norms(reference)
  prog = _norms(program)
  if sorted(ref) != sorted(prog):
    raise ValueError("program and reference have different leaves: "
                     f"{sorted(set(ref) ^ set(prog))[:6]}")
  median = float(np.median(list(ref.values())))
  gaps = {}
  for key in (leaves if leaves is not None else sorted(ref)):
    gap = abs(prog[key] - ref[key]) / max(ref[key], median, 1e-300)
    gaps[key] = gap if np.isfinite(gap) else float("inf")
  return gaps


def _worst_and_median(gaps: dict):
  where = max(gaps, key=gaps.get)
  return gaps[where], where, float(np.median(list(gaps.values())))


def moving_leaves(reference_gradient: dict):
  """Leaves whose reference gradient is not nought to rounding."""
  norms = _norms(reference_gradient)
  median = float(np.median(list(norms.values())))
  return sorted(k for k, n in norms.items()
                if n >= ZERO_GRADIENT_SHARE * median)


def training_numbers(program: dict, reference: dict) -> dict:
  """Each number compared, with where its worst leaf was. `program` and
  `reference` hold `losses` (three), `params0`, `first_gradient`, `params`
  (nested or flat)."""
  out = {}
  for i, (lp, lr) in enumerate(zip(program["losses"], reference["losses"]),
                               start=1):
    gap = abs(float(lp) - float(lr)) / max(abs(float(lr)), 1e-300)
    out[f"loss{i}"] = {"value": gap if np.isfinite(gap) else float("inf"),
                       "program": float(lp), "reference": float(lr)}
  p0_p, p0_r = flatten(program["params0"]), flatten(reference["params0"])
  g_p = flatten(program["first_gradient"])
  g_r = flatten(reference["first_gradient"])
  keep = moving_leaves(g_r)
  gaps = leaf_gaps(g_p, g_r, keep)
  worst, where, median = _worst_and_median(gaps)
  out["first_gradient"] = {"value": worst, "leaf": where, "leaves": gaps}
  out["first_gradient_median"] = {"value": median}
  p_p, p_r = flatten(program["params"]), flatten(reference["params"])
  change_p = {k: p_p[k] - p0_p[k] for k in p_p}
  change_r = {k: p_r[k] - p0_r[k] for k in p_r}
  gaps = leaf_gaps(change_p, change_r, keep)
  worst, where, median = _worst_and_median(gaps)
  out["param_change"] = {"value": worst, "leaf": where, "leaves": gaps,
                         "left_out": sorted(set(g_r) - set(keep))}
  out["param_change_median"] = {"value": median}
  init_gap = max(float(np.max(np.abs(p0_p[k] - p0_r[k]))) for k in p0_r)
  out["initial_weights"] = {"value": init_gap}
  if reference.get("first_batch_stats"):
    out["batch_means"] = batch_means_number(
        flatten(program["first_batch_stats"]),
        flatten(reference["first_batch_stats"]))
  return out


def batch_means_number(program: dict, reference: dict) -> dict:
  """Normalization layers' running means after step 1: per layer the norm of
  the difference over the reference's norm, and of those the median layer.
  A batch mean is taken over so many values that rounding noise and the flips
  of ReLU and max-pool gates average out of it, so it moves in proportion to
  the precision of the products in front of it: it is the number that tells a
  lower precision from the stated one where gradients cannot (PERF.md)."""
  layers = {}
  for key in sorted(reference):
    if key.endswith("/mean"):
      scale = max(float(np.linalg.norm(reference[key])), 1e-300)
      gap = float(np.linalg.norm(program[key] - reference[key])) / scale
      layers[key] = gap if np.isfinite(gap) else float("inf")
  where = max(layers, key=layers.get)
  return {"value": float(np.median(list(layers.values()))),
          "worst": layers[where], "leaf": where, "leaves": layers}


def decide(numbers: dict, limits: dict):
  """(correct, checks): every number in `limits` at or under its limit. A
  number the limits file does not name is printed and not held."""
  checks, correct = {}, True
  for name, entry in numbers.items():
    limit = limits.get(name)
    checks[name] = {"value": entry["value"], "limit": limit}
    if limit is not None and not entry["value"] <= limit:
      correct = False
  for name in limits:
    if name not in numbers and not name.startswith("_"):
      checks[name] = {"value": None, "limit": limits[name]}
      correct = False
  return correct, checks
