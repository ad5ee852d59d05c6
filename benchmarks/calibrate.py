"""Readings that the limits in `benchmarks/limits/<cell>.json` are set from.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 12 --controls 3

In one process, on the chip, at the cell's own size:

  * the program's numbers (the trainer's first three steps against the plain
    reference) over `--seeds` seeds: the largest is a number's lower reading;
  * the control's over `--controls` seeds: the reference put in the program's
    place and computed in the configuration's `control_precision`, the nearest
    precision below the one the configuration states: the smallest is the
    upper reading;
  * the fault "half of the batch left out, the mean taken over the rest",
    planted in the reference put in the program's place, on the same seeds.
    (A step that returns its state unchanged reads 1 for `param_change` by
    construction and needs no run.)

The benchmark's own runs never run this. It prints one JSON object and, with
`--out`, writes it to a file. tests/benchmark/test_control.py keeps the same
readings at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from benchmarks.harness import compare  # noqa: E402
from benchmarks.harness import manifest  # noqa: E402


def values(numbers: dict) -> dict:
  out = {k: v["value"] for k, v in numbers.items()}
  for k, v in numbers.items():
    if "leaves" in v:  # kept so that another statistic can be read off later
      out[f"{k}.leaves"] = v["leaves"]
  return out


def stand_in_readings(cell, seed: int, rehearse: bool, batches) -> dict:
  """The control's and the fault's numbers for one seed, each against the
  float32 reference on the same batches."""
  from benchmarks.drivers import trainer

  reference = cell.reference()
  sizes = trainer.reference_sizes(cell, rehearse)
  truth = reference.train_steps(seed, sizes, batches)
  control = reference.train_steps(
      seed, sizes, batches, precision=cell.config["control_precision"])
  rows = len(next(iter(batches[0].values())))
  half = reference.train_steps(seed, sizes, batches,
                               rows=slice(0, rows // 2))
  return {"control": values(compare.training_numbers(control, truth)),
          "half_batch": values(compare.training_numbers(half, truth))}


def main(argv=None) -> int:
  parser = argparse.ArgumentParser()
  parser.add_argument("--workload", required=True)
  parser.add_argument("--seeds", type=int, default=12)
  parser.add_argument("--controls", type=int, default=3)
  parser.add_argument("--first-seed", type=int, default=2_000_000_011)
  parser.add_argument("--rehearse", action="store_true")
  parser.add_argument("--out", default=None)
  args = parser.parse_args(argv)

  import jax

  cell = manifest.Cell(args.workload)
  platform = jax.devices()[0].platform
  if (platform == "tpu") == args.rehearse:
    raise SystemExit(f"platform {platform!r} with rehearse={args.rehearse}")
  out = {"cell": cell.name, "platform": platform, "program": {},
         "control": {}, "half_batch": {}, "seconds": {}}
  seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
  for i, seed in enumerate(seeds):
    options = {"seed": seed, "seconds": 0.5, "trace": False,
               "rehearse": args.rehearse, "root": ROOT, "keep": None,
               "process_start": time.perf_counter(),
               "keep_batches": i < args.controls}
    started = time.perf_counter()
    run = cell.driver().run(cell, options)
    out["program"][str(seed)] = values(run["numbers"])
    if i < args.controls:
      readings = stand_in_readings(cell, seed, args.rehearse,
                                   run["raw_batches"])
      out["control"][str(seed)] = readings["control"]
      out["half_batch"][str(seed)] = readings["half_batch"]
    out["seconds"][str(seed)] = time.perf_counter() - started
    def short(reading):
      return reading and {k: v for k, v in reading.items()
                          if not k.endswith(".leaves")}
    print(json.dumps({"seed": seed,
                      "program": short(out["program"][str(seed)]),
                      "control": short(out["control"].get(str(seed))),
                      "half_batch": short(out["half_batch"].get(str(seed)))}),
          file=sys.stderr, flush=True)
  names = sorted(n for n in next(iter(out["program"].values()))
                 if not n.endswith(".leaves"))
  out["lower"] = {n: max(r[n] for r in out["program"].values())
                  for n in names}
  out["upper_control"] = {n: min(r[n] for r in out["control"].values())
                          for n in names} if out["control"] else {}
  out["upper_half_batch"] = {n: min(r[n] for r in out["half_batch"].values())
                             for n in names} if out["half_batch"] else {}
  text = json.dumps(out, indent=1)
  if args.out:
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
      f.write(text)
  print(text)
  return 0


if __name__ == "__main__":
  sys.exit(main())
