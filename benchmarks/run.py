"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of `BENCHMARK.json` once, on the machine it is started on, and
prints as the last line of standard output one JSON object with the keys
`correct`, `attempted`, `failed`, `metrics`, `device` (with `--trace 1` also
`breakdown`) and, last, `checks`: each number the comparison with the plain
reference read, beside its limit. The same numbers are the last lines on
standard error. `--trace 0` reports the cell's end-to-end metrics, `--trace 1`
its per-layer metrics.

It exits non-zero and prints no result when JAX finds no TPU or another count
of chips than the cell asks for, and in a directory that lacks the program.
`--rehearse` drives the same code on the CPU at the traffic mix's `tiny` sizes,
with Pallas interpreted: it prints counts only, names the CPU under `device`,
and puts no rate, share or time under a metric's name.

The process stays off JAX until it has read the cell's files. It is the one
process that touches the chip; it starts no other.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)

from benchmarks.harness import manifest  # noqa: E402


def _parse(argv):
  parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  parser.add_argument("--workload", required=True)
  parser.add_argument("--seed", type=int, default=0)
  parser.add_argument("--seconds", type=float, default=None)
  parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
  parser.add_argument("--rehearse", action="store_true")
  parser.add_argument("--keep", default=None,
                      help="directory that keeps the raw trace (for looking "
                           "at one by hand)")
  return parser.parse_args(argv)


def _device_record(cell, rehearse: bool) -> dict:
  import jax

  devices = jax.devices()
  record = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
  if rehearse:
    if record["platform"] != "cpu":
      raise SystemExit("--rehearse is the CPU stand-in; this process has "
                       f"{record}. Run the cell itself here.")
    return record
  if record["platform"] != "tpu" or record["count"] != cell.chips:
    raise SystemExit(
        f"cell {cell.name!r} needs {cell.chips} TPU chip(s); JAX has {record}."
        " No chip, no number.")
  return record


def _read_metrics(cell, run: dict, kind: str) -> dict:
  out = {}
  for metric in cell.metrics(kind):
    name = metric["name"]
    if kind == "end_to_end":
      value = run.get(name)
    else:
      value = manifest.layer_metric_reader(name)(run)
    if value is None:
      continue  # a reader that finds nothing to read returns nothing
    out[name] = {"value": float(value), "unit": metric["unit"]}
  return out


def main(argv=None) -> int:
  args = _parse(sys.argv[1:] if argv is None else argv)
  if not os.path.isdir(os.path.join(ROOT, "tensor2robot_tpu")):
    print("the program (tensor2robot_tpu/) is not in this checkout",
          file=sys.stderr)
    return 2
  benchmark = manifest.load_benchmark(ROOT)
  cell = manifest.Cell(args.workload, benchmark)
  seconds = (args.seconds if args.seconds is not None
             else benchmark["run_seconds"])

  device = _device_record(cell, args.rehearse)
  peaks = None
  if not args.rehearse:
    from benchmarks.harness import peaks as peaks_lib
    peaks = peaks_lib.peaks_for(device["kind"])

  options = {"seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
             "rehearse": args.rehearse, "root": ROOT, "keep": args.keep,
             "process_start": PROCESS_START}
  run = cell.driver().run(cell, options)
  run.update({"cell": cell.name, "trace": bool(args.trace), "peaks": peaks,
              "rehearse": args.rehearse, "device": device})
  run["examples_per_s"] = run["steps"] * run["batch_size"] / run["window_s"]

  result = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]), "failed": int(run["failed"])}
  memory = run.get("memory", {})
  device_out = dict(device)
  # The allocator's peak counts buffers (state, and every batch the host has
  # placed ahead of the device); a program's temporaries are outside it on
  # this runtime and are there whenever a step runs (PERF.md, section 6).
  device_out["memory_peak_bytes"] = int(
      memory.get("peak_bytes_in_use", 0) + memory.get("step_temp_bytes", 0))
  if args.rehearse:
    result["metrics"] = {}
    result["counts"] = {"steps": run["steps"],
                        "examples": run["steps"] * run["batch_size"],
                        "model_flops_per_step": run["model_flops_per_step"],
                        "trace_events": len(run.get("events", []))}
  elif args.trace:
    from benchmarks.harness import trace_reduce
    busy = trace_reduce.device_busy(run["events"])
    if busy is None or not busy["busy_s"] > 0:
      print("the trace holds no device operation", file=sys.stderr)
      return 3
    run["busy"] = busy
    device_out["busy_s"] = busy["busy_s"]
    device_out["window_s"] = busy["window_s"]
    result["metrics"] = _read_metrics(cell, run, "per_layer")
    result["breakdown"] = trace_reduce.breakdown(run["events"])
    if args.keep:
      import gzip
      with open(os.path.join(args.keep, f"summary_{cell.name}.txt"), "w") as f:
        f.write(trace_reduce.summarize(run["events"]))
      with gzip.open(os.path.join(args.keep, f"events_{cell.name}.json.gz"),
                     "wt") as f:
        json.dump(run["events"], f)
  else:
    result["metrics"] = _read_metrics(cell, run, "end_to_end")
  result["device"] = device_out
  result["seconds"] = {"window_s": run["window_s"], "steps": run["steps"],
                       "reference_s": run["reference_s"],
                       "compiles_in_window": run["compiles_in_window"]}
  result["checks"] = run["checks"]

  for name, check in run["checks"].items():
    detail = {k: v for k, v in run["numbers"].get(name, {}).items()
              if k not in ("value", "leaves")}
    print(f"check {name}: value {check['value']!r} limit {check['limit']!r} "
          f"{detail}", file=sys.stderr)
  print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
  print(json.dumps(result), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
