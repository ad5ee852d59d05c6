"""What the span metrics share: the program's own span tree, read after the
run, and the arithmetic on it. No metric of its own.

The program (`tensor2robot_tpu/obs/trace.py`) keeps a ring of complete events
`{"name", "ts", "dur", "tid", "id", "parent", "step", "args"}`, `ts` and `dur`
in microseconds of `perf_counter`; `train_eval_model` arms it for every
training run. Each loop iteration is one `train/iteration` (with `step`), and
what the loop, stepstats, the hooks and the summary writer do is below it. A
program that has no such spans (a parent commit) gives every function here
nothing to read, and the metric is left out.

Everything works on a plain list of such events, so it is checked on lists
written by hand (`tests/benchmark/test_program_spans.py`).
"""

from __future__ import annotations

import statistics
import sys

from benchmarks.harness import trace_reduce

ITERATION = "train/iteration"
MIN_GAP_NS = 1e6  # shorter device gaps are launch latency between programs


def program_events(run) -> list:
  """The ring of the run's process: `run["program_events"]` where a test
  gives one, else the program's tracer as the trainer left it. Nothing for a
  record that is not a finished run."""
  if not run.get("steps"):
    return []
  if "program_events" in run:
    return run["program_events"]
  try:
    from tensor2robot_tpu.obs import trace as trace_lib
  except ImportError:
    return []
  return trace_lib.get_tracer().events()


def spans(events) -> list:
  """The complete events that are part of the tree."""
  return [e for e in events if e.get("ph") == "X" and "id" in e]


def named(events, name: str) -> list:
  return sorted((e for e in spans(events) if e["name"] == name),
                key=lambda e: e["ts"])


def end(span) -> float:
  return span["ts"] + span["dur"]


def window_iterations(events, steps: int) -> list:
  """The `train/iteration` spans of the window: those of the last `steps`
  values of `step`, in order."""
  iterations = [e for e in named(events, ITERATION) if "step" in e]
  kept = sorted({e["step"] for e in iterations})[-int(steps):]
  return [e for e in iterations if e["step"] in set(kept)]


def children(events, span) -> list:
  """The spans opened below `span`, in order. An externally timed window
  that was recorded while `span` was open, and began before it (stepstats'
  `train/step_window`), names it as parent without lying inside it: it is
  no part of the tree."""
  return sorted((e for e in spans(events) if e.get("parent") == span["id"]
                 and e["ts"] >= span["ts"] - 1e-3),
                key=lambda e: e["ts"])


def covered_us(intervals, start: float, stop: float) -> float:
  """Length of [start, stop) that the (start, end) intervals cover."""
  total, cursor = 0.0, start
  for a, b in sorted(intervals):
    a, b = max(a, cursor), min(b, stop)
    if b > a:
      total += b - a
      cursor = b
  return total


def self_ms(events, span) -> float:
  """The span's duration minus what its children cover of it."""
  inside = [(c["ts"], end(c)) for c in children(events, span)]
  return (span["dur"] - covered_us(inside, span["ts"], end(span))) / 1e3


def leaf_self_times(events, spans_in) -> dict:
  """name -> summed self time (ms) over `spans_in` and all below them; a
  `train/hook` or an observer is named with its `hook` or `observer`."""
  out = {}
  todo = list(spans_in)
  while todo:
    span = todo.pop()
    args = span.get("args") or {}
    who = args.get("hook") or args.get("observer")
    name = f"{span['name']}[{who}]" if who else span["name"]
    out[name] = out.get(name, 0.0) + self_ms(events, span)
    todo.extend(children(events, span))
  return out


def median_ms(chosen):
  return statistics.median(e["dur"] for e in chosen) / 1e3 if chosen else None


def window_spans(events, steps: int, name: str) -> list:
  """Spans called `name` in the window: by `step` where they have one, else
  (another thread's) by starting inside the window's iterations' time."""
  iterations = window_iterations(events, steps)
  if not iterations:
    return []
  in_window = {e["step"] for e in iterations}
  start, stop = iterations[0]["ts"], end(iterations[-1])
  return [e for e in named(events, name)
          if (e["step"] in in_window if "step" in e
              else start <= e["ts"] < stop)]


def record_stalls(events, steps: int) -> list:
  """[(step, ms, spans between)]: per stepstats record of the window, from
  the end of its `train/barrier` to the start of the next `train/dispatch`,
  less what the benchmark's own hook took of it (a traced run stops its
  trace there). The device has nothing queued in that stretch: the barrier
  waited for it."""
  dispatches = named(events, "train/dispatch")
  own = [(e["ts"], end(e)) for e in bench_hook_spans(events)]
  out = []
  for iteration in window_iterations(events, steps):
    barriers = [c for c in children(events, iteration)
                if c["name"] == "train/barrier"]
    if not barriers:
      continue
    stop = end(barriers[-1])
    later = [d for d in dispatches if d["ts"] >= stop]
    if not later:
      continue  # the run's last record: nothing is dispatched after it
    between = [c for c in children(events, iteration)
               if c["ts"] >= stop and not is_bench_hook(c)]
    stall_us = later[0]["ts"] - stop - covered_us(own, stop, later[0]["ts"])
    out.append((iteration["step"], stall_us / 1e3, between))
  return out


def setup_s(events, *names):
  """Summed seconds of the named spans, each of which the run has once."""
  chosen = [named(events, name) for name in names]
  if not all(len(c) == 1 for c in chosen):
    return None
  return sum(c[0]["dur"] for c in chosen) / 1e6


def is_bench_hook(span) -> bool:
  args = span.get("args") or {}
  return (span["name"] == "train/hook" and args.get("hook") == "BenchHook"
          and args.get("method") == "after_step")


def bench_hook_spans(events) -> list:
  """The program's `train/hook` spans of the benchmark's own hook."""
  return [e for e in named(events, "train/hook") if is_bench_hook(e)]


def offset_from_pairs(events, trace_events):
  """Nanoseconds to add to a ring time (`ts` x 1000) to get the trace's
  clock, from data: every `bench/after_step` event of the trace lies inside
  the program's `train/hook` span of `BenchHook` for the same step. The
  trace holds a run of consecutive steps; the alignment with the ring's run
  is the one under which the differences of the starts spread least, and the
  offset their median."""
  bench = sorted(e[3] for e in trace_events if e[2] == "bench/after_step")
  ring = [e["ts"] * 1e3 for e in bench_hook_spans(events)]
  if len(bench) < 2 or len(ring) < len(bench):
    return None
  best = None
  for shift in range(len(ring) - len(bench) + 1):
    diffs = [b - r for b, r in zip(bench, ring[shift:])]
    spread = max(diffs) - min(diffs)
    if best is None or spread < best[0]:
      best = (spread, statistics.median(diffs))
  return best[1]


def device_gaps(trace_events, min_ns: float = MIN_GAP_NS) -> list:
  """[(start_ns, end_ns)] of the first device's idle gaps of `min_ns` and
  more inside the traced window (first device op's start to the last's end)."""
  planes = trace_reduce.device_planes(trace_events)
  if not planes:
    return []
  window = trace_reduce.device_window(trace_events)
  busy = trace_reduce.merged_intervals(trace_reduce.clip(
      trace_reduce._op_events(trace_events, planes[0]), *window))
  gaps, cursor = [], window[0]
  for start, stop in busy:
    if start - cursor >= min_ns:
      gaps.append((cursor, start))
    cursor = max(cursor, stop)
  return gaps


def unattributed_share(events, gaps, offset_ns):
  """Of the device's idle time in `gaps` (`device_gaps`), the share (%) that
  no span below a `train/iteration` covers, with the ring laid over the
  trace by `offset_ns`. 0 where there is no gap."""
  iterations = named(events, ITERATION)
  if not iterations or offset_ns is None:
    return None
  idle = sum(b - a for a, b in gaps)
  if not idle:
    return 0.0
  below = [(c["ts"] * 1e3 + offset_ns, end(c) * 1e3 + offset_ns)
           for it in iterations for c in children(events, it)]
  named_ns = sum(covered_us(below, a, b) for a, b in gaps)
  return 100.0 * (idle - named_ns) / idle


def say(*parts) -> None:
  print("[bench spans]", *parts, file=sys.stderr, flush=True)
