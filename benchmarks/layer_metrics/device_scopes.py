"""What the phase and scope metrics share: the program's own op table laid
over the trace. No metric of its own.

Where it compiles the train step, the program (`tensor2robot_tpu/obs/xray.py`)
reads the executable's text once and keeps, for every instruction, its phase
(forward, recompute, backward, optimizer, ema, other), the innermost
`jax.named_scope` the program declared around it and its Flax module path. A
trace names a device op by that same instruction, so the split of a step's
device time is a dictionary lookup, done by the program's own
`xray.device_time_by_scope` (the function `hooks/profiler.ProfilerHook` runs
for an operator): no shape is looked at. A program without the table (a
parent commit) gives every function here nothing to read, and the metric is
left out.

The reduction is printed once a run on standard error, under `[bench
scopes]`: ms a step by phase, by scope and by (phase, scope, module path) with
the copies in each, and the heaviest ops by name.
"""

from __future__ import annotations

import sys

from benchmarks.harness import trace_reduce

EXECUTABLE = "train_step"   # the name `train_eval` analyses the step under
_LAST = {}                  # the last reduction, with what it was made from


def op_table(run):
  """`run["op_table"]` where a test gives one, else the program's table of
  the train step, else None."""
  if "op_table" in run:
    return run["op_table"]
  try:
    from tensor2robot_tpu.obs import xray
  except ImportError:
    return None
  read = getattr(xray, "op_scopes", None)
  return read(EXECUTABLE) if read else None


def reduced(run):
  """`xray.device_time_by_scope` of the first device's op line, the ops inside
  the train step's executions only; None where there is no trace, no table or
  no execution of the table's module."""
  events = run.get("events")
  planes = trace_reduce.device_planes(events or ())
  table = op_table(run)
  if not planes or not table:
    return None
  if _LAST.get("events") is events and _LAST.get("table") is table:
    return _LAST["value"]
  from tensor2robot_tpu.obs import xray

  def line(name):
    return [(e[2], e[3], e[4]) for e in trace_reduce.select(
        events, plane=planes[0], line=name)]

  out = xray.device_time_by_scope(
      line(trace_reduce.OPS_LINE), table, line(trace_reduce.MODULE_LINE))
  if not out["steps"] or not out["total_s"]:
    out = None
  else:
    for text in xray.format_device_scopes(out):
      print("[bench scopes]", text, file=sys.stderr, flush=True)
  _LAST.update(events=events, table=table, value=out)
  return out


def _ms_a_step(out, seconds):
  return 1e3 * seconds / out["steps"] if seconds else None


def phase_ms(run, *phases):
  """Device ms a step under the phases; None where that is nothing."""
  out = reduced(run)
  if out is None:
    return None
  return _ms_a_step(out, sum(out["by_phase"].get(p, 0.0) for p in phases))


def scope_ms(run, *scopes):
  """Device ms a step under the declared scopes, all phases."""
  out = reduced(run)
  if out is None:
    return None
  return _ms_a_step(out, sum(out["by_scope"].get(s, 0.0) for s in scopes))


def first_step_spans(run, *names):
  """Summed seconds of step 1's spans called `names` (xray's, below the
  first `train/dispatch`), or None where it has none of them."""
  from benchmarks.layer_metrics import program_spans

  events = program_spans.program_events(run)
  chosen = [e for name in names for e in program_spans.named(events, name)
            if e.get("step") == 1
            and (e.get("args") or {}).get("executable") == EXECUTABLE]
  return sum(e["dur"] for e in chosen) / 1e6 if chosen else None
