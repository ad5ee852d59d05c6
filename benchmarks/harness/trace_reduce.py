"""The one reduction from a profiler trace to numbers.

It works on a plain list of events `(plane, line, name, start_ns,
duration_ns)`, so it can be checked on a list written by hand and on a small
recorded trace (`benchmarks/traces/`). `read_xplane` makes that list from the
`.xplane.pb` the JAX profiler writes, with nothing but JAX.

What the v5e's trace looks like (looked at by hand, PERF.md): one plane per
chip, `/device:TPU:<n>`, with a line `XLA Modules` (one event per executed
program) and a line `XLA Ops` (one event per HLO operation, nested for loops
and fusions' callers); host threads are lines of the plane `/host:CPU`, and
`jax.profiler.TraceAnnotation` spans land there under their own names.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def read_xplane(trace_dir: str, host_prefixes=("bench/",)):
  """Events of the newest trace under `trace_dir`: every event of the device
  planes' module and op lines, and of the host planes only the spans whose
  name starts with one of `host_prefixes` (host lines hold a great many
  events)."""
  import jax

  files = sorted(glob.glob(os.path.join(
      trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
  if not files:
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
  data = jax.profiler.ProfileData.from_file(files[-1])
  events = []
  for plane in data.planes:
    if plane.name.startswith(DEVICE_PLANE_PREFIX):
      for line in plane.lines:
        if line.name in (MODULE_LINE, OPS_LINE):
          for e in line.events:
            events.append((plane.name, line.name, e.name, float(e.start_ns),
                           float(e.duration_ns)))
    elif plane.name.startswith(HOST_PLANE_PREFIX):
      for line in plane.lines:
        for e in line.events:
          if e.name.startswith(tuple(host_prefixes)):
            events.append((plane.name, line.name, e.name, float(e.start_ns),
                           float(e.duration_ns)))
  return events


def device_planes(events):
  return sorted({p for p, *_ in events if p.startswith(DEVICE_PLANE_PREFIX)})


def select(events, plane=None, line=None, name_has=None):
  return [e for e in events
          if (plane is None or e[0] == plane)
          and (line is None or e[1] == line)
          and (name_has is None or name_has in e[2])]


def merged_intervals(events):
  """Sorted, disjoint [start, end) intervals covered by the events."""
  spans = sorted((s, s + d) for _, _, _, s, d in events if d > 0)
  merged = []
  for start, end in spans:
    if merged and start <= merged[-1][1]:
      if end > merged[-1][1]:
        merged[-1][1] = end
    else:
      merged.append([start, end])
  return merged


def busy_ns(events) -> float:
  return sum(end - start for start, end in merged_intervals(events))


def clip(events, start_ns: float, end_ns: float):
  """Events cut to [start_ns, end_ns)."""
  out = []
  for plane, line, name, s, d in events:
    a, b = max(s, start_ns), min(s + d, end_ns)
    if b > a:
      out.append((plane, line, name, a, b - a))
  return out


def device_window(events):
  """(start, end) of the device work in the trace: first op start to last op
  end over all device planes."""
  ops = [e for e in events if e[0].startswith(DEVICE_PLANE_PREFIX)]
  if not ops:
    return None
  return min(e[3] for e in ops), max(e[3] + e[4] for e in ops)


def _op_events(events, plane):
  """A plane's op events, or its module events where it has no op line."""
  return select(events, plane=plane, line=OPS_LINE) or select(
      events, plane=plane, line=MODULE_LINE)


def device_busy(events, window=None):
  """{"busy_s", "window_s", "per_device"}: per device plane the union of its
  op intervals, averaged over the planes; the window is the span given, or
  from the first device op's start to the last one's end."""
  planes = device_planes(events)
  if not planes:
    return None
  window = window or device_window(events)
  per_device = {}
  for plane in planes:
    per_device[plane] = busy_ns(clip(_op_events(events, plane),
                                     *window)) / 1e9
  return {"busy_s": sum(per_device.values()) / len(planes),
          "window_s": (window[1] - window[0]) / 1e9,
          "per_device": per_device}


def top_level(events):
  """Events not nested inside an earlier event of the same list (a while
  loop's body ops lie inside the loop's own event)."""
  out, end = [], -1.0
  for e in sorted(events, key=lambda e: (e[3], -e[4])):
    if e[3] >= end:
      out.append(e)
      end = e[3] + e[4]
  return out


def short_name(op_name: str) -> str:
  """The v5e's trace names a device op by its whole HLO instruction
  (`%fusion.12 = bf16[...] fusion(...), kind=kOutput, ...`): keep the
  instruction's own name and its opcode, `fusion.12 fusion`."""
  head, sep, rest = op_name.partition(" = ")
  if not sep:
    return op_name[:96]
  if rest.startswith("("):  # a tuple shape comes first: skip to its close
    rest = rest[_matching_paren(rest) + 1:]
  else:                     # one shape, then the opcode
    rest = rest.partition(" ")[2]
  opcode = rest.strip().split("(")[0].strip()
  return f"{head.lstrip('%')} {opcode}".strip()[:96]


def _matching_paren(text: str) -> int:
  depth = 0
  for i, c in enumerate(text):
    depth += c == "("
    depth -= c == ")"
    if depth == 0:
      return i
  return len(text) - 1


def output_shapes(op_name: str):
  """Element types of an HLO instruction's outputs, in order:
  `%a = (bf16[..]{..}, f32[..]{..}) custom-call(...)` -> ["bf16", "f32"]."""
  _, sep, rest = op_name.partition(" = ")
  if not sep:
    return []
  if rest.startswith("("):
    rest = rest[1:_matching_paren(rest)]
  else:
    rest = rest.split(" ")[0]
  return [part.strip().split("[")[0] for part in _split_top(rest)]


def _split_top(text: str):
  parts, depth, start = [], 0, 0
  for i, c in enumerate(text):
    depth += c in "([{"
    depth -= c in ")]}"
    if c == "," and depth == 0:
      parts.append(text[start:i])
      start = i + 1
  parts.append(text[start:])
  return [p for p in parts if p.strip()]


def sum_by_name(events, name=lambda n: n):
  """name -> summed duration in seconds."""
  sums = {}
  for _, _, op, _, d in events:
    sums[name(op)] = sums.get(name(op), 0.0) + d / 1e9
  return sums


def module_durations(events, plane=None):
  """module name -> list of durations (s), from the module line."""
  out = {}
  for _, _, name, _, d in select(events, plane=plane, line=MODULE_LINE):
    out.setdefault(base_name(name), []).append(d / 1e9)
  return out


def base_name(module_event_name: str) -> str:
  """`jit_step_fn(1234567)` -> `jit_step_fn`."""
  return module_event_name.split("(", 1)[0]


def heaviest_module(events, plane=None):
  """(name, durations) of the module with the most summed time: in a training
  window that is the train step."""
  modules = module_durations(events, plane)
  if not modules:
    return None, []
  name = max(modules, key=lambda k: sum(modules[k]))
  return name, modules[name]


def step_durations(events):
  """Durations (s) of the train step's executions on the first device, or []
  where the trace has no device plane."""
  planes = device_planes(events or ())
  return heaviest_module(events, planes[0])[1] if planes else []


def idle_gaps(events, host_events=(), window=None, plane=None, top=5):
  """The longest gaps between device ops on one plane, each with the host
  span that covers at least half of it, else `after <the last host span that
  began before the gap ended>`, else "(no span)": [(label, seconds)]."""
  planes = device_planes(events)
  if not planes:
    return []
  plane = plane or planes[0]
  window = window or device_window(events)
  busy = merged_intervals(clip(_op_events(events, plane), *window))
  gaps, cursor = [], window[0]
  for start, end in busy:
    if start > cursor:
      gaps.append((cursor, start))
    cursor = max(cursor, end)
  if window[1] > cursor:
    gaps.append((cursor, window[1]))
  gaps.sort(key=lambda g: g[0] - g[1])
  spans = sorted(host_events, key=lambda e: e[3])
  out = []
  for start, end in gaps[:top]:
    best, best_cover, before = None, 0.0, None
    for _, _, name, s, d in spans:
      cover = min(end, s + d) - max(start, s)
      if cover > best_cover:
        best, best_cover = name, cover
      if s < end:
        before = name
    if best is not None and best_cover >= 0.5 * (end - start):
      label = best
    elif before is not None:
      # No span covers the gap: say what the host did last before it ended.
      label = f"after {before}"
    else:
      label = "(no span)"
    out.append((label, (end - start) / 1e9))
  return out


def breakdown(events, window=None, top=10):
  """{"device_ops": [[name, s]], "idle_gaps": [[name, s]]} for the result
  line: the top-level device ops that took most time on the first device, and
  its longest idle gaps by what the host was doing."""
  planes = device_planes(events)
  if not planes:
    return {"device_ops": [], "idle_gaps": []}
  window = window or device_window(events)
  ops = top_level(clip(select(events, plane=planes[0], line=OPS_LINE),
                       *window))
  sums = sorted(sum_by_name(ops, short_name).items(),
                key=lambda kv: -kv[1])[:top]
  host = [e for e in events if e[0].startswith(HOST_PLANE_PREFIX)]
  return {"device_ops": [[k, v] for k, v in sums],
          "idle_gaps": [[k, v] for k, v in idle_gaps(
              events, host, window, planes[0], top=5)]}


def summarize(events, top=25) -> str:
  """For looking at a trace by hand: planes, lines, and the heaviest names."""
  lines = []
  keys = sorted({(p, l) for p, l, *_ in events})
  for plane, line in keys:
    chosen = select(events, plane=plane, line=line)
    lines.append(f"{plane} | {line}: {len(chosen)} events, "
                 f"{busy_ns(chosen) / 1e9:.6f} s busy")
    sums = sorted(sum_by_name(chosen).items(), key=lambda kv: -kv[1])[:top]
    for name, seconds in sums:
      count = sum(1 for e in chosen if e[2] == name)
      lines.append(f"    {seconds:.6f} s  x{count}  {name[:400]}")
  return "\n".join(lines)
