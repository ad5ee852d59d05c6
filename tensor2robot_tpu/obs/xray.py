"""graftscope-xray: compile, cost and memory introspection below dispatch.

The reference has nothing under the dispatch boundary — TPUEstimator
hides compilation and HBM inside the session
(/root/reference/models/abstract_model.py:662-834) and every OOM or
compile stall surfaces as an opaque session error. Here the jit/pjit
entry points can be X-rayed: `analyze_jit` AOT-traces/lowers/compiles a
jitted callable with per-phase timing and reads the compiled
executable's own XLA cost analysis (FLOPs, bytes accessed) and memory
analysis (argument/output/temp bytes), plus jaxpr equation counts and
declared-donation byte accounting from `Traced.args_info`. From those it
derives arithmetic intensity, an analytic v5e roofline, and (given a
measured step time) MFU — the accounting that diagnosed the round-5
b80–b128 valley by hand (PERFORMANCE.md: 451 ms/step measured vs a
~28 ms roofline priced from the very same cost-analysis numbers).

`memory_accounting` prices a TrainState + batch in bytes, globally and
PER SHARD (via each leaf's `sharding.shard_shape`; replicated leaves
cost full bytes per device), and `hbm_watermark_estimate` combines it
with the executable's temp bytes into the per-run HBM watermark that
rounds 2–5 OOMed without (b512/b320/b384 all died blind).

Analysis results land in three places at once: the process-wide metrics
registry (the `xray/analyses`, `xray/analyze_failures` and
`xray/compiled_call_fallbacks` counters), a module-level record
collector (drained into `obs.runlog` run records), and the caller's
hands.

The op table (PR 37): where it compiles, `analyze_jit` also reads the
executable's text once and keeps, per instruction, the program's own
names for it: `phase` (forward, recompute, backward, optimizer, ema,
other), the innermost declared `jax.named_scope` (`DEVICE_SCOPES`) and
the Flax module path, all from the instruction's `op_name`
(`build_op_table`). A profiler trace names a device op by that same
instruction, so `device_time_by_scope` turns one device's op line into
seconds by phase, by scope and by module with a dictionary lookup, and
no reader has to guess an op by its shapes. `op_scopes(name)` hands the
table out; it persists with the cache entry.

Backend-free at import like the rest of `obs/` — jax is imported only
inside the analysis functions, which are called from live loops where
the backend is already up (tests/test_observability.py proves the
import under a poisoned JAX_PLATFORMS). Telemetry must never take down
a train loop: `XrayedFunction` falls back to the plain jitted callable
on ANY analysis or compiled-call failure.

graftcache (PR 7): `analyze_jit`/`XrayedFunction` take a `cache=` seam
(`obs.excache`) that persists the AOT executables they produce and
short-circuits lower+compile with a deserialize on later processes —
trainer restarts and serving cold starts warm-start in
milliseconds. All cache failure modes degrade to the fresh compile.
"""

from __future__ import annotations

import functools
import re
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import trace as trace_lib
from tensor2robot_tpu.utils import backend as backend_lib

__all__ = ["analyze_jit", "XrayedFunction", "memory_accounting",
           "hbm_watermark_estimate", "pytree_bytes",
           "pytree_shard_bytes", "records", "clear_records",
           "DEVICE_SCOPES", "PHASES", "classify_op_name", "build_op_table",
           "op_entry", "op_scopes",
           "device_time_by_scope", "format_device_scopes",
           "read_device_lines"]

_RECORDS: List[Dict[str, Any]] = []
_OP_TABLES: Dict[str, Dict[str, Any]] = {}
_LOCK = threading.Lock()


def records() -> List[Dict[str, Any]]:
  """Compile records collected since the last `clear_records()`."""
  with _LOCK:
    return list(_RECORDS)


def clear_records() -> None:
  """Drops collected records and op tables (run start, alongside
  trace/metrics reset)."""
  with _LOCK:
    _RECORDS.clear()
    _OP_TABLES.clear()


def _collect(record: Dict[str, Any]) -> None:
  with _LOCK:
    _RECORDS.append(record)


# ---------------------------------------------------------------------------
# Byte accounting over pytrees.
# ---------------------------------------------------------------------------


def _leaf_nbytes(leaf) -> int:
  """Logical bytes of one array-like leaf (0 for non-arrays)."""
  nbytes = getattr(leaf, "nbytes", None)
  if nbytes is not None:
    return int(nbytes)
  shape = getattr(leaf, "shape", None)
  dtype = getattr(leaf, "dtype", None)
  if shape is None or dtype is None:
    return 0
  import numpy as np

  size = 1
  for dim in shape:
    size *= int(dim)
  return size * np.dtype(dtype).itemsize


def _leaf_shard_nbytes(leaf) -> int:
  """Per-device bytes of one leaf: the shard slice when the leaf carries
  a sharding, the full array otherwise (replicated arrays DO occupy full
  bytes on every device — that is the honest per-shard cost)."""
  sharding = getattr(leaf, "sharding", None)
  shape = getattr(leaf, "shape", None)
  if sharding is not None and shape is not None:
    try:
      import numpy as np

      shard_shape = sharding.shard_shape(tuple(shape))
      size = 1
      for dim in shard_shape:
        size *= int(dim)
      return size * np.dtype(leaf.dtype).itemsize
    except Exception:  # noqa: BLE001 - fall back to the global bytes
      pass
  return _leaf_nbytes(leaf)


def pytree_bytes(tree) -> int:
  """Total logical bytes over every array leaf of `tree`."""
  import jax

  return sum(_leaf_nbytes(x) for x in jax.tree_util.tree_leaves(tree))


def pytree_shard_bytes(tree) -> int:
  """Per-device bytes over every leaf (see `_leaf_shard_nbytes`)."""
  import jax

  return sum(_leaf_shard_nbytes(x) for x in jax.tree_util.tree_leaves(tree))


# ---------------------------------------------------------------------------
# Compile telemetry.
# ---------------------------------------------------------------------------


def _count_eqns(jaxpr) -> int:
  """Total equation count, nested jaxprs (pjit/scan/custom_vjp bodies)
  included — a cheap structural size proxy that moves when a model edit
  re-traces into something materially different."""
  jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
  total = 0
  for eqn in getattr(jaxpr, "eqns", ()):
    total += 1
    for value in eqn.params.values():
      values = value if isinstance(value, (list, tuple)) else (value,)
      for item in values:
        if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
          total += _count_eqns(item)
  return total


def _donation_bytes(traced, args) -> Tuple[float, float]:
  """(donated, undonated) argument bytes from the Traced's args_info
  (the declared donation set — what the caller hands over, whether or
  not XLA finds a reusable buffer for each)."""
  import jax

  infos = jax.tree_util.tree_leaves(
      traced.args_info, is_leaf=lambda n: hasattr(n, "donated"))
  if infos and all(hasattr(i, "donated") for i in infos):
    donated = sum(_leaf_nbytes(i) for i in infos if i.donated)
    total = sum(_leaf_nbytes(i) for i in infos)
    return float(donated), float(total - donated)
  total = sum(pytree_bytes(a) for a in args)
  return 0.0, float(total)


# ---------------------------------------------------------------------------
# The op table: the program's names for the device's ops.
# ---------------------------------------------------------------------------

# Every `jax.named_scope("...")` the package opens (a test holds the two
# lists equal). The first four are the step's own (`parallel/train_step`),
# `param_cast` is `models/abstract`'s (the bfloat16 copy of the
# parameters); the rest are opened by the layers and models.
DEVICE_SCOPES = (
    "loss", "optimizer", "ema", "metrics", "param_cast",
    "gdn_conv", "gdn_scan", "attn_gated",
    "ssm_conv", "ssm_scan", "attn_plain",
    "attn_global", "attn_window", "mlp_dense",
    "moe_route", "moe_experts", "moe_shared",
    "lm_loss",
)
PHASES = ("forward", "recompute", "backward", "optimizer", "ema", "other")
_TRANSFORMS = ("jvp(", "transpose(", "vmap(")
# Path tokens that say how an op was reached, not which module it is in.
_PLUMBING = frozenset((
    "checkpoint", "rematted_computation", "closed_call", "while", "body",
    "cond", "core_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "pjit", "remat", "scan"))
# Instructions that are no work of their own: never an event of a trace.
_NO_WORK = frozenset(("parameter", "constant", "tuple", "get-tuple-element"))
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_FIRST_OPERAND = re.compile(r"\(%?([A-Za-z_][\w.\-]*)")
_INHERIT_HOPS = 8


def _split_path(op_name: str) -> List[str]:
  """`a/jvp(b/c)/d` -> [`a`, `jvp(b/c)`, `d`]: split at the slashes that
  are inside no parenthesis."""
  tokens, depth, start = [], 0, 0
  for i, c in enumerate(op_name):
    if c == "(":
      depth += 1
    elif c == ")":
      depth -= 1
    elif c == "/" and depth == 0:
      tokens.append(op_name[start:i])
      start = i + 1
  tokens.append(op_name[start:])
  return [t for t in tokens if t]


def _bare(token: str) -> str:
  """`transpose(jvp(lm_loss))` -> `lm_loss`: a scope's name as it was
  declared, whatever autodiff wrapped around it."""
  while token.endswith(")") and token.startswith(_TRANSFORMS):
    token = token[token.index("(") + 1:-1]
  return token


def classify_op_name(op_name: str) -> Tuple[str, str, str]:
  """(`phase`, `scope`, `path`) of one instruction, from its `op_name`.

  The rule, read off the compiled train steps of the four benchmark
  cells (JAX 0.9.0; a `value_and_grad` over a `jax.checkpoint`ed
  forward, inside `named_scope("loss")`):

      jit(t2r_train_step)/loss/jvp(_HybridDecoder)/layer_0/mixer/ssm_scan/...            forward
      .../loss/transpose(jvp(_HybridDecoder))/loss/jvp(_HybridDecoder)/checkpoint/rematted_computation/layer_0/...  recompute
      .../loss/transpose(jvp(_HybridDecoder))/loss/jvp(_HybridDecoder)/checkpoint/layer_0/...   backward
      .../loss/transpose(jvp(lm_loss))/while/body/...                                     backward
      jit(t2r_train_step)/optimizer/mul                                                   optimizer

  so, on the path's tokens (split at `/` outside parentheses): a token
  whose bare name (`jvp(...)`, `transpose(...)`, `vmap(...)` stripped)
  is `optimizer` or `ema` makes that phase; else a `rematted_computation`
  makes `recompute` (autodiff only recomputes inside a backward pass);
  else a token that starts with `transpose(` makes `backward` (the
  primitive `transpose`, a path's last token, has no parenthesis); else
  a `loss` scope or any `jvp(` makes `forward`; anything else (the
  step's rng, its counters, the `metrics` scope, an instruction with no
  `op_name`) is `other`.

  `scope` is the innermost token whose bare name is in `DEVICE_SCOPES`
  (`` where none is). `path` is what is left of the tokens once the
  `jit(...)` root, the autodiff wrappers, the declared scopes, the
  plumbing (`checkpoint`, `while/body`, `closed_call`, ...) and the last
  token, the primitive, are dropped: Flax's module path,
  `layer_3/moe/router`. Of names XLA joined (`x;y`) the first counts.
  """
  return _classify_head(op_name.split(";", 1)[0].rpartition("/")[0])


@functools.lru_cache(maxsize=None)
def _classify_head(head: str) -> Tuple[str, str, str]:
  """`classify_op_name` on an `op_name` less its last token, the
  primitive: a step has a few thousand distinct ones."""
  tokens = _split_path(head)
  bare = [_bare(t) for t in tokens]
  if "optimizer" in bare:
    phase = "optimizer"
  elif "ema" in bare:
    phase = "ema"
  elif "rematted_computation" in tokens:
    phase = "recompute"
  elif any(t.startswith("transpose(") for t in tokens):
    phase = "backward"
  elif "loss" in bare or any(t.startswith("jvp(") for t in tokens):
    phase = "forward"
  else:
    phase = "other"
  scope = next((b for b in reversed(bare) if b in DEVICE_SCOPES), "")
  path = "/".join(
      t for t, b in zip(tokens, bare)
      if "(" not in t and b not in DEVICE_SCOPES and t not in _PLUMBING
      and not t.startswith("branch_"))
  return phase, scope, path


def _opcode(rest: str) -> str:
  """The opcode of `<shape> <opcode>(<operands>), ...`; the shape may be
  a tuple with parentheses of its own."""
  if rest.startswith("("):
    depth = 0
    for i, c in enumerate(rest):
      depth += c == "("
      depth -= c == ")"
      if depth == 0:
        rest = rest[i + 1:]
        break
  else:
    rest = rest.partition(" ")[2]
  return rest.lstrip().partition("(")[0].strip()


def build_op_table(hlo_text: str, executable: str = "") -> Dict[str, Any]:
  """The op table of one compiled executable, from its text
  (`compiled.as_text()`): JSON-safe, so it persists beside the cache
  entry.

      {"executable", "module",                 # `jit_t2r_train_step`
       "paths": [[phase, scope, path], ...],   # each distinct triple once
       "ops": {name: [opcode, index into paths, fused phases or None]}}

  `ops` holds every instruction of every computation, loop bodies and
  fused computations too, under its own name as a trace shows it (no
  `%`), but for the four opcodes that are no work (`parameter`,
  `constant`, `tuple`, `get-tuple-element`). A fusion also gets the
  phases its fused instructions carry (those with an `op_name`), in
  `PHASES`' order: more than one and `op_entry` reads it as `mixed`. Its
  own phase stays its own name's, which is XLA's choice among its parts:
  XLA fuses Adam's update into the product that makes the weight's
  gradient and copies cheap forward passes into backward fusions, and no
  rule splits such a fusion's time (the later phase would call g44's
  weight-gradient convolutions the moving average's, 28.6 ms of a step
  whose whole update is under a millisecond: my chip run, PR 37), so
  `device_time_by_scope` also says how much time each phase shares.

  XLA gives some instructions no `op_name` (a fusion it built, a copy it
  inserted, the two halves of an asynchronous copy or slice) or a bare
  one (`gather`). Such a fusion takes the commonest (phase, scope, path)
  of its fused instructions; any other takes those of the instruction
  that made its first operand, followed back up to `_INHERIT_HOPS`
  instructions: the names go with the data. What still has none is
  `other`. Read the table with `op_entry`.
  """
  nameless = ("other", "", "")
  module = ""
  inherits = set()  # instructions with no `op_name`, or a bare primitive's
  triples: Dict[str, Tuple[str, str, str]] = {}   # of every instruction
  first_operand: Dict[str, str] = {}
  opcodes: Dict[str, str] = {}
  fused_in: Dict[str, Dict[Tuple[str, str, str], int]] = {}
  fusions: Dict[str, str] = {}
  current = None
  for line in hlo_text.splitlines():
    if line.startswith("HloModule "):
      module = line.split()[1].rstrip(",")
      continue
    if not line.startswith(" "):
      match = _COMPUTATION.match(line)
      current = match.group(1) if match else None
      continue
    match = _INSTRUCTION.match(line)
    if not match:
      continue
    name, rest = match.groups()
    opcode = _opcode(rest)
    found = _OP_NAME.search(rest)
    if found and "/" in found.group(1):
      triple = classify_op_name(found.group(1))
      counts = fused_in.setdefault(current, {})
      counts[triple] = counts.get(triple, 0) + 1
    else:
      triple = nameless
      inherits.add(name)
      at = rest.find(opcode + "(")
      operand = _FIRST_OPERAND.search(rest, at) if at >= 0 else None
      if operand:
        first_operand[name] = operand.group(1)
    triples[name] = triple
    if opcode in _NO_WORK:
      continue
    opcodes[name] = opcode
    if opcode == "fusion":
      called = _CALLS.search(rest)
      if called:
        fusions[name] = called.group(1)
  fused_phases = {}
  for name, called in fusions.items():
    counts = fused_in.get(called)
    if counts:
      phases = sorted({t[0] for t in counts}, key=PHASES.index)
      fused_phases[name] = phases
      if name in inherits:
        triples[name] = max(sorted(counts), key=counts.get)
        inherits.discard(name)
  for name in opcodes:
    at, hops = name, 0
    while at in inherits and at in first_operand and hops < _INHERIT_HOPS:
      at, hops = first_operand[at], hops + 1
    if hops:
      triples[name] = triples.get(at, nameless)
  paths: Dict[Tuple[str, str, str], int] = {}
  ops = {name: [opcode, paths.setdefault(triples[name], len(paths)),
                fused_phases.get(name)]
         for name, opcode in opcodes.items()}
  return {"executable": executable, "module": module,
          "paths": [list(t) for t in sorted(paths, key=paths.get)],
          "ops": ops}


def _own_name(event_name: str) -> str:
  """`%fusion.3 = f32[8]{0} fusion(...)` -> `fusion.3`."""
  return event_name.partition(" = ")[0].strip().lstrip("%")


def op_entry(table: Dict[str, Any], name: str) -> Optional[Dict[str, Any]]:
  """One instruction of a table: {"name", "opcode", "phase", "scope",
  "path", "phases"} or None where the table lacks the name. `name` may
  be a trace event's whole text (`%fusion.3 = f32[8]{0} fusion(...)`).
  `phases` is None for all but fusions, `"mixed"` for a fusion of
  instructions of more than one phase (which ones: `fused_phases`),
  else that one phase."""
  own = _own_name(name)
  found = table["ops"].get(own)
  if found is None:
    return None
  opcode, index, fused = found
  phase, scope, path = table["paths"][index]
  phases = None if fused is None else (
      "mixed" if len(fused) > 1 else fused[0])
  entry = {"name": own, "opcode": opcode, "phase": phase, "scope": scope,
           "path": path, "phases": phases}
  if phases == "mixed":
    entry["fused_phases"] = list(fused)
  return entry


def op_scopes(name: str) -> Optional[Dict[str, Any]]:
  """The op table of the executable `analyze_jit` last analysed under
  `name` in this process (`train_step`), or None: no analysis yet, a
  cache entry with no table beside it, or a failure while building it
  (counted in `xray/analyze_failures`). The table's
  `module` is the HLO module's name, which a profiler trace shows on
  its module line (`jit_t2r_train_step`). Cleared by `clear_records`."""
  with _LOCK:
    return _OP_TABLES.get(name)


def _keep_op_table(name: str, table: Optional[Dict[str, Any]]) -> None:
  with _LOCK:
    if table is None:
      _OP_TABLES.pop(name, None)
    else:
      _OP_TABLES[name] = table


def _table_from_executable(name: str, compiled,
                           reg) -> Optional[Dict[str, Any]]:
  """The executable's table or None. Never raises: a failure leaves the
  table absent and the run untouched."""
  try:
    table = build_op_table(compiled.as_text(), executable=name)
    if not table["ops"]:
      table = None
  except Exception as e:  # noqa: BLE001 - telemetry never breaks the run
    table = None
    reg.counter("xray/analyze_failures").inc()
    print(f"graftscope-xray: no op table for {name!r} "
          f"({type(e).__name__}: {e})", file=sys.stderr)
  return table


def device_time_by_scope(op_events, table: Dict[str, Any],
                         module_events) -> Dict[str, Any]:
  """Lays the op table over one device's op line: seconds by phase, by
  scope and by module.

  `op_events` are `(name, start_ns, duration_ns)` of ONE device's op line
  of a profiler trace (`name` the instruction's whole text or its own
  name); `module_events` the same of its module line. Only the ops that
  start inside an execution of the table's module count, and `steps`
  says how many executions there were. Top-level ops only: an
  op nested inside an earlier one (a loop's body inside its `while`, a
  fusion's parts) is part of that one, which counts whole under its own
  phase and scope. Pure Python, no jax. Returns, in seconds,

      {"steps", "ops", "total_s",
       "by_phase": {phase: s}, "copy_by_phase": {phase: s},
       "shared_by_phase": {phase: s},  # of all ops that hold the phase
       "by_scope": {scope: s}, "copy_by_scope": {scope: s},   # `` = none
       "groups": [{"phase", "scope", "path", "seconds", "copy_s", "ops"}],
       "mixed_s":   under fusions whose parts are of more than one phase,
       "mixed_by_phases": {"backward+optimizer": s},   # which, of mixed_s
       "unscoped_s": phase `other` under no declared scope,
       "unknown_s", "unknown": {name: s},   # names the table lacks
       "top_ops": [{"name", "opcode", "phase", "scope", "path", "seconds"}]}

  `groups` is by (phase, scope, the path's first two tokens), heaviest
  first; `top_ops` and `unknown` are the twelve heaviest; `copy_*` is the part of each under `copy*` opcodes.
  The phases of `by_phase` and `unknown_s` add up to `total_s`. An op
  counts once, under its own phase; `shared_by_phase` counts a mixed
  fusion under every phase its parts carry, so a phase costs between
  `by_phase` less what is mixed in it and `shared_by_phase`.
  """
  events = sorted(((s, -d, n) for n, s, d in op_events if d > 0))
  runs = sorted((s, s + d) for n, s, d in module_events
                if n.split("(", 1)[0] == table.get("module"))
  by_phase = {p: 0.0 for p in PHASES}
  shared_by_phase = {p: 0.0 for p in PHASES}
  copy_by_phase: Dict[str, float] = {}
  by_scope: Dict[str, float] = {}
  copy_by_scope: Dict[str, float] = {}
  groups: Dict[Tuple[str, str, str], List[float]] = {}
  unknown: Dict[str, float] = {}
  by_name: Dict[str, float] = {}
  mixed: Dict[str, float] = {}
  unscoped_s = 0.0
  count, end, run_at = 0, float("-inf"), 0
  for start, neg, name in events:
    if start < end:
      continue  # nested inside the op before it
    while run_at < len(runs) and runs[run_at][1] <= start:
      run_at += 1
    if run_at == len(runs) or start < runs[run_at][0]:
      continue  # another program's op
    end = start - neg
    seconds = -neg / 1e9
    count += 1
    entry = op_entry(table, name)
    if entry is None:
      own = _own_name(name)
      unknown[own] = unknown.get(own, 0.0) + seconds
      continue
    phase, scope = entry["phase"], entry["scope"]
    by_name[entry["name"]] = by_name.get(entry["name"], 0.0) + seconds
    by_phase[phase] += seconds
    by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    key = (phase, scope, "/".join(entry["path"].split("/")[:2]))
    group = groups.setdefault(key, [0.0, 0.0, 0])
    group[0] += seconds
    group[2] += 1
    if entry["opcode"].startswith("copy"):
      group[1] += seconds
      copy_by_phase[phase] = copy_by_phase.get(phase, 0.0) + seconds
      copy_by_scope[scope] = copy_by_scope.get(scope, 0.0) + seconds
    if entry["phases"] == "mixed":
      fused = "+".join(entry["fused_phases"])
      mixed[fused] = mixed.get(fused, 0.0) + seconds
    for held in set(entry.get("fused_phases", ())) | {phase}:
      shared_by_phase[held] += seconds
    if phase == "other" and not scope:
      unscoped_s += seconds
  unknown_s = sum(unknown.values())
  heaviest = sorted(by_name.items(), key=lambda kv: -kv[1])[:12]
  return {
      "steps": len(runs), "ops": count,
      "total_s": sum(by_phase.values()) + unknown_s,
      "by_phase": by_phase, "copy_by_phase": copy_by_phase,
      "shared_by_phase": shared_by_phase,
      "by_scope": by_scope, "copy_by_scope": copy_by_scope,
      "groups": [
          {"phase": k[0], "scope": k[1], "path": k[2], "seconds": v[0],
           "copy_s": v[1], "ops": v[2]}
          for k, v in sorted(groups.items(), key=lambda kv: -kv[1][0])],
      "mixed_s": sum(mixed.values()), "mixed_by_phases": mixed,
      "unscoped_s": unscoped_s,
      "unknown_s": unknown_s,
      "unknown": dict(sorted(unknown.items(), key=lambda kv: -kv[1])[:12]),
      "top_ops": [dict(op_entry(table, n), seconds=s) for n, s in heaviest],
  }


def format_device_scopes(reduced: Dict[str, Any]) -> List[str]:
  """`device_time_by_scope`'s result as lines of text, milliseconds a
  step: the phases, the scopes, the forty heaviest groups with the
  copies in each, the heaviest ops."""
  per = 1e3 / (reduced["steps"] or 1)
  total = reduced["total_s"] or 1e-30
  lines = [f"device time by phase and scope (ms a step; "
           f"{reduced['steps']} steps, {reduced['ops']} top-level ops, "
           f"total {reduced['total_s'] * per:.3f})"]
  for phase in PHASES:
    seconds = reduced["by_phase"].get(phase, 0.0)
    lines.append(
        f"  phase {phase:<10}{seconds * per:>10.3f}"
        f"{100 * seconds / total:>7.2f} %   copies "
        f"{reduced['copy_by_phase'].get(phase, 0.0) * per:.3f}   held by "
        f"{reduced.get('shared_by_phase', {}).get(phase, 0.0) * per:.3f}")
  lines.append(
      f"  unknown names   {reduced['unknown_s'] * per:>10.3f}   unscoped "
      f"{reduced['unscoped_s'] * per:.3f}   mixed fusions "
      f"{reduced['mixed_s'] * per:.3f}")
  for fused, seconds in sorted(reduced.get("mixed_by_phases", {}).items(),
                               key=lambda kv: -kv[1]):
    lines.append(f"  mixed {fused:<34}{seconds * per:>10.3f}")
  for scope, seconds in sorted(reduced["by_scope"].items(),
                               key=lambda kv: -kv[1]):
    lines.append(
        f"  scope {scope or '(none)':<14}{seconds * per:>10.3f}   copies "
        f"{reduced['copy_by_scope'].get(scope, 0.0) * per:.3f}")
  lines.append(f"  {'phase':<10}{'scope':<14}{'path':<34}{'ms':>9}"
               f"{'copies':>9}{'ops':>7}")
  for group in reduced["groups"][:40]:
    lines.append(
        f"  {group['phase']:<10}{group['scope'] or '-':<14}"
        f"{group['path'] or '-':<34}{group['seconds'] * per:>9.3f}"
        f"{group['copy_s'] * per:>9.3f}{group['ops']:>7}")
  for op in reduced["top_ops"]:
    lines.append(
        f"  op {op['name']:<28}{op['seconds'] * per:>9.3f}  {op['opcode']} "
        f"{op['phase']} {op['scope'] or '-'} {op['path'] or '-'}"
        + (" (mixed)" if op["phases"] == "mixed" else ""))
  for name, seconds in reduced["unknown"].items():
    lines.append(f"  unknown {name:<28}{seconds * per:>9.3f}")
  return lines


def read_device_lines(trace_dir: str) -> Optional[Dict[str, list]]:
  """{"modules": [...], "ops": [...]}: `(name, start_ns, duration_ns)` of
  the `XLA Modules` and `XLA Ops` lines of the first device plane that
  has an op line, in the newest `jax.profiler` trace under `trace_dir`;
  None where the trace has no such plane (a CPU run) or there is no
  trace."""
  import glob
  import os

  import jax

  files = sorted(glob.glob(os.path.join(
      trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
  if not files:
    return None
  data = jax.profiler.ProfileData.from_file(files[-1])
  for plane in sorted((p for p in data.planes
                       if p.name.startswith("/device:")),
                      key=lambda p: p.name):
    lines = {line.name: line for line in plane.lines}
    if "XLA Ops" in lines:  # a chip's plane, not a runtime's beside it
      return {key: [(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in lines[name].events] if name in lines else []
              for key, name in (("modules", "XLA Modules"),
                                ("ops", "XLA Ops"))}
  return None


def analyze_jit(name: str, fn, *args,
                registry: Optional[metrics_lib.Registry] = None,
                collect: bool = True,
                cache=None) -> Tuple[Any, Dict[str, Any]]:
  """AOT trace->lower->compile of a jitted `fn` at `args`, instrumented.

  Returns `(compiled, record)` where `compiled` is the executable
  (callable with the same signature and shardings/donation as `fn`) and
  `record` is a JSON-safe dict: per-phase times (`trace_s`, `lower_s`,
  `compile_s`), `jaxpr_eqns`, declared `donated_bytes` /
  `undonated_bytes`, XLA `flops` / `bytes_accessed` (None where the
  backend reports none), memory analysis (`temp_bytes`, `output_bytes`,
  `argument_bytes`, `generated_code_bytes`), the derived
  `arithmetic_intensity` (FLOPs/byte) + `roofline_ms`. The op table is
  kept for `op_scopes(name)` and stays out of the record; what it cost
  is the `xray/op_scopes` span.

  Spans (`obs.trace`, children of whatever span is open: the
  `xray/analyze` of `XrayedFunction`), from the clocks read here anyway:
  `xray/trace`, then `xray/lower` and `xray/compile` or, on a cache hit,
  `xray/cache_load`, then `xray/op_scopes` (the executable's text read
  and the table built; on a hit the table loaded from beside the entry).

  `roofline_ms` always prices against the project's one real device
  class (v5e public peaks, `utils.backend`), whatever backend compiled
  the executable — it answers "what SHOULD this step cost on the chip",
  which is exactly the number the round-5 valley violated 16x.

  `cache` (an `obs.excache.ExecutableCache` or a directory path)
  short-circuits lower+compile with a persisted executable when the
  content-addressed key (jaxpr fingerprint, abstract shapes/dtypes/
  shardings, donation layout, static args, device topology, backend
  version) hits: the record then carries the COLD process's cost/memory
  analysis plus a `cache` block (`{hit, key, load_ms, bytes}`) and
  `lower_s == compile_s == 0`. A load failure of any kind — corrupt
  blob, version skew, key trouble — falls back to the fresh compile
  below (cache trouble must never take down the run, the same contract
  as every other telemetry path here); a miss stores the fresh
  executable for the next process.

  Raises on (compile) failure — callers that must not die use
  `XrayedFunction` (or wrap in try/except) and keep the plain jitted fn.
  """
  from tensor2robot_tpu.obs import excache as excache_lib

  reg = registry or metrics_lib.get_registry()
  tracer = trace_lib.get_tracer()
  who = {"executable": name}
  cache = excache_lib.as_cache(cache)
  t0 = time.perf_counter_ns()
  traced = fn.trace(*args)
  t1 = time.perf_counter_ns()
  tracer.add_complete("xray/trace", t0, t1 - t0, cat="xray", args=who)

  cache_key = None
  if cache is not None:
    try:
      cache_key = excache_lib.cache_key(
          name, **excache_lib.key_components_from_traced(traced, args))
    except Exception as e:  # noqa: BLE001 - key trouble = no caching
      reg.counter("cache/key_failures").inc()
      print(f"graftcache: key computation for {name!r} failed "
            f"({type(e).__name__}: {e}); compiling fresh",
            file=sys.stderr)
    if cache_key is not None:
      load_ns = time.perf_counter_ns()
      entry = cache.load(cache_key)
      if entry is not None:
        loaded_ns = time.perf_counter_ns()
        tracer.add_complete("xray/cache_load", load_ns, loaded_ns - load_ns,
                            cat="xray", args=who)
        table = cache.load_op_scopes(cache_key)  # `store` put it there
        tabled_ns = time.perf_counter_ns()
        tracer.add_complete("xray/op_scopes", loaded_ns,
                            tabled_ns - loaded_ns, cat="xray", args=who)
        _keep_op_table(name, table)
        donated, undonated = _donation_bytes(traced, args)
        record = dict(entry["record"])
        record.update({
            "name": name,
            "trace_s": (t1 - t0) / 1e9,
            "lower_s": 0.0,
            "compile_s": 0.0,
            "jaxpr_eqns": _count_eqns(traced.jaxpr),
            "donated_bytes": donated,
            "undonated_bytes": undonated,
            "cache": {"hit": True, "key": cache_key,
                      "load_ms": entry["load_ms"],
                      "bytes": entry["bytes"]},
        })
        record.setdefault("flops", None)
        record.setdefault("bytes_accessed", None)
        reg.counter("xray/analyses").inc()
        if collect:
          _collect(record)
        return entry["compiled"], record

  lowered = traced.lower()
  t2 = time.perf_counter_ns()
  if cache is not None and cache_key is not None:
    # An AOT-tier miss about to be stored compiles WITHOUT the XLA
    # persistent cache: an executable served out of that cache does not
    # survive the serialize round-trip, so the entry could never
    # (re)fill — see excache.xla_cache_bypassed.
    with excache_lib.xla_cache_bypassed():
      compiled = lowered.compile()
  else:
    compiled = lowered.compile()
  t3 = time.perf_counter_ns()
  tracer.add_complete("xray/lower", t1, t2 - t1, cat="xray", args=who)
  tracer.add_complete("xray/compile", t2, t3 - t2, cat="xray", args=who)
  table = _table_from_executable(name, compiled, reg)
  tracer.add_complete("xray/op_scopes", t3, time.perf_counter_ns() - t3,
                      cat="xray", args=who)
  _keep_op_table(name, table)

  donated, undonated = _donation_bytes(traced, args)
  record: Dict[str, Any] = {
      "name": name,
      "trace_s": (t1 - t0) / 1e9,
      "lower_s": (t2 - t1) / 1e9,
      "compile_s": (t3 - t2) / 1e9,
      "jaxpr_eqns": _count_eqns(traced.jaxpr),
      "donated_bytes": donated,
      "undonated_bytes": undonated,
  }
  flops = bytes_accessed = None
  try:
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
    if "flops" in cost:
      flops = float(cost["flops"])
    if "bytes accessed" in cost:
      bytes_accessed = float(cost["bytes accessed"])
  except Exception:  # noqa: BLE001 - cost analysis is backend-optional
    pass
  record["flops"] = flops
  record["bytes_accessed"] = bytes_accessed
  # flops == 0.0 is a valid answer (copy/gather-dominated executables):
  # the memory-bound roofline bytes/BW is exactly the health-check
  # number then, so only a missing/zero bytes figure disables it.
  if flops is not None and bytes_accessed:
    record["arithmetic_intensity"] = flops / bytes_accessed
    record["roofline_ms"] = 1e3 * max(
        flops / backend_lib.V5E_PEAK_BF16_FLOPS,
        bytes_accessed / backend_lib.V5E_PEAK_HBM_BW)
    record["peak_flops"] = backend_lib.V5E_PEAK_BF16_FLOPS
    record["peak_hbm_bw"] = backend_lib.V5E_PEAK_HBM_BW
  try:
    mem = compiled.memory_analysis()
    if mem is not None:
      record["temp_bytes"] = float(mem.temp_size_in_bytes)
      record["output_bytes"] = float(mem.output_size_in_bytes)
      record["argument_bytes"] = float(mem.argument_size_in_bytes)
      record["generated_code_bytes"] = float(
          mem.generated_code_size_in_bytes)
  except Exception:  # noqa: BLE001 - memory analysis is backend-optional
    pass

  if cache is not None and cache_key is not None:
    # Persist for the NEXT process (best-effort, counted); the stored
    # sidecar carries this record so a warm start keeps full compile
    # telemetry without paying the compile, and the op table goes
    # beside it.
    stored = cache.store(cache_key, compiled, record=record, name=name,
                         op_scopes=table)
    record["cache"] = {"hit": False, "key": cache_key, "stored": stored}

  reg.counter("xray/analyses").inc()
  if collect:
    _collect(record)
  return compiled, record


class XrayedFunction:
  """Lazily X-rays a jitted fn on its first call; never breaks the call.

  The first invocation runs `analyze_jit` at the live arguments and
  keeps the AOT executable for every later call (the same compile the
  plain jit would have paid on first dispatch — no double work, the
  plain path never compiles). Any failure — no AOT support, a backend
  without cost analysis, a later call at different shapes that the
  frozen executable rejects — permanently degrades to the plain jitted
  fn with a counter bump (`xray/analyze_failures` /
  `xray/compiled_call_fallbacks`), because telemetry must never take
  down a train loop or a serving path.
  """

  def __init__(self, name: str, fn,
               registry: Optional[metrics_lib.Registry] = None,
               cache=None):
    self._name = name
    self._fn = fn
    self._registry = registry or metrics_lib.get_registry()
    # graftcache seam: a persisted executable turns the first call's
    # compile into a deserialize (trainer restarts warm-start); all cache failure modes already degrade inside analyze_jit.
    self._cache = cache
    self._compiled = None
    self._record: Optional[Dict[str, Any]] = None
    self._failed = False
    self._lock = threading.Lock()

  @property
  def record(self) -> Optional[Dict[str, Any]]:
    return self._record

  def _analyze(self, args) -> None:
    with self._lock:
      if self._compiled is not None or self._failed:
        return
      # The compile-or-cache-load of the first call, as a child of the
      # caller's span (the trainer's first `train/dispatch`) and parent
      # of `analyze_jit`'s own (`xray/trace`, `xray/compile`, ...).
      span = trace_lib.get_tracer().open(
          "xray/analyze", cat="xray", executable=self._name, cache_hit=False)
      try:
        self._compiled, self._record = analyze_jit(
            self._name, self._fn, *args, registry=self._registry,
            cache=self._cache)
        span.set_arg("cache_hit", bool(
            (self._record.get("cache") or {}).get("hit")))
        span.close()
      except Exception as e:  # noqa: BLE001 - degrade, never break the call
        span.close()
        self._failed = True
        self._registry.counter("xray/analyze_failures").inc()
        from absl import logging

        logging.warning("graftscope-xray: analysis of %r unavailable "
                        "(%s: %s); running the plain jitted fn",
                        self._name, type(e).__name__, e)

  def __call__(self, *args):
    if self._compiled is None and not self._failed:
      self._analyze(args)
    compiled = self._compiled
    if compiled is None:
      return self._fn(*args)
    try:
      return compiled(*args)
    except Exception:  # noqa: BLE001 - e.g. new shapes vs frozen executable
      with self._lock:
        self._compiled = None
        self._failed = True
      # Retry on the plain jit ONLY while the inputs are intact — i.e.
      # the failure was a pre-execution rejection (shape/dtype mismatch
      # against the frozen executable). An execution-phase error on a
      # donating fn (e.g. jax_debug_nans) has already consumed its
      # donated buffers; retrying would mask the real error behind an
      # "Array has been deleted", so re-raise the original instead.
      import jax

      if any(getattr(leaf, "is_deleted", lambda: False)()
             for leaf in jax.tree_util.tree_leaves(args)):
        raise
      self._registry.counter("xray/compiled_call_fallbacks").inc()
      # The plain jit re-traces at the new shapes; a genuine math/user
      # error re-raises from here unchanged.
      return self._fn(*args)


# ---------------------------------------------------------------------------
# Memory accounting.
# ---------------------------------------------------------------------------


def memory_accounting(state=None, batch=None,
                      num_data_shards: Optional[int] = None
                      ) -> Dict[str, float]:
  """Prices a TrainState (+ optional batch) in bytes, global and
  per-shard.

  `state` is duck-typed on the TrainState fields (`params`,
  `opt_state`, `ema_params`, `mutable_state`); any may be absent.
  Per-shard bytes come from each leaf's committed sharding
  (`sharding.shard_shape`); replicated leaves cost full bytes per
  device. A HOST batch (numpy, no shardings) is divided by
  `num_data_shards` when given — the data-parallel placement estimate
  for batches that are not on device yet.
  """
  out: Dict[str, float] = {}
  state_total = state_shard = 0
  for field, key in (("params", "params"), ("opt_state", "opt_state"),
                     ("ema_params", "ema"), ("mutable_state", "mutable")):
    tree = getattr(state, field, None)
    if tree is None:
      continue
    total = pytree_bytes(tree)
    shard = pytree_shard_bytes(tree)
    out[f"{key}_bytes"] = float(total)
    out[f"{key}_bytes_per_shard"] = float(shard)
    state_total += total
    state_shard += shard
  if state is not None:
    out["state_bytes"] = float(state_total)
    out["state_bytes_per_shard"] = float(state_shard)
  if batch is not None:
    total = pytree_bytes(batch)
    shard = pytree_shard_bytes(batch)
    if shard == total and num_data_shards and num_data_shards > 1:
      shard = -(-total // num_data_shards)  # host batch: ceil split
    out["batch_bytes"] = float(total)
    out["batch_bytes_per_shard"] = float(shard)
  return out


def hbm_watermark_estimate(memory: Dict[str, float],
                           compile_records=()) -> float:
  """Per-device HBM watermark estimate in bytes.

  resident state + resident batch + the executable's scratch: XLA's
  `temp_bytes` when a compile record reports it, else the param bytes
  again (the gradient/update buffers a train step materializes — the
  floor for any backward pass). An ESTIMATE, not an allocator readout:
  its job is to say "b512 will not fit in 16 GB" BEFORE the probe OOMs
  blind, the way rounds 2–5 did.
  """
  temp = max((float(r.get("temp_bytes") or 0.0) for r in compile_records),
             default=0.0)
  scratch = max(temp, memory.get("params_bytes_per_shard", 0.0))
  return (memory.get("state_bytes_per_shard", 0.0)
          + memory.get("batch_bytes_per_shard", 0.0) + scratch)
