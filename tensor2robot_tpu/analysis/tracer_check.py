"""AST lint for backend-initialization, jit-tracing and device-timing
hazards in Python sources.

* `import-time-backend` — backend-touching calls at module import level
  (`jax.devices`, `jax.default_backend`, `jax.device_put`, any
  `jax.numpy` / `jax.random` / `jax.nn` call, …). Importing such a module
  initializes the backend — and takes the chip, which belongs to one
  process at a time — as a side effect of `import`. Module/class-level
  statements and function default arguments count;
  `if __name__ == "__main__"` blocks do not (script mains may touch
  hardware deliberately).
* `host-sync-in-jit`    — `.item()`, or `float()`/`int()`/`bool()`/
  `np.asarray()`/`np.array()` applied to a traced argument, inside a
  `jax.jit`/`pjit`-traced function: a host sync that fails or silently
  constant-folds under tracing.
* `impure-in-jit`       — `time.time`-family calls or stateful global
  `np.random.*` inside a traced function: traced once, frozen forever.
* `device-timing`       — a `time.time()`/`time.perf_counter()` clock
  pair (``t0 = time.perf_counter()`` … ``time.perf_counter() - t0``)
  whose window contains a device-dispatching call (`jnp.*`,
  `jax.lax.*`, `jax.device_put`, …) but no barrier: jax returns before
  the device finishes, so that measures DISPATCH, not execution. A
  window is closed by `jax.block_until_ready` (measured on the v5e in
  PR 22: a window it closes takes 0.9997 of one a host fetch closes —
  it IS a barrier) or by a host fetch (`backend.sync`/`state_barrier`,
  `np.asarray`, `jax.device_get`, `.item()`, `float()`). `obs/` is
  exempt — the one place allowed to own clocks around device code (its
  windows end in barriers by design).

A function is "traced" when decorated with `jax.jit`/`pjit` (directly or
via `functools.partial`), or passed by name/lambda to a `jax.jit(...)` /
`pjit(...)` call in an enclosing scope. Nested defs inherit tracedness.

Suppress with a trailing `# graftlint: disable=<rule>` comment.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from tensor2robot_tpu.analysis import engine as engine_lib
from tensor2robot_tpu.analysis.findings import (Finding, filter_findings,
                                                load_suppressions)

__all__ = ["check_python_source", "check_python_file"]

_JIT_NAMES = {
    "jax.jit", "jax.pjit", "jax.experimental.pjit.pjit",
    "jax.experimental.pjit",
}
_PARTIAL_NAMES = {"functools.partial", "partial"}

# Calls that initialize / query the backend or create device values.
_BACKEND_CALLS = {
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.default_backend", "jax.process_count",
    "jax.process_index", "jax.device_put", "jax.device_get",
    "jax.live_arrays", "jax.block_until_ready",
}
# Any call through these prefixes executes an op (= backend init when at
# import time).
_BACKEND_PREFIXES = ("jax.numpy.", "jax.random.", "jax.nn.", "jax.lax.")

_TIME_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "time.time_ns", "time.perf_counter_ns",
}
# numpy.random entry points that are NOT the stateful global RNG.
_NP_RANDOM_SAFE = {
    "RandomState", "Generator", "default_rng", "SeedSequence", "PCG64",
    "MT19937", "Philox", "SFC64", "BitGenerator",
}
_HOST_CONVERTERS = {"float", "int", "bool"}
_NP_HOST_CONVERTERS = {"numpy.asarray", "numpy.array", "numpy.asanyarray"}

# device-timing rule vocabulary: calls that dispatch device work (jax
# returns before it is done) vs calls that wait for the device.
_DISPATCH_CALLS = {"jax.device_put"}
_BARRIER_CALLS = _NP_HOST_CONVERTERS | {"jax.device_get", "float", "int",
                                        "jax.block_until_ready"}
# Method/attribute names that barrier regardless of the object they hang
# off (backend.sync, backend_lib.state_barrier, arr.item(),
# arr.block_until_ready()).
_BARRIER_ATTRS = {"sync", "state_barrier", "block_until_ready", "item"}


def _import_aliases(tree: ast.AST) -> Dict[str, str]:
  """name -> dotted module/attr path, from every import in the file."""
  aliases: Dict[str, str] = {}
  for node in ast.walk(tree):
    if isinstance(node, ast.Import):
      for alias in node.names:
        aliases[alias.asname or alias.name.split(".", 1)[0]] = (
            alias.name if alias.asname else alias.name.split(".", 1)[0])
    elif isinstance(node, ast.ImportFrom) and not node.level:
      for alias in node.names:
        if node.module:
          aliases[alias.asname or alias.name] = (
              f"{node.module}.{alias.name}")
  return aliases


def _qualified(node: ast.AST, aliases: Dict[str, str]) -> Optional[str]:
  """Dotted name of an expression like `jnp.asarray` -> 'jax.numpy.asarray'."""
  parts: List[str] = []
  while isinstance(node, ast.Attribute):
    parts.append(node.attr)
    node = node.value
  if not isinstance(node, ast.Name):
    return None
  root = aliases.get(node.id, node.id)
  return ".".join([root] + list(reversed(parts)))


def _root_name(node: ast.AST) -> Optional[str]:
  """Base variable of `x`, `x.attr`, `x[i]`, `x.attr[i]` chains."""
  while isinstance(node, (ast.Attribute, ast.Subscript)):
    node = node.value
  return node.id if isinstance(node, ast.Name) else None


def _is_jit_expr(node: ast.AST, aliases: Dict[str, str]) -> bool:
  """True for `jax.jit`, `pjit`, `functools.partial(jax.jit, ...)`."""
  q = _qualified(node, aliases)
  if q in _JIT_NAMES or (q is not None and q.split(".")[-1] == "pjit"):
    return True
  if isinstance(node, ast.Call):
    fq = _qualified(node.func, aliases)
    if fq in _JIT_NAMES or (fq is not None and fq.split(".")[-1] == "pjit"):
      return True  # jax.jit(static_argnums=...) factory style
    if fq in _PARTIAL_NAMES and node.args and _is_jit_expr(node.args[0],
                                                           aliases):
      return True
  return False


class _TracedCollector(ast.NodeVisitor):
  """Finds function nodes whose bodies run under jit tracing."""

  def __init__(self, aliases: Dict[str, str]):
    self.aliases = aliases
    self.traced: List[ast.AST] = []
    # Stack of {local def name -> node} scopes for resolving jax.jit(f).
    self._scopes: List[Dict[str, ast.AST]] = [{}]

  def _handle_def(self, node):
    self._scopes[-1][node.name] = node
    if any(_is_jit_expr(d, self.aliases) for d in node.decorator_list):
      self.traced.append(node)
    self._scopes.append({})
    self.generic_visit(node)
    self._scopes.pop()

  visit_FunctionDef = _handle_def
  visit_AsyncFunctionDef = _handle_def

  def visit_ClassDef(self, node):
    self._scopes.append({})
    self.generic_visit(node)
    self._scopes.pop()

  def visit_Call(self, node):
    if _is_jit_expr(node.func, self.aliases) and node.args:
      target = node.args[0]
      if isinstance(target, ast.Lambda):
        self.traced.append(target)
      elif isinstance(target, ast.Name):
        for scope in reversed(self._scopes):
          if target.id in scope:
            self.traced.append(scope[target.id])
            break
    self.generic_visit(node)


def _walk_traced(node: ast.AST, aliases: Dict[str, str], path: str,
                 findings: List[Finding]) -> None:
  """Applies the in-jit rules over one traced function's subtree."""
  params: Set[str] = set()

  def _add_params(fn_node) -> None:
    if isinstance(fn_node, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
      a = fn_node.args
      for arg in (list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
                  + ([a.vararg] if a.vararg else [])
                  + ([a.kwarg] if a.kwarg else [])):
        params.add(arg.arg)

  _add_params(node)

  def _visit(n: ast.AST) -> None:
    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
      _add_params(n)  # nested defs trace too; their args are tracers
    if isinstance(n, ast.Call):
      q = _qualified(n.func, aliases)
      if (isinstance(n.func, ast.Attribute) and n.func.attr == "item"
          and not n.args and not n.keywords):
        findings.append(Finding(
            path, n.lineno, "host-sync-in-jit",
            ".item() inside a jit-traced function is a host sync — "
            "return the array and convert outside the jit boundary",
            end_line=getattr(n, "end_lineno", 0) or 0))
      elif (q in _HOST_CONVERTERS or q in _NP_HOST_CONVERTERS) and n.args:
        root = _root_name(n.args[0])
        if root is not None and root in params:
          findings.append(Finding(
              path, n.lineno, "host-sync-in-jit",
              f"{q}() on traced argument {root!r} inside a jit-traced "
              "function forces a host sync (or silently freezes a "
              "tracer) — use jnp ops or move it outside the jit",
              end_line=getattr(n, "end_lineno", 0) or 0))
      elif q in _TIME_CALLS:
        findings.append(Finding(
            path, n.lineno, "impure-in-jit",
            f"{q}() inside a jit-traced function is evaluated once at "
            "trace time and frozen into the compiled program",
            end_line=getattr(n, "end_lineno", 0) or 0))
      elif (q is not None and q.startswith("numpy.random.")
            and q.split(".")[-1] not in _NP_RANDOM_SAFE):
        findings.append(Finding(
            path, n.lineno, "impure-in-jit",
            f"stateful {q}() inside a jit-traced function is drawn once "
            "at trace time and frozen — use jax.random with an explicit "
            "key", end_line=getattr(n, "end_lineno", 0) or 0))
    for child in ast.iter_child_nodes(n):
      _visit(child)

  for child in ast.iter_child_nodes(node):
    _visit(child)


def _check_import_time(tree: ast.Module, aliases: Dict[str, str],
                       path: str, findings: List[Finding]) -> None:
  """Flags backend-touching calls executed as a side effect of import."""

  def _is_main_guard(node: ast.AST) -> bool:
    return (isinstance(node, ast.If)
            and isinstance(node.test, ast.Compare)
            and isinstance(node.test.left, ast.Name)
            and node.test.left.id == "__name__")

  def _flag_calls(n: ast.AST) -> None:
    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
      # Body runs later — but default arguments AND decorator
      # expressions evaluate at import time.
      defaults = list(n.args.defaults) + [d for d in n.args.kw_defaults
                                          if d is not None]
      if not isinstance(n, ast.Lambda):
        defaults.extend(n.decorator_list)
      for d in defaults:
        _flag_calls_expr(d)
      return
    if _is_main_guard(n):
      return
    if isinstance(n, ast.Call):
      _flag_call(n)
    for child in ast.iter_child_nodes(n):
      _flag_calls(child)

  def _flag_calls_expr(n: ast.AST) -> None:
    for sub in ast.walk(n):
      if isinstance(sub, ast.Call):
        _flag_call(sub)

  def _flag_call(n: ast.Call) -> None:
    q = _qualified(n.func, aliases)
    if q is None:
      return
    if q in _BACKEND_CALLS or q.startswith(_BACKEND_PREFIXES):
      findings.append(Finding(
          path, n.lineno, "import-time-backend",
          f"{q}() at module import level initializes the JAX backend "
          "(and takes the chip) as an import side "
          "effect — build the value lazily or use numpy",
          end_line=getattr(n, "end_lineno", 0) or 0))

  for stmt in tree.body:
    _flag_calls(stmt)


def _check_device_timing(tree: ast.Module, aliases: Dict[str, str],
                         path: str, findings: List[Finding]) -> None:
  """Flags host-clock windows around un-barriered device dispatches.

  Pattern: ``t0 = time.perf_counter()`` … ``time.perf_counter() - t0``
  within one scope, with a device-dispatching call between the two clock
  reads and no barrier. Each function is its own scope
  (nested defs do not execute inside the enclosing window)."""

  def _is_clock_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and _qualified(node.func, aliases) in _TIME_CALLS)

  def _scope_statements(scope: ast.AST):
    """Yields every node in the scope, skipping nested function bodies."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
      node = stack.pop()
      yield node
      if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)):
        stack.extend(ast.iter_child_nodes(node))

  def _check_scope(scope: ast.AST) -> None:
    clock_assigns: Dict[str, List[int]] = {}
    closes: List[tuple] = []  # (varname, line, end_line)
    calls: List[tuple] = []  # (line, qualified, attr_name)
    for node in _scope_statements(scope):
      if (isinstance(node, ast.Assign) and _is_clock_call(node.value)
          and len(node.targets) == 1
          and isinstance(node.targets[0], ast.Name)):
        clock_assigns.setdefault(node.targets[0].id, []).append(node.lineno)
      elif (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)
            and isinstance(node.right, ast.Name)
            and (_is_clock_call(node.left)
                 or isinstance(node.left, ast.Name))):
        closes.append((node.right.id, node.lineno,
                       getattr(node, "end_lineno", 0) or node.lineno))
      if isinstance(node, ast.Call):
        attr = (node.func.attr
                if isinstance(node.func, ast.Attribute) else None)
        calls.append((node.lineno, _qualified(node.func, aliases), attr))
    for var, line, end_line in closes:
      starts = [s for s in clock_assigns.get(var, []) if s < line]
      if not starts:
        continue
      start = max(starts)
      window = [(q, attr) for (call_line, q, attr) in calls
                if start < call_line <= end_line]
      dispatches = [q for q, _ in window if q is not None
                    and (q in _DISPATCH_CALLS
                         or q.startswith(_BACKEND_PREFIXES))]
      barriered = any((q in _BARRIER_CALLS if q is not None else False)
                      or attr in _BARRIER_ATTRS for q, attr in window)
      if dispatches and not barriered:
        findings.append(Finding(
            path, line, "device-timing",
            f"host-clock window (since line {start}) times "
            f"{dispatches[0]}() without a barrier — jax returns before "
            "the device is done, so this measures dispatch, not "
            "execution; end the window with jax.block_until_ready or a "
            "host fetch (tensor2robot_tpu.utils.backend.sync / "
            "np.asarray)",
            end_line=end_line))

  _check_scope(tree)
  for node in ast.walk(tree):
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
      _check_scope(node)


def check_python_tree(tree: ast.Module, path: str,
                      allow_device_timing: bool = False
                      ) -> List[Finding]:
  """Raw (unfiltered, unsorted) findings over an already-parsed module
  — the engine's entry point; `check_python_source` wraps it with the
  parse/filter/sort tail the standalone API always had."""
  aliases = _import_aliases(tree)
  findings: List[Finding] = []

  if not allow_device_timing:
    _check_device_timing(tree, aliases, path, findings)

  _check_import_time(tree, aliases, path, findings)

  collector = _TracedCollector(aliases)
  collector.visit(tree)
  seen_traced: Set[int] = set()
  for node in collector.traced:
    if id(node) in seen_traced:
      continue
    seen_traced.add(id(node))
    _walk_traced(node, aliases, path, findings)

  return findings


def check_python_source(text: str, path: str,
                        allow_device_timing: bool = False
                        ) -> List[Finding]:
  """Lints one Python source; returns (suppression-filtered) findings."""
  try:
    tree = ast.parse(text, filename=path)
  except SyntaxError as e:
    return [Finding(path, e.lineno or 0, "parse-error",
                    f"syntax error: {e.msg}")]
  findings = check_python_tree(
      tree, path, allow_device_timing=allow_device_timing)
  return sorted(filter_findings(findings, load_suppressions(text)),
                key=lambda f: (f.line, f.rule))


def allows_device_timing(path: str) -> bool:
  """True for the paths that own clocks around device code — shared by
  `check_python_file` and the engine registration, so the exemption
  cannot drift between the two call paths: obs/ owns the
  instrumentation clocks (its windows end in barriers by design)."""
  norm = path.replace("\\", "/")
  return "/obs/" in norm or norm.startswith("obs/")


def check_python_file(path: str) -> List[Finding]:
  with open(path) as f:
    return check_python_source(
        f.read(), path, allow_device_timing=allows_device_timing(path))


def _engine_check(ctx) -> List[Finding]:
  return check_python_tree(
      ctx.tree, ctx.path,
      allow_device_timing=allows_device_timing(ctx.path))


engine_lib.register(engine_lib.Rule(
    name="tracer", kind="py", scope=".py", family="tracer",
    infos=(
        engine_lib.RuleInfo(
            id="import-time-backend",
            doc="backend-touching call at module import level",
            meaning=("backend-touching call (`jax.devices`, any "
                     "`jnp`/`jax.random`/`jax.nn` call, fn default args) "
                     "at module import level")),
        engine_lib.RuleInfo(
            id="host-sync-in-jit",
            doc=(".item() / float() / np.asarray() on traced\n"
                 "values inside a jitted function"),
            meaning=("`.item()` / `float()` / `np.asarray()` on traced "
                     "values inside a jitted function")),
        engine_lib.RuleInfo(
            id="impure-in-jit",
            doc=("time.time / stateful np.random inside a jitted\n"
                 "function"),
            meaning=("`time.time` family / stateful global `np.random` "
                     "inside a jitted function")),
        engine_lib.RuleInfo(
            id="device-timing",
            doc=("time.time/perf_counter window around device\n"
                 "dispatch without a barrier (block_until_ready or\n"
                 "a host fetch): measures dispatch, not execution;\n"
                 "obs/ is exempt"),
            meaning=("`time.time`/`perf_counter` window around a device "
                     "dispatch without a barrier "
                     "(`jax.block_until_ready` or a host fetch) — "
                     "measures dispatch, not execution; `obs/` (the "
                     "clock owner) is exempt")),
    ),
    check=_engine_check))
