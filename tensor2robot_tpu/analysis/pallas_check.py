"""graftlint: Pallas kernel-tier interpret seam.

Pallas ships inside the one jax this repo runs on, so kernel modules
import it plainly (`ops/attention.py`, `ops/decode_kernels.py`). What a
kernel still owes the CPU suite is an `interpret` seam, so CPU tier-1
exercises the real kernel body instead of dying at lowering ("Only
interpret mode is supported on CPU backend"):

* `pallas-missing-fallback` — a `pallas_call(...)` /
  `pl.pallas_call(...)` call site that does not thread an `interpret=`
  argument through the call (a `**splat` at the call site is accepted —
  not statically analyzable).

Pure AST analysis, backend-free like every graftlint rule. Suppress
with a trailing `# graftlint: disable=pallas-missing-fallback`.
"""

from __future__ import annotations

import ast
from typing import List

from tensor2robot_tpu.analysis import engine as engine_lib
from tensor2robot_tpu.analysis.findings import (Finding, filter_findings,
                                                load_suppressions)

__all__ = ["check_python_source", "check_python_file"]

_RULE = "pallas-missing-fallback"


def _is_pallas_call(func: ast.AST) -> bool:
  if isinstance(func, ast.Name):
    return func.id == "pallas_call"
  if isinstance(func, ast.Attribute):
    return func.attr == "pallas_call"
  return False


def _check_tree(path: str, tree: ast.Module) -> List[Finding]:
  """Findings for one parsed module (shared by the standalone path and
  the engine's whole-tree check)."""
  findings: List[Finding] = []
  for node in ast.walk(tree):
    if not (isinstance(node, ast.Call) and _is_pallas_call(node.func)):
      continue
    end = getattr(node, "end_lineno", node.lineno) or node.lineno
    if not any(kw.arg == "interpret" or kw.arg is None
               for kw in node.keywords):
      findings.append(Finding(
          path=path, line=node.lineno, rule=_RULE, end_line=end,
          message=("pallas_call without an `interpret=` seam — CPU "
                   "smoke/tier-1 cannot run this kernel in interpreter "
                   "mode and hits 'Only interpret mode is supported on "
                   "CPU backend' instead of exercising the kernel "
                   "body; thread an interpret argument through the "
                   "call (`**splat` accepted)")))
  return findings


def check_python_source(path: str, source: str) -> List[Finding]:
  try:
    tree = ast.parse(source, filename=path)
  except SyntaxError:
    return []  # the engine reports unparseable files
  return _check_tree(path, tree)


def check_python_file(path: str) -> List[Finding]:
  with open(path, encoding="utf-8", errors="replace") as f:
    source = f.read()
  return filter_findings(check_python_source(path, source),
                         load_suppressions(source))


engine_lib.register(engine_lib.Rule(
    name="pallas", kind="py", scope=".py", family="pallas",
    infos=(engine_lib.RuleInfo(
        id=_RULE,
        doc=("a `pallas_call` site threads no `interpret=`\n"
             "seam (CPU tier-1 cannot run the kernel body);\n"
             "a `**splat` call site is accepted"),
        meaning=("a `pallas_call` site has no `interpret=` seam for "
                 "CPU runs (`**splat` accepted)")),),
    check=lambda ctx: _check_tree(ctx.path, ctx.tree)))
