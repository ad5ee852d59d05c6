"""graftaudit: jaxpr-level semantic auditing of a config's jit entry
points — ahead-of-time, trace-only, never on the chip.

The reference stack validated tensors at RUNTIME (tensorspec_utils
assert/validate helpers fired per batch inside the input pipeline); the
graftlint layer moved the spec checks ahead of time but stops at the
AST. This module closes the remaining gap: the expensive mistakes that
are INVISIBLE in source text and only exist in the traced program —

* `audit-baked-constant`       a large array closure-captured into the
                               jitted function becomes a jaxpr constant:
                               it bloats every serialized graftcache
                               entry, dodges donation, and re-uploads
                               with every executable;
* `audit-undonated-state`      a state-sized input whose shape/dtype
                               reappears in the outputs but is not
                               donated — the runtime keeps two copies
                               live across every dispatch (the train
                               state / decode arena mistake);
* `audit-host-callback-in-loop` a host-callback primitive inside a
                               `scan`/`while` body: one host round-trip
                               PER ITERATION, serialized against the
                               device stream;
* `audit-unhashable-static`    a static arg that is unhashable (jit
                               raises at every call site) or hashes by
                               object identity (every fresh instance is
                               a silent recompile).

Split exactly like `obs/forge.py`, whose enumeration it reuses: the
PARENT (`audit_config`) is backend-free — it enumerates the config's
executable set through `forge.plan_from_config`, then hands every
traceable target to ONE fresh worker subprocess (`--worker`), which
pins the CPU backend (`utils.backend.pin_cpu`; `GRAFTAUDIT_PLATFORM`
overrides, the forge-worker pattern) before any jax import can take
the chip. The worker builds exactly the objects the deployment
builds — `forge.build_rung_engine(...)` + `rung_traces()` for serving
ladders, `forge.build_train_step(...)` for the trainer — and audits
each `.trace(*args)` result: `traced.jaxpr` for constants and loop
bodies, `traced.args_info` for donation. Tracing never lowers or
compiles, so even excache-gated (unforgeable) train targets are
auditable.

Findings surface through the graftlint engine: the four rules are
registered in `analysis/engine.py`'s catalog (kind "jaxpr" — catalog/
severity only, the file walk never runs them), anchored on the audited
config file spanning its full length, so one trailing
`# graftlint: disable=<rule>` comment anywhere in the config suppresses
deliberately accepted hits. CLI: `python -m
tensor2robot_tpu.bin.graftscope audit <config.gin>` (exit 0 clean, 1
findings/errors, 2 usage).

`audit_callable(name, fn, args, ...)` is the fixture-test seam: it
audits ONE callable the same way the worker audits a config target
(tests/test_jaxpr_audit.py seeds each violation through it).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

from tensor2robot_tpu.analysis import engine as engine_lib
from tensor2robot_tpu.analysis.findings import Finding, load_suppressions

__all__ = ["audit_config", "audit_callable", "audit_traced",
           "report_findings", "format_report", "AUDIT_CONST_BYTES",
           "AUDIT_STATE_BYTES"]

# A closure-captured constant this large is a deployment bug, not a
# scalar epsilon: 1 MiB is far above any legitimate baked table in this
# repo and far below any real weight array.
AUDIT_CONST_BYTES = 1 << 20
# Inputs at least this large with an output shape twin are "state" for
# the donation rule (param leaves, decode arenas — not batch scalars).
AUDIT_STATE_BYTES = 64 << 10

_LOOP_PRIMITIVES = frozenset({"scan", "while"})
# Host round-trip primitives. Matching also catches dialect variants
# ("callback" substring) so a jax rename degrades to MORE coverage.
_CALLBACK_PRIMITIVES = frozenset({"pure_callback", "io_callback",
                                  "debug_callback", "outside_call"})


def _entry(executable: str, rule: str, message: str) -> Dict[str, str]:
  return {"executable": executable, "rule": rule, "message": message}


def _aval_bytes(aval) -> int:
  import numpy as np

  shape = getattr(aval, "shape", None)
  dtype = getattr(aval, "dtype", None)
  if shape is None or dtype is None:
    return 0
  size = 1
  for dim in shape:
    try:
      size *= int(dim)
    except TypeError:  # symbolic dim: size unknowable, skip
      return 0
  return size * np.dtype(dtype).itemsize


def _sub_jaxprs(params: Mapping[str, Any]):
  """Every sub-jaxpr hiding in one eqn's params (scan/while bodies,
  cond branches, pjit calls) — ClosedJaxpr or raw Jaxpr, single or
  listed."""
  for value in params.values():
    for candidate in (value if isinstance(value, (list, tuple))
                      else (value,)):
      inner = getattr(candidate, "jaxpr", None)
      if inner is not None and hasattr(inner, "eqns"):
        yield inner
      elif hasattr(candidate, "eqns"):
        yield candidate


def _walk_loop_callbacks(jaxpr, enclosing_loop: Optional[str],
                         hits: List[Tuple[str, str]]) -> None:
  for eqn in jaxpr.eqns:
    prim = eqn.primitive.name
    if enclosing_loop and (prim in _CALLBACK_PRIMITIVES
                           or "callback" in prim):
      hits.append((prim, enclosing_loop))
    loop = prim if prim in _LOOP_PRIMITIVES else enclosing_loop
    for sub in _sub_jaxprs(eqn.params):
      _walk_loop_callbacks(sub, loop, hits)


def audit_traced(name: str, traced,
                 const_bytes: int = AUDIT_CONST_BYTES,
                 state_bytes: int = AUDIT_STATE_BYTES
                 ) -> List[Dict[str, str]]:
  """Audits one `jitted.trace(*args)` result (worker side; jax is
  imported by the caller's trace already). Returns raw entry dicts —
  the parent converts them to engine Findings."""
  import jax

  entries: List[Dict[str, str]] = []
  closed = traced.jaxpr  # ClosedJaxpr

  # -- audit-baked-constant ------------------------------------------------
  for var, _val in zip(closed.jaxpr.constvars, closed.consts):
    aval = getattr(var, "aval", None)
    nbytes = _aval_bytes(aval)
    if nbytes >= const_bytes:
      entries.append(_entry(
          name, "audit-baked-constant",
          f"a {tuple(aval.shape)} {aval.dtype} constant "
          f"({nbytes / 2**20:.1f} MiB) is baked into the executable "
          "(closure-captured array: it bloats every serialized cache "
          "entry, dodges donation, and re-uploads with the program — "
          "pass it as an argument instead)"))

  # -- audit-undonated-state -----------------------------------------------
  infos = jax.tree_util.tree_leaves(
      traced.args_info, is_leaf=lambda n: hasattr(n, "donated"))
  out_sigs = {(tuple(a.shape), str(a.dtype)) for a in closed.out_avals
              if hasattr(a, "shape") and hasattr(a, "dtype")}
  undonated = 0
  undonated_bytes = 0
  # args_info leaves and in_avals share one flat order (ArgInfo keeps
  # its aval private, so the donation flag is paired with the public
  # aval list; a length mismatch — statics, future jax — skips the
  # rule rather than mispairing).
  in_avals = list(closed.in_avals)
  for info, aval in (zip(infos, in_avals)
                     if len(infos) == len(in_avals) else ()):
    if getattr(info, "donated", False):
      continue
    nbytes = _aval_bytes(aval)
    if (nbytes >= state_bytes
        and (tuple(aval.shape), str(aval.dtype)) in out_sigs):
      undonated += 1
      undonated_bytes += nbytes
  if undonated:
    entries.append(_entry(
        name, "audit-undonated-state",
        f"{undonated} undonated input leaf(ves) totalling "
        f"{undonated_bytes / 2**20:.1f} MiB whose shape/dtype reappears "
        "in the outputs — state carried through the step without "
        "donate_argnums keeps BOTH copies live across every dispatch"))

  # -- audit-host-callback-in-loop -----------------------------------------
  hits: List[Tuple[str, str]] = []
  _walk_loop_callbacks(closed.jaxpr, None, hits)
  for prim, loop in hits:
    entries.append(_entry(
        name, "audit-host-callback-in-loop",
        f"host-callback primitive {prim!r} inside a {loop!r} body: one "
        "host round-trip PER ITERATION, serialized against the device "
        "stream — hoist it out of the loop or batch it"))
  return entries


def _audit_static_args(name: str,
                       static_args: Mapping[str, Any]
                       ) -> List[Dict[str, str]]:
  entries: List[Dict[str, str]] = []
  for arg_name in sorted(static_args):
    value = static_args[arg_name]
    try:
      hash(value)
    except TypeError:
      entries.append(_entry(
          name, "audit-unhashable-static",
          f"static arg {arg_name!r} ({type(value).__name__}) is "
          "unhashable — jit raises at every call site; pin it as a "
          "hashable (tuple / frozenset / frozen dataclass)"))
      continue
    if type(value).__hash__ is object.__hash__ and not callable(value):
      entries.append(_entry(
          name, "audit-unhashable-static",
          f"static arg {arg_name!r} ({type(value).__name__}) hashes by "
          "object identity — every fresh instance is a new jit cache "
          "entry, a silent recompile per construction"))
  return entries


def audit_callable(name: str, fn, args: Sequence[Any],
                   donate_argnums: Sequence[int] = (),
                   static_args: Optional[Mapping[str, Any]] = None
                   ) -> List[Dict[str, str]]:
  """Audits ONE callable exactly as the worker audits a config target
  (the fixture-test seam). `fn` may be a plain callable (jitted here
  with `donate_argnums`) or anything with a `.trace` AOT method;
  `static_args` is a name->value mapping audited for hashability
  WITHOUT entering the trace (an unhashable static would abort it)."""
  import jax

  entries = _audit_static_args(name, dict(static_args or {}))
  jitted = fn if hasattr(fn, "trace") else jax.jit(
      fn, donate_argnums=tuple(donate_argnums))
  entries.extend(audit_traced(name, jitted.trace(*args)))
  return entries


# ---------------------------------------------------------------------------
# Worker side (fresh subprocess; the only half that touches jax —
# the obs/forge.py split).
# ---------------------------------------------------------------------------


def _audit_target(spec: Dict[str, Any],
                  target: Dict[str, Any]) -> Dict[str, Any]:
  from tensor2robot_tpu.obs import forge

  findings: List[Dict[str, str]] = []
  try:
    if target["family"] in ("serve", "session"):
      engine = forge.build_rung_engine(spec, target)
      for rung, traced, _args in engine.rung_traces():
        if target["family"] == "session":
          exe = (f"{target['name']}/reset_slot" if rung == "reset"
                 else f"{target['name']}/decode{rung}")
        else:
          exe = f"{target['name']}/bucket{rung}"
        findings.extend(audit_traced(exe, traced))
    elif target["family"] == "train":
      step, args = forge.build_train_step(spec, target)
      findings.extend(audit_traced(target["name"], step.trace(*args)))
    else:
      return {"name": target["name"], "family": target["family"],
              "status": "skipped",
              "reason": "no trace recipe for this family"}
  except Exception as e:  # noqa: BLE001 - one bad target != a dead audit
    return {"name": target["name"], "family": target["family"],
            "status": "error", "error": f"{type(e).__name__}: {e}"}
  return {"name": target["name"], "family": target["family"],
          "status": "ok", "findings": findings}


def _worker_main(spec_path: str, result_path: str) -> int:
  with open(spec_path) as f:
    spec = json.load(f)
  if os.environ.get("GRAFTAUDIT_PLATFORM", "cpu") == "cpu":
    # Default-safe: the audit worker must never take the chip by
    # accident (the GRAFTFORGE_PLATFORM pattern).
    from tensor2robot_tpu.utils import backend

    backend.pin_cpu()
  from tensor2robot_tpu.utils import config

  config.clear_config()
  config.parse_config_files_and_bindings(list(spec["config_files"]),
                                         list(spec["bindings"]))
  results = [_audit_target(spec, target) for target in spec["targets"]]
  with open(result_path, "w") as f:
    json.dump(results, f)
  return 0 if all(r["status"] != "error" for r in results) else 1


# ---------------------------------------------------------------------------
# Parent side (backend-free).
# ---------------------------------------------------------------------------


def _run_worker(plan: Dict[str, Any], targets: List[Dict[str, Any]],
                cache_dir: Optional[str], device_count: Optional[int],
                timeout_s: float) -> List[Dict[str, Any]]:
  from tensor2robot_tpu.obs import forge

  if not targets:
    return []
  env = forge._worker_env(device_count)
  with tempfile.TemporaryDirectory(prefix="graftaudit-") as tmp:
    spec = {
        "config_files": plan["config_files"],
        "bindings": plan["bindings"],
        "model": plan.get("model"),
        "model_dir": plan.get("model_dir"),
        # Engines want a cache dir at construction; tracing never
        # touches it, so a throwaway default keeps the audit read-only.
        "cache_dir": cache_dir or os.path.join(tmp, "cache"),
        "targets": targets,
    }
    spec_path = os.path.join(tmp, "spec.json")
    result_path = os.path.join(tmp, "result.json")
    with open(spec_path, "w") as f:
      json.dump(spec, f)
    proc = subprocess.Popen(
        [sys.executable, "-m", "tensor2robot_tpu.analysis.jaxpr_audit",
         "--worker", spec_path, result_path], env=env)
    try:
      proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
      # NEVER SIGKILL a possibly-mid-TPU-init child (CLAUDE.md); the
      # worker is CPU-pinned but the discipline is unconditional.
      proc.terminate()
      try:
        proc.wait(timeout=30)
      except subprocess.TimeoutExpired:
        pass  # abandon, never SIGKILL
    if os.path.isfile(result_path):
      try:
        with open(result_path) as f:
          return json.load(f)
      except (OSError, ValueError):
        pass
    return [{"name": t["name"], "family": t["family"], "status": "error",
             "error": f"audit worker exited {proc.returncode} without "
                      "a result"} for t in targets]


def report_findings(plan: Dict[str, Any],
                    results: Sequence[Dict[str, Any]]) -> List[Finding]:
  """Worker entries -> engine-catalogued Findings, anchored on the
  first audited config file and spanning its full length — so a
  trailing `# graftlint: disable=<rule>` comment on ANY line of the
  config suppresses a deliberately accepted hit (file-level
  suppression, the same `findings.Suppressions` model every graftlint
  rule uses)."""
  anchor = (plan.get("config_files") or ["<config>"])[0]
  try:
    with open(anchor, encoding="utf-8", errors="replace") as f:
      text = f.read()
  except OSError:
    text = ""
  end_line = max(1, text.count("\n") + 1)
  raw = [Finding(path=anchor, line=1, rule=entry["rule"],
                 message=f"{entry['executable']}: {entry['message']}",
                 end_line=end_line)
         for result in results
         for entry in (result.get("findings") or [])]
  supps = load_suppressions(text)
  kept = [f for f in raw if supps.match(f.line, f.rule, f.end_line) is None]
  return sorted(kept, key=lambda f: (f.path, f.rule, f.message))


def _default_device_count(plan: Dict[str, Any]) -> int:
  """The smallest worker topology the plan's targets can build on:
  placed fleet replicas need one device each, an explicit mesh shape
  needs its product, and the trainer's unbound "default" mesh mirrors
  the repo's standard virtual 8-device topology (tests/conftest.py)."""
  need = 1
  for target in plan["targets"]:
    if target.get("placed"):
      need = max(need, int(target.get("num_replicas") or 1))
    shape = target.get("mesh_shape")
    if isinstance(shape, (list, tuple)):
      product = 1
      for dim in shape:
        product *= int(dim)
      need = max(need, product)
    elif shape == "default":
      need = max(need, 8)
  return need


def audit_config(config_files: Sequence[str],
                 bindings: Sequence[str] = (),
                 model: Optional[str] = None,
                 export_dir: Optional[str] = None,
                 model_dir: Optional[str] = None,
                 cache_dir: Optional[str] = None,
                 device_count: Optional[int] = None,
                 timeout_s: float = 600.0
                 ) -> Tuple[Dict[str, Any], List[Dict[str, Any]],
                            List[Finding]]:
  """Audits every jit entry point a research config deploys.

  Backend-free in THIS process: enumeration is `forge.plan_from_config`
  and all tracing happens in one CPU-pinned worker subprocess (its
  device count defaults to what the plan's targets need). Returns
  `(plan, per-target results, findings)` — findings already filtered
  through the config's suppression comments. Excache-gated
  (unforgeable) train targets ARE audited: tracing never serializes an
  executable, so the donating-mesh gate does not apply.
  """
  from tensor2robot_tpu.obs import forge

  plan = forge.plan_from_config(config_files, bindings, model=model,
                                export_dir=export_dir,
                                model_dir=model_dir)
  targets = [t for t in plan["targets"]
             if t["family"] in ("serve", "session", "train")]
  results = _run_worker(plan, targets, cache_dir,
                        device_count or _default_device_count(plan),
                        timeout_s)
  return plan, results, report_findings(plan, results)


def format_report(plan: Dict[str, Any],
                  results: Sequence[Dict[str, Any]],
                  findings: Sequence[Finding]) -> str:
  """The `graftscope audit` summary table (format_plan's sibling)."""
  lines = [f"graftaudit: {', '.join(plan['config_files'])} "
           f"(model: {json.dumps(plan.get('model'))})"]
  for result in results:
    status = result["status"]
    detail = (result.get("error") or result.get("reason")
              or f"{len(result.get('findings') or [])} finding(s)")
    lines.append(f"  {result['family']:<9}{result['name']:<18}"
                 f"{status:>8}  {detail}")
  lines.append(f"  {len(findings)} finding(s) after suppressions")
  return "\n".join(lines)


engine_lib.register(engine_lib.Rule(
    name="audit", kind="jaxpr",
    scope="jit entry points, via `graftscope audit <config>`",
    family="audit",
    infos=(
        engine_lib.RuleInfo(
            id="audit-baked-constant", severity="warning",
            doc=("a large array is closure-captured into a jit\n"
                 "entry point (a jaxpr constant: bloats every\n"
                 "cache entry, dodges donation)"),
            meaning=("a large array is closure-captured into a jit "
                     "entry point — a jaxpr constant that bloats every "
                     "serialized cache entry and dodges donation")),
        engine_lib.RuleInfo(
            id="audit-undonated-state", severity="warning",
            doc=("a state-sized input whose shape/dtype reappears\n"
                 "in the outputs is not donated (two live copies\n"
                 "per dispatch)"),
            meaning=("a state-sized input whose shape/dtype reappears "
                     "in the outputs is not donated — two live copies "
                     "per dispatch (the train-state/arena mistake)")),
        engine_lib.RuleInfo(
            id="audit-host-callback-in-loop", severity="warning",
            doc=("a host-callback primitive inside a scan/while\n"
                 "body: one host round-trip PER ITERATION"),
            meaning=("a host-callback primitive inside a `scan`/`while` "
                     "body — one host round-trip per iteration")),
        engine_lib.RuleInfo(
            id="audit-unhashable-static", severity="warning",
            doc=("a static arg is unhashable (jit raises) or\n"
                 "hashes by identity (silent recompile per\n"
                 "instance)"),
            meaning=("a static arg is unhashable (jit raises at every "
                     "call site) or hashes by object identity (a silent "
                     "recompile per fresh instance)")),
    )))


if __name__ == "__main__":
  if len(sys.argv) == 4 and sys.argv[1] == "--worker":
    sys.exit(_worker_main(sys.argv[2], sys.argv[3]))
  print("usage: python -m tensor2robot_tpu.analysis.jaxpr_audit "
        "--worker <spec.json> <result.json>\n(operators drive the audit "
        "through `python -m tensor2robot_tpu.bin.graftscope audit`)",
        file=sys.stderr)
  sys.exit(2)
