"""Every command the living documents tell a reader to run names something
that is there.

Scanned: `README.md`, `CLAUDE.md`, `PERF.md` and `docs/*.md`; in them, every
inline code span and every fenced code block; in those, every
`python <path>.py`, `python3 <path>.py`, `python -m <module>` and
`scripts/<name>.(sh|py)`. One case a distinct target. A path must be a file
of the checkout; a module must be one of the checkout's or one that
`importlib.util.find_spec` finds by its top-level name (nothing is imported
to decide it). And every `--flag` a document passes to a program of the
checkout must occur in that program's source. History files (`PERFORMANCE.md`, `CHANGES.md`, `VERDICT.md`,
...) are not scanned: they say what was run then.
"""

import glob
import importlib.util
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "CLAUDE.md", "PERF.md"] + sorted(
    os.path.relpath(path, REPO_ROOT)
    for path in glob.glob(os.path.join(REPO_ROOT, "docs", "*.md")))

_FENCED = re.compile(r"```.*?```", re.DOTALL)
_INLINE = re.compile(r"`([^`]+)`")
_MODULE = re.compile(r"\bpython3?\s+-m\s+([A-Za-z_][\w.]*)")
_SCRIPT = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
_UNDER_SCRIPTS = re.compile(r"\bscripts/[\w.-]+\.(?:sh|py)\b")
# One invocation: the program, then its arguments up to the next command.
_INVOCATION = re.compile(
    r"\bpython3?\s+(?:-m\s+([A-Za-z_][\w.]*)|([\w./-]+\.py))"
    r"((?:\s+(?!python3?\b|chiprun\b)[^\s;|&]+)*)")
_FLAG = re.compile(r"(?<![\w-])--[a-z][\w-]*")


def _code_lines():
  """(document, line) for every line of code the documents hold: a fenced
  block a line at a time (a trailing backslash joins the next), an inline
  span as one line (it may wrap)."""
  for document in DOCUMENTS:
    with open(os.path.join(REPO_ROOT, document)) as f:
      text = f.read()
    for block in _FENCED.findall(text):
      for line in block.replace("\\\n", " ").splitlines():
        yield document, line
    for span in _INLINE.findall(_FENCED.sub("", text)):
      yield document, " ".join(span.split())


def _module_file(module):
  """The checkout's file for `module`, or None."""
  path = os.path.join(REPO_ROOT, *module.split("."))
  for candidate in (path + ".py", os.path.join(path, "__main__.py")):
    if os.path.isfile(candidate):
      return candidate
  return None


def _scan():
  """Two tables, each keyed to the first document that has the entry:
  {target} (a module as `-m <module>`), and {(program file, flag)} for the
  flags passed to programs of the checkout."""
  commands, flags = {}, {}
  for document, line in _code_lines():
    targets = ([f"-m {m}" for m in _MODULE.findall(line)]
               + _SCRIPT.findall(line) + _UNDER_SCRIPTS.findall(line))
    for target in targets:
      commands.setdefault(target, document)
    for module, path, arguments in _INVOCATION.findall(line):
      program = (_module_file(module) if module
                 else os.path.join(REPO_ROOT, path))
      if program is None or not os.path.isfile(program):
        continue  # not the checkout's, or its own case fails on it
      for flag in _FLAG.findall(arguments):
        flags.setdefault((os.path.relpath(program, REPO_ROOT), flag),
                         document)
  return commands, flags


def _module_is_there(module):
  if _module_file(module):
    return True
  top_level = module.split(".", 1)[0]
  if os.path.isdir(os.path.join(REPO_ROOT, top_level)):
    return False  # the checkout's own package, and no such module in it
  return importlib.util.find_spec(top_level) is not None


_COMMANDS, _FLAGS = _scan()


@pytest.mark.parametrize("target", sorted(_COMMANDS))
def test_documented_command_resolves(target):
  where = _COMMANDS[target]
  if target.startswith("-m "):
    assert _module_is_there(target[3:]), (
        f"{where} says `python {target}`: no such module")
  else:
    assert os.path.isfile(os.path.join(REPO_ROOT, target)), (
        f"{where} names `{target}`: no such file in the checkout")


@pytest.mark.parametrize("program,flag", sorted(_FLAGS))
def test_documented_flag_is_one_the_program_knows(program, flag):
  with open(os.path.join(REPO_ROOT, program)) as f:
    assert flag in f.read(), (
        f"{_FLAGS[program, flag]} passes {flag} to {program}, which does "
        "not name it")


def test_the_scan_finds_the_commands_it_is_for():
  """A scan that finds nothing proves nothing: the two commands every
  session runs are among its cases."""
  assert {"chip_smoke.py", "benchmarks/run.py"} <= set(_COMMANDS)
  assert {("chip_smoke.py", "--multichip"),
          ("benchmarks/run.py", "--workload")} <= set(_FLAGS)
