"""Finds what a cell is made of, by name. `BENCHMARK.json` names a cell's
configuration and traffic mix and every metric; each of those is a file of its
own under `benchmarks/`, so a later PR adds a cell, a configuration or a metric
by adding files and entries and editing none:

    configs/<configuration>.json     sizes, gin files and bindings, source
    references/<configuration>.py    plain reference and `model_flops`
    traffic/<traffic>.json           batch, pool, warm-up, driver, tiny sizes
    limits/<cell>.json               the limit of each number `correct` compares
    layer_metrics/<metric>.py        one `read(run)`
    drivers/<driver>.py              one `run(cell, options)`

Pure Python: nothing here touches JAX.
"""

from __future__ import annotations

import importlib
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
  pass


def _read_json(relative: str, roots) -> dict:
  for root in roots:
    path = os.path.join(root, relative)
    if os.path.isfile(path):
      with open(path) as f:
        return json.load(f)
  raise ManifestError(f"missing file {relative}")


def load_benchmark(root: str = ROOT) -> dict:
  return _read_json("BENCHMARK.json", [root])


class Cell:
  """One entry of `workloads`, with its configuration, its traffic mix, its
  limits and the metrics it reports. `roots` are the checkouts searched for
  the data files, first hit wins (a test lays a temporary one over this)."""

  def __init__(self, name: str, benchmark: dict = None, roots=(ROOT,)):
    self.benchmark = benchmark or load_benchmark(roots[0])
    entries = {w["name"]: w for w in self.benchmark["workloads"]}
    if name not in entries:
      raise ManifestError(f"no workload {name!r} in BENCHMARK.json; it has "
                          f"{sorted(entries)}")
    self.entry = entries[name]
    self.name = name
    self.chips = int(self.entry["chips"])
    self.config_name = self.entry["config"]
    self.traffic_name = self.entry["traffic"]
    configs = {c["name"]: c for c in self.benchmark["configs"]}
    if self.config_name not in configs:
      raise ManifestError(f"workload {name!r} names configuration "
                          f"{self.config_name!r}, which `configs` lacks")
    bench = self.benchmark["paths"][0]
    self.config = _read_json(configs[self.config_name]["file"], roots)
    self.traffic = _read_json(
        f"{bench}/traffic/{self.traffic_name}.json", roots)
    self.limits = _read_json(f"{bench}/limits/{name}.json", roots)

  def metrics(self, kind: str):
    """The `end_to_end` or `per_layer` metrics this cell reports."""
    return [m for m in self.benchmark[kind]
            if "workloads" not in m or self.name in m["workloads"]]

  def reference(self):
    return importlib.import_module(
        f"benchmarks.references.{self.config.get('reference', self.config_name)}")

  def driver(self):
    return importlib.import_module(
        f"benchmarks.drivers.{self.traffic['driver']}")


def layer_metric_reader(name: str):
  return importlib.import_module(f"benchmarks.layer_metrics.{name}").read
