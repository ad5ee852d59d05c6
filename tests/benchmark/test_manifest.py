"""`BENCHMARK.json` and the files it names hang together, and a later PR can
add a cell with files and entries alone."""

import copy
import importlib
import json
import os
import re

import pytest

from benchmarks.harness import manifest
from tests.benchmark import test_device_scopes
from tests.benchmark import test_hybrid_cell
from tests.benchmark import test_nemotron_cell

BENCHMARK = manifest.load_benchmark()
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def test_top_level_keys_are_the_contracts():
  assert sorted(BENCHMARK) == sorted([
      "command", "paths", "run_seconds", "configs", "workloads",
      "end_to_end", "per_layer"])
  assert 1 <= BENCHMARK["run_seconds"] <= 51
  assert BENCHMARK["command"][-1].startswith(BENCHMARK["paths"][0] + "/")
  assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) \
      < 64 * 1024


@pytest.mark.parametrize("name", [
    *(m["name"] for m in METRICS), *CELLS,
    *(c["name"] for c in BENCHMARK["configs"]),
    *(w["traffic"] for w in BENCHMARK["workloads"])])
def test_names_use_only_the_allowed_characters(name):
  assert manifest.NAME_RE.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
  allowed = {"name", "unit", "better", "source", "workloads"}
  per_layer = metric in BENCHMARK["per_layer"]
  allowed |= {"layer", "moves"} if per_layer else {"bound"}
  assert set(metric) <= allowed and {"name", "unit", "better",
                                     "source"} <= set(metric)
  assert manifest.UNIT_RE.match(metric["unit"])
  assert metric["better"] in ("lower", "higher")
  assert metric["source"] in manifest.SOURCES
  if not per_layer:
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0 < metric["bound"] <= 0.1
  for cell in metric.get("workloads", []):
    assert cell in CELLS


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(metric):
  end_to_end = {m["name"]: m for m in BENCHMARK["end_to_end"]}
  assert metric["moves"] in end_to_end
  moved = end_to_end[metric["moves"]]
  for cell in metric.get("workloads", CELLS):
    assert cell in moved.get("workloads", CELLS)
  reader = manifest.layer_metric_reader(metric["name"])
  assert callable(reader)
  assert reader({}) is None  # nothing to read: nothing returned, never 0
  assert len(metric["layer"]) <= 200 and "\n" not in metric["layer"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_exist_and_agree(name):
  cell = manifest.Cell(name)
  assert cell.chips in (1, 4)
  assert len(cell.entry["why"]) <= 200
  entry = next(c for c in BENCHMARK["configs"]
               if c["name"] == cell.config_name)
  assert entry["file"].startswith(tuple(p + "/" for p in BENCHMARK["paths"]))
  assert cell.config["source"] == entry["source"]
  assert cell.config["reduced"] == entry["reduced"]
  for gin in cell.config["gin_files"]:
    assert os.path.isfile(os.path.join(manifest.ROOT, gin)), gin
  reference = cell.reference()
  assert callable(reference.model_flops) and callable(reference.train_steps)
  assert callable(cell.driver().run)
  assert cell.traffic["warmup_steps"] > 3
  assert cell.traffic["pool_batches"] >= 3
  assert {"setup_s"} <= {m["name"] for m in cell.metrics("end_to_end")}
  assert len(cell.metrics("end_to_end")) >= 2
  assert cell.metrics("per_layer")
  for number, limit in cell.limits.items():
    if not number.startswith("_"):
      assert limit >= 0


def test_no_width_is_reduced():
  for config in BENCHMARK["configs"]:
    for key in config["reduced"]:
      assert not re.search(r"(_dim|_rank|hidden|intermediate|head)", key)


def test_files_under_paths_are_named_from_allowed_characters():
  for path in BENCHMARK["paths"]:
    for folder, dirs, files in os.walk(os.path.join(manifest.ROOT, path)):
      dirs[:] = [d for d in dirs if d != "__pycache__"]
      for f in files:
        assert re.match(r"^[A-Za-z0-9_.-]+$", f), os.path.join(folder, f)


def test_a_later_pr_adds_a_cell_with_files_and_entries_alone(tmp_path):
  """A new traffic mix, its limits and its entry, laid over the checkout from
  a temporary directory: the harness finds them by name."""
  benchmark = copy.deepcopy(BENCHMARK)
  benchmark["workloads"].append({
      "name": "seq_train_T512", "config": "seq_trunk_h512",
      "traffic": "pool_b512_T512", "chips": 1, "why": "a later PR's cell"})
  bench = benchmark["paths"][0]
  (tmp_path / bench / "traffic").mkdir(parents=True)
  (tmp_path / bench / "limits").mkdir(parents=True)
  base = json.load(open(os.path.join(
      manifest.BENCH_DIR, "traffic", "pool_b128_T2048.json")))
  base.update({"batch_size": 512, "bindings": [
      "SequenceRegressionModel.sequence_length = 512"]})
  (tmp_path / bench / "traffic" / "pool_b512_T512.json").write_text(
      json.dumps(base))
  (tmp_path / bench / "limits" / "seq_train_T512.json").write_text(
      json.dumps({"loss1": 0.01}))
  cell = manifest.Cell("seq_train_T512", benchmark,
                       roots=(str(tmp_path), manifest.ROOT))
  assert cell.traffic["batch_size"] == 512
  assert cell.config["model"]["hidden_size"] == 512
  assert cell.limits == {"loss1": 0.01}
  assert [m["name"] for m in cell.metrics("end_to_end")] == [
      "examples_per_s", "setup_s"]
  # The flash rooflines list their cells, so the new cell does not owe them.
  assert "flash_fwd_roofline" not in [
      m["name"] for m in cell.metrics("per_layer")]
  assert importlib.import_module("benchmarks.drivers.trainer").run


LATER_CELL = "later_train_T2048"
LATER_METRIC = "later_kernel_roofline"


def _later_benchmark(tmp_path):
  """The real `BENCHMARK.json` with what a later `model_config` PR appends: a
  fifth configuration (its sizes, traffic mix and limits laid in `tmp_path`,
  its reference one already in the checkout), a fifth cell and a per-layer
  metric listed for an accepted cell and the new one. Returns it with the
  new cell as the harness finds it."""
  benchmark = copy.deepcopy(BENCHMARK)
  bench = benchmark["paths"][0]
  accepted = manifest.Cell(test_nemotron_cell.CELL)
  config = dict(accepted.config, name="later_model")
  traffic = dict(accepted.traffic, model={"sequence_length": 2048},
                 bindings=["HybridDecoderLM.sequence_length = 2048"])
  for folder, name, data in (("configs", "later_model", config),
                             ("traffic", "pool_b1_T2048_later", traffic),
                             ("limits", LATER_CELL, accepted.limits)):
    (tmp_path / bench / folder).mkdir(parents=True, exist_ok=True)
    (tmp_path / bench / folder / f"{name}.json").write_text(json.dumps(data))
  benchmark["configs"].append({
      "name": "later_model", "source": config["source"],
      "file": f"{bench}/configs/later_model.json",
      "reduced": config["reduced"], "why": "a later PR's configuration"})
  benchmark["workloads"].append({
      "name": LATER_CELL, "config": "later_model",
      "traffic": "pool_b1_T2048_later", "chips": 1,
      "why": "a later PR's cell"})
  benchmark["per_layer"].append({
      "name": LATER_METRIC, "unit": "%", "better": "higher",
      "source": "device_trace", "layer": "kernels",
      "moves": "examples_per_s",
      "workloads": [*test_device_scopes.EXPERT_CELLS, LATER_CELL]})
  cell = manifest.Cell(LATER_CELL, benchmark,
                       roots=(str(tmp_path), manifest.ROOT))
  return benchmark, cell


@pytest.mark.parametrize("check", [
    test_hybrid_cell.check_listing, test_nemotron_cell.check_listing,
    test_device_scopes.check_listing], ids=lambda c: c.__module__)
def test_accepted_listing_checks_hold_after_a_later_pr_appends(tmp_path,
                                                              check):
  """A fifth configuration, cell and metric appended to the real
  `BENCHMARK.json` leave every accepted cell's listing check true: those
  checks ask for their own entries, never for a count or a position."""
  benchmark, cell = _later_benchmark(tmp_path)
  assert len(benchmark["configs"]) == len(BENCHMARK["configs"]) + 1
  assert cell.config_name == "later_model"
  assert cell.traffic["model"] == {"sequence_length": 2048}
  assert cell.reference().__name__.endswith(test_nemotron_cell.CONFIG)
  reported = [m["name"] for m in cell.metrics("per_layer")]
  assert LATER_METRIC in reported
  assert test_hybrid_cell.GENERIC_METRICS <= set(reported)
  assert "moe_e128_route_ms" not in reported  # listed for its own cell only
  check(benchmark)


def test_unknown_cell_is_an_error():
  with pytest.raises(manifest.ManifestError):
    manifest.Cell("no_such_cell")
