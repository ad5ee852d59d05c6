"""The kernels' roofline readers and the two route readers on a step written
by hand around instruction texts copied from a TPU v5 lite trace of each
expert cell (`benchmarks/traces/kernel_texts.json`): each share against a
count by hand, and which ops each metric takes."""

import json
import os

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import peaks
from benchmarks.harness import trace_reduce as tr
from tests.benchmark import test_hybrid_cell
from tests.benchmark import test_nemotron_cell

D0 = "/device:TPU:0"
V5E = peaks.peaks_for("TPU v5 lite")
QWEN, NEMOTRON = test_hybrid_cell.CELL, test_nemotron_cell.CELL

# Per cell: the rows the router sent an expert layer (the counter
# `moe_rows_held`), the static buffer's rows, and per grouped product of the
# trace (the expert's matrix, K x N; bytes of its weights, read or written;
# bytes of its row operands and result).
PRODUCTS = {
    QWEN: (3050.0, 20480, {
        # forward: bf16 [20480, 2048] x [32, 2048, 1024] -> f32 [20480, 1024]
        "grouped_matmul.48": (
            2048 * 1024, 32 * 2048 * 1024 * 2,
            20480 * 2048 * 2 + 20480 * 1024 * 4),
        # the rows' cotangent, weights [32, 512, 2048] read transposed:
        # bf16 [20480, 2048] -> bf16 [20480, 512]
        "grouped_matmul.58": (
            512 * 2048, 32 * 512 * 2048 * 2,
            20480 * 2048 * 2 + 20480 * 512 * 2),
        # the weights' cotangent: [20480, 2048] and [20480, 1024] -> bf16
        # [32, 2048, 1024]
        "grouped_matmul_t.17": (
            2048 * 1024, 32 * 2048 * 1024 * 2,
            20480 * 2048 * 2 + 20480 * 1024 * 2),
    }),
    NEMOTRON: (2400.0, 6144, {
        # the op hands the [8, 2688, 1856] weights over as [8, 1856, 2688]:
        # the down product reads them as they lie, the up product transposed
        "grouped_matmul.49": (
            1856 * 2688, 8 * 1856 * 2688 * 2,
            6144 * 1856 * 2 + 6144 * 2688 * 4),
        "grouped_matmul.48": (
            2688 * 1856, 8 * 1856 * 2688 * 2,
            6144 * 2688 * 2 + 6144 * 1856 * 4),
        "grouped_matmul_t.23": (
            1856 * 2688, 8 * 1856 * 2688 * 2,
            6144 * 1856 * 2 + 6144 * 2688 * 2),
    }),
}
READERS = {QWEN: ("moe_experts_roofline", "moe_route_ms"),
           NEMOTRON: ("moe_e128_experts_roofline", "moe_e128_route_ms")}
# Routing ops of a step, written by hand at each cell's sizes (a sort of the
# N x k pairs, the gather into the buffer): 1.0 and 2.5 ms.
ROUTE_OPS = {
    QWEN: ["%sort.3 = (s32[40960]{0}, s32[40960]{0}) sort(s32[40960]{0} %k, "
           "s32[40960]{0} %i), dimensions={0}",
           "%fusion.11 = bf16[20480,2048]{1,0} fusion(bf16[4096,2048]{1,0} "
           "%x, s32[20480]{0} %t), kind=kCustom"],
    NEMOTRON: ["%sort.3 = (s32[24576]{0}, s32[24576]{0}) sort(s32[24576]{0} "
               "%k, s32[24576]{0} %i), dimensions={0}",
               "%fusion.11 = bf16[6144,2688]{1,0} fusion(bf16[4096,2688]{1,0} "
               "%x, s32[6144]{0} %t), kind=kCustom"],
}
# The short convolution: (channels, taps, a bias), and the bytes of one
# forward and one backward call by hand (bf16): x read and y written; x and
# dy read and dx written; the taps (and bias) read, dk (and db) written.
CONV = {QWEN: (8192, 4, False), NEMOTRON: (6144, 4, True)}
CONV_BYTES = {
    QWEN: ((2 * 4096 * 8192 + 4 * 8192) * 2,
           (3 * 4096 * 8192 + 2 * 4 * 8192) * 2),
    NEMOTRON: ((2 * 4096 * 6144 + 4 * 6144 + 6144) * 2,
               (3 * 4096 * 6144 + 2 * 4 * 6144 + 2 * 6144) * 2),
}


def _kernels(cell):
  with open(os.path.join(manifest.BENCH_DIR, "traces",
                         "kernel_texts.json")) as f:
    return json.load(f)[cell]


def _sizes(cell):
  return (test_hybrid_cell if cell == QWEN else test_nemotron_cell)._sizes()


def _run(cell):
  """One step of 20 ms: the routing ops, then each kernel of the trace once
  with the device time it took there."""
  events = [(D0, tr.MODULE_LINE, "jit_t2r_train_step(1)", 0.0, 20e6)]
  t = 0.0
  for text, ns in zip(ROUTE_OPS[cell], (1e6, 2.5e6)):
    events.append((D0, tr.OPS_LINE, text, t, ns))
    t += ns
  for text, ns in _kernels(cell).values():
    events.append((D0, tr.OPS_LINE, text, t, ns))
    t += ns
  rows_held = PRODUCTS[cell][0]
  return {"events": events, "sizes": _sizes(cell), "batch_size": 1,
          "peaks": V5E, "stepstats": [(10, {"moe_rows_held/layer_1":
                                            rows_held})]}


def _ns(cell, *prefixes):
  return sum(ns for name, (_, ns) in _kernels(cell).items()
             if name.partition(".")[0] in prefixes)


@pytest.mark.parametrize("cell", [QWEN, NEMOTRON])
def test_expert_roofline_counts_the_kernels_as_their_text_says(cell):
  rows_held, buffer_rows, products = PRODUCTS[cell]
  least = 0.0
  for matrix, weights, rows in products.values():
    least += max(2 * rows_held * matrix / V5E["bf16_flops_per_s"],
                 (weights + rows * rows_held / buffer_rows)
                 / V5E["hbm_bytes_per_s"])
  seconds = _ns(cell, "grouped_matmul", "grouped_matmul_t") / 1e9
  read = manifest.layer_metric_reader(READERS[cell][0])
  assert read(_run(cell)) == pytest.approx(100.0 * least / seconds, rel=1e-9)
  assert 0 < read(_run(cell)) <= 100


@pytest.mark.parametrize("cell", [QWEN, NEMOTRON])
def test_route_leaves_the_grouped_kernels_out(cell):
  """Every kernel names the buffer's rows, which routing's ops name too; the
  route metric takes the sort and the gather, 3.5 ms, and no kernel."""
  run = _run(cell)
  buffer = f"[{PRODUCTS[cell][1]},{_sizes(cell)['hidden_size']}]"
  assert all(buffer in text for name, (text, _) in _kernels(cell).items()
             if name.startswith("grouped_matmul"))
  read = manifest.layer_metric_reader(READERS[cell][1])
  assert read(run) == pytest.approx(3.5)


@pytest.mark.parametrize("cell", [QWEN, NEMOTRON])
def test_short_conv_roofline_counts_the_cells_channels(cell):
  """The kernels read their channels of the whole in-projection in place:
  the operand's shape is the projection's, the count is the channels'."""
  texts = _kernels(cell)
  forward = next(text for name, (text, _) in texts.items()
                 if name.startswith("short_conv."))
  assert f"[1,4096,{CONV[cell][0]}]" in forward.partition(" = ")[2][:40]
  fwd_bytes, bwd_bytes = CONV_BYTES[cell]
  least = (fwd_bytes + bwd_bytes) / V5E["hbm_bytes_per_s"]
  seconds = _ns(cell, "short_conv", "short_conv_bwd") / 1e9
  read = manifest.layer_metric_reader("short_conv_roofline")
  assert read(_run(cell)) == pytest.approx(100.0 * least / seconds, rel=1e-9)
  assert read(dict(_run(cell), sizes={"num_heads": 8})) is None


def test_gdn_inverse_roofline_counts_the_doubling_products():
  """Ten [64, 64] products a head and chunk at six bfloat16 passes each,
  against the operand read and the inverse written once: 64 chunks x 32
  heads."""
  blocks = 64 * 1 * 32
  flops = 6 * 10 * 2 * 64 ** 3 * blocks
  nbytes = 2 * blocks * 64 * 64 * 4
  least = max(flops / V5E["bf16_flops_per_s"], nbytes / V5E["hbm_bytes_per_s"])
  assert least == flops / V5E["bf16_flops_per_s"]   # the products bound it
  read = manifest.layer_metric_reader("gdn_inverse_roofline")
  seconds = _ns(QWEN, "gdn_inverse") / 1e9
  assert read(_run(QWEN)) == pytest.approx(100.0 * least / seconds, rel=1e-9)
  assert read(_run(NEMOTRON)) is None   # no such kernel, no such sizes


def test_kernel_rooflines_are_listed_with_their_cells():
  per_layer = {m["name"]: m for m in manifest.load_benchmark()["per_layer"]}
  for name, cells in (("short_conv_roofline", {QWEN, NEMOTRON}),
                      ("gdn_inverse_roofline", {QWEN})):
    metric = per_layer[name]
    assert (metric["unit"], metric["better"], metric["layer"],
            metric["moves"], metric["source"]) == (
                "%", "higher", "kernels", "examples_per_s", "device_trace")
    assert cells <= set(metric["workloads"])
