"""The chip's compiler, without the chip.

The image carries libtpu, so two things run here at no chip time:

* `jax.export` with platforms=["tpu"] runs the REAL Pallas->Mosaic TPU
  lowering (block-spec tiling rules, iota rank rules, memory-space
  checks — the constraint layer whose violations interpret mode hides);
* `.lower(...).compile()` against a DESCRIBED v5e
  (`topologies.get_topology_desc`) runs the whole TPU compiler, Mosaic ->
  machine code included: fast-memory limits, (8, 128) tile alignment, a
  program that does not fit the device's memory, a kernel that cannot be
  partitioned. Every kernel and step `chip_smoke.py` runs is compiled
  here at the size it runs there (ISSUE 22 §1).

Nothing runs, so this says nothing about results or times: the chip run
(`chip_smoke.py`) remains the final word.

The topology is described, and the TPU export probed, inside
module-scoped fixtures of THIS file — never while a module is imported,
never in a `skipif` condition or a `parametrize` argument: only one
process may hold the TPU's library, every xdist worker imports every
test file, and a worker that lost that race at import would collect
other tests than its peers (see the on-chip-measurement guide, §2).
"""

from __future__ import annotations

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from tensor2robot_tpu.ops import attention
from tensor2robot_tpu.ops import grouped_matmul
from tensor2robot_tpu.ops import linear_attention
from tensor2robot_tpu.ops import short_conv
from tensor2robot_tpu.ops import state_space

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PACKAGE = os.path.join(_REPO_ROOT, "tensor2robot_tpu")


def _export_for_tpu(fn, *shapes):
  from jax import export

  return export.export(jax.jit(fn), platforms=["tpu"])(*shapes)


@pytest.fixture(scope="module")
def tpu_lowering():
  """Skips, with the reason, where a TPU-platform export cannot run (so
  an API/libtpu breakage reads as itself, not as a silent pass)."""
  try:
    _export_for_tpu(lambda x: x + 1.0,
                    jax.ShapeDtypeStruct((8, 128), jnp.float32))
  except Exception as exc:  # noqa: BLE001 - the reason lands in the skip
    pytest.skip(f"TPU lowering unavailable: {type(exc).__name__}: {exc}")


@pytest.fixture(scope="module")
def topo():
  from jax.experimental import topologies

  try:
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # noqa: BLE001 - the reason lands in the skip
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
  return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def v5e_devices(topo):
  return np.array(topo.devices)


@pytest.fixture(scope="module")
def two_slice_devices(topo):
  from jax.experimental import topologies

  del topo  # described first: this one skips where that one does
  return np.array(topologies.get_topology_desc(
      platform="tpu", topology_name="v5e:2x2", num_slices=2).devices)


def _model_from_config(config_file, bindings=()):
  """The model (and train batch size) a shipped config builds."""
  from tensor2robot_tpu.utils import config

  config.clear_config()
  try:
    config.parse_config_files_and_bindings(
        [os.path.join(_PACKAGE, config_file)], list(bindings))
    model = config.query_parameter("train_eval_model.model")
    batch = config.query_parameter(
        "train_eval_model.input_generator_train").batch_size
  finally:
    config.clear_config()
  return model, batch


CONFIGS = [
    # (b, h, t, d), causal, block_q, block_k
    ((2, 4, 256, 64), True, 128, 128),    # flagship-ish self-attention
    ((2, 4, 256, 64), False, 128, 128),
    ((1, 2, 512, 128), True, 128, 128),   # wide heads
    ((1, 1, 100, 64), False, 128, 128),   # non-tiling T: padded + masked
    ((1, 2, 64, 64), True, 64, 64),       # sub-128 blocks (lse tiling!)
    ((1, 1, 16, 64), False, 128, 128),    # tiny T, block > T
    ((1, 2, 1024, 64), True, 128, 256),   # asymmetric block sizes
    ((1, 1, 4096, 64), True, 128, 128),   # long-context SP building block
    ((2, 8, 1024, 64), True, 512, 512),   # two heads of 64 a program
    ((2, 2, 512, 256), True, 256, 256),   # one head in a 256-lane block
    ((2, 8, 256, 32), True, 128, 128),    # four heads of 32 a program
    ((2, 4, 256, 16), False, 128, 128),   # whole H x D = 64, under 128
    ((1, 3, 256, 64), True, 128, 128),    # whole H x D = 192: 128 divides not
    ((2, 16, 4096, 256), True, 512, 512),  # the hybrid decoder cell's layer
]


def _flash_shapes(shape, dtype=jnp.bfloat16):
  """[B, T, H x D] operands and the head count for a (b, h, t, d) case."""
  b, h, t, d = shape
  return jax.ShapeDtypeStruct((b, t, h * d), dtype), h


def _flash_grads(h, **kwargs):
  """(q, k, v) -> their gradients through the real (Mosaic) kernels."""
  return jax.grad(
      lambda q, k, v: attention.flash_attention(
          q, k, v, h, interpret=False, **kwargs).astype(jnp.float32).sum(),
      argnums=(0, 1, 2))


def _flash_calls(text: str) -> dict:
  """Flash kernel -> its custom calls in a compiled text, by instruction
  name (`%flash_window_fwd.3 = ...`, or `%jvp_flash_window_fwd_.1 = ...`
  where the op is compiled alone); the metadata, which names the Python
  functions (`_flash_fwd`), is not read."""
  calls = {}
  for kernel in re.findall(
      r"%[\w.-]*?(flash_(?:window_)?(?:fwd|bwd))_*\.\d+ = [^\n]*"
      r"custom-call\(", text):
    calls[kernel] = calls.get(kernel, 0) + 1
  return calls


@pytest.mark.usefixtures("tpu_lowering")
class TestFlashMosaicLowering:

  @pytest.mark.parametrize("shape,causal,bq,bk", CONFIGS)
  def test_forward_lowers(self, shape, causal, bq, bk):
    s, h = _flash_shapes(shape)
    _export_for_tpu(
        lambda q, k, v: attention.flash_attention(
            q, k, v, h, causal=causal, block_q=bq, block_k=bk,
            interpret=False), s, s, s)

  @pytest.mark.parametrize("shape,causal,bq,bk", CONFIGS)
  def test_backward_lowers(self, shape, causal, bq, bk):
    s, h = _flash_shapes(shape)
    _export_for_tpu(_flash_grads(h, causal=causal, block_q=bq, block_k=bk),
                    s, s, s)

  def test_lowered_module_contains_mosaic_kernels(self):
    s, h = _flash_shapes((2, 2, 256, 64))
    exported = _export_for_tpu(
        lambda q, k, v: attention.flash_attention(q, k, v, h, causal=True,
                                                  interpret=False),
        s, s, s)
    text = exported.mlir_module()
    assert "tpu_custom_call" in text, "flash did not lower via Mosaic"

  def test_default_interpret_lowers_mosaic_for_tpu(self):
    """interpret=None (every model-path call: MultiHeadAttention,
    ulysses inner='flash') must select the REAL kernel per lowering
    platform. Regression for the seqattn incident: the old
    jax.default_backend() auto-select baked the CPU host backend into
    TPU-target AOT programs, so 'flash' compile facts silently priced
    the interpreter emulation."""
    s, h = _flash_shapes((2, 2, 256, 64))
    exported = _export_for_tpu(
        lambda q, k, v: attention.flash_attention(q, k, v, h, causal=True),
        s, s, s)
    assert "tpu_custom_call" in exported.mlir_module(), (
        "default-interpret flash lowered the interpreter emulation "
        "into a TPU-target program")
    # The backward pass too (the custom-vjp kernels ride the same
    # auto-select).
    grads = _export_for_tpu(
        lambda q, k, v: jax.grad(
            lambda q_, k_, v_: attention.flash_attention(
                q_, k_, v_, h, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v), s, s, s)
    assert "tpu_custom_call" in grads.mlir_module()

  def test_backward_is_one_mosaic_kernel(self):
    """PR 32: dQ, dK and dV come out of one kernel, `flash_bwd`. A
    gradient's module holds it once beside the forward, and none of the
    two kernels it replaced."""
    s, h = _flash_shapes((2, 8, 1024, 64))
    text = _export_for_tpu(_flash_grads(h, causal=True), s, s,
                           s).mlir_module()
    assert text.count('kernel_name = "flash_fwd"') == 1
    assert text.count('kernel_name = "flash_bwd"') == 1
    assert text.count("kernel_name = ") == 2

  @pytest.mark.parametrize("t", [8192, 8000])
  def test_long_context_train_graph_compiles(self, t, v5e_devices):
    """The kernel embedded in a model-like graph (projections + grad)
    must COMPILE at long T, not just lower: without the optimization
    barriers XLA:TPU fuses the surrounding layout ops into the
    custom-call's scoped-VMEM region and T=8192 dies with
    RESOURCE_EXHAUSTED 'allocating on stack' (the bare-kernel tests
    above can't see it). T=8000 covers the non-block-multiple path,
    where the pad ops sit between the projections and the kernel — the
    barriers must bind to the padded operands, not the pre-pad ones."""
    mesh = Mesh(v5e_devices[:1], ("data",))
    repl = NamedSharding(mesh, PartitionSpec())
    bsz, h, d, f = 2, 8, 64, 512
    xs = jax.ShapeDtypeStruct((bsz, t, f), jnp.bfloat16, sharding=repl)
    ws = jax.ShapeDtypeStruct((f, h * d), jnp.bfloat16, sharding=repl)

    def loss(x, wq, wk, wv):
      out = attention.flash_attention(x @ wq, x @ wk, x @ wv, h,
                                      causal=True, interpret=False)
      return out.astype(jnp.float32).sum()

    jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        xs, ws, ws, ws).compile()

  @pytest.mark.parametrize("batch", [1, 2])
  def test_sixteen_heads_of_256_compile_at_t4096(self, batch, one_chip):
    """The gated-attention layer of `configs/train_qwen3next_ep16share.gin`
    (`qwen3next_train_T4096`: one head of 256 a program, the reduced
    denominator, T 4096, the longest the forward takes at this head),
    forward and the one backward kernel, through the chip's whole compiler:
    the backward states 23.3 MB of VMEM here, past Mosaic's default 16."""
    shape = jax.ShapeDtypeStruct((batch, 4096, 16 * 256), jnp.bfloat16,
                                 sharding=one_chip)
    text = jax.jit(_flash_grads(16, causal=True)).lower(
        shape, shape, shape).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd" in text

  def test_thirty_two_heads_of_128_compile_at_t4096(self, one_chip):
    """The attention layer of `configs/train_nemotron3nano_ep16share.gin`
    (`nemotron3nano_train_T4096`: one head of 128 a program, 32 of them at
    T 4096), forward and the one backward kernel, through the chip's whole
    compiler: the d 128 side of `lane_block` and `_sum_rides`."""
    shape = jax.ShapeDtypeStruct((1, 4096, 32 * 128), jnp.bfloat16,
                                 sharding=one_chip)
    text = jax.jit(_flash_grads(32, causal=True)).lower(
        shape, shape, shape).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd" in text

  @pytest.mark.parametrize("shape,window,bq,bk", [
      ((1, 4, 256, 32), 40, 64, 64),     # four heads a program
      ((1, 2, 300, 128), 17, 128, 64),   # padded; 17 divides no block
      ((1, 2, 64, 128), 16, 64, 64)])    # sub-128 blocks
  def test_windowed_kernels_lower(self, shape, window, bq, bk):
    s, h = _flash_shapes(shape)
    text = _export_for_tpu(_flash_grads(h, causal=True, window=window,
                                        block_q=bq, block_k=bk),
                           s, s, s).mlir_module()
    assert text.count('kernel_name = "flash_window_fwd"') == 1
    assert text.count('kernel_name = "flash_window_bwd"') == 1
    assert text.count("kernel_name = ") == 2

  def test_laguna_layers_flash_compiles_at_t4096(self, one_chip):
    """The attention of `configs/train_laguna_xs2_ep16share.gin`'s layers
    through the chip's whole compiler, forward and the one backward kernel:
    a sliding layer's 64 heads of 128 over a window of 512 (the windowed
    kernels alone) and a global layer's 48 heads causal."""
    for heads, window, present in (
        (64, 512, ("flash_window_fwd", "flash_window_bwd")),
        (48, None, ("flash_fwd", "flash_bwd"))):
      shape = jax.ShapeDtypeStruct((1, 4096, heads * 128), jnp.bfloat16,
                                   sharding=one_chip)
      text = jax.jit(_flash_grads(heads, causal=True, window=window)).lower(
          shape, shape, shape).compile().as_text()
      assert set(_flash_calls(text)) == set(present), heads

  def test_f32_inputs_lower(self):
    s, h = _flash_shapes((1, 2, 256, 64), jnp.float32)
    _export_for_tpu(
        lambda q, k, v: attention.flash_attention(q, k, v, h,
                                                  interpret=False),
        s, s, s)


# The chunked layout of the hybrid decoder cell's delta rule: 64 chunks x 1
# sequence x 32 value heads of [64, 64].
_CELL_INVERSE = (64, 1, 32, 64, 64)


def _keeps_the_chunked_layout(custom_call: str, shape) -> bool:
  """Operand and result of the instruction name `[N,B,H,C`: the string
  `gdn_scan_ms` finds the delta rule's ops by."""
  layout = "[" + ",".join(str(d) for d in shape[:4])
  result, _, operands = custom_call.partition("custom-call(")
  return layout in result and layout in operands


class TestDeltaRuleInverseMosaicLowering:
  """`gdn_inverse` (ops/linear_attention.py): the Mosaic lowering, and the
  chip's compiler on it at the cell's shape."""

  def test_default_interpret_lowers_mosaic_for_tpu(self, tpu_lowering):
    """interpret=None takes the kernel per lowering platform, forward and
    through the closed-form backward's residual."""
    a = jax.ShapeDtypeStruct((4, 2, 4, 64, 64), jnp.float32)
    inverse = linear_attention._inverse_of_unit_lower
    assert "tpu_custom_call" in _export_for_tpu(inverse, a).mlir_module()
    assert "tpu_custom_call" in _export_for_tpu(
        jax.grad(lambda x: inverse(x).sum()), a).mlir_module()

  @pytest.mark.parametrize("shape", [
      _CELL_INVERSE, (64, 2, 32, 64, 64), (8, 1, 3, 64, 64),
      (8, 1, 32, 16, 16), (8, 2, 4, 8, 8), (8, 1, 6, 24, 24),
      (8, 1, 16, 128, 128)])
  def test_compiles_for_v5e(self, shape, one_chip):
    """Every chunk size `_kernel_takes` says Mosaic tiles: heads side by
    side in the lanes where 128 / C of them divide the group, one a tile
    where not (C 24, 3 heads, C 128)."""
    assert linear_attention._kernel_takes(shape)
    a = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    inverse = lambda x: linear_attention._inverse_of_unit_lower(  # noqa: E731
        x, False)
    calls = [line for line in jax.jit(inverse).lower(a).compile()
             .as_text().splitlines() if "custom-call(" in line]
    assert len(calls) == 1 and "gdn_inverse" in calls[0]
    assert _keeps_the_chunked_layout(calls[0], shape), calls[0]
    text = jax.jit(jax.grad(lambda x: inverse(x).sum())).lower(
        a).compile().as_text()
    assert text.count("custom-call(") == 1   # the backward is XLA's own


class TestDeltaRuleScanMosaicLowering:
  """`gated_delta_rule` (ops/linear_attention.py): the Mosaic lowering, and
  the chip's compiler on the forward and backward kernels at the hybrid
  cell's shape."""

  def test_default_interpret_lowers_mosaic_for_tpu(self, tpu_lowering):
    """interpret=None takes the kernels per lowering platform; one forward
    (the one that keeps the states and inverses) and one backward a
    gradient."""
    shapes = (jax.ShapeDtypeStruct((1, 128, 4 * 128), jnp.bfloat16),
              jax.ShapeDtypeStruct((1, 128, 2), jnp.float32),
              jax.ShapeDtypeStruct((1, 128, 2), jnp.float32))
    module = _export_for_tpu(
        jax.grad(lambda *a: linear_attention.gated_delta_rule(
            *a, 1, 128, matmul_dtype=jnp.bfloat16)[0].sum(),
                 argnums=(0, 1, 2)), *shapes).mlir_module()
    assert module.count('kernel_name = "gdn_scan"') == 1
    assert module.count('kernel_name = "gdn_scan_bwd"') == 1

  def test_forward_and_backward_compile_for_v5e(self, one_chip):
    """T 4096, 16 key heads and 32 value heads of 128, chunks of 64: one
    call of each kernel, both reading the convolution's whole result in
    place (no slice of q, k or v and no repeat laid out), the cotangent of
    that result in its dtype and g's and beta's in float32; no loop over
    chunks."""
    spec = lambda dims, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)

    def rule(m, g, beta, do, dlast):
      out, vjp = jax.vjp(lambda *a: linear_attention.gated_delta_rule(
          *a, 16, 128, matmul_dtype=jnp.bfloat16, interpret=False),
                         m, g, beta)
      return out + vjp((do, dlast))

    compiled = jax.jit(rule).lower(
        spec((1, 4096, 8192), jnp.bfloat16), spec((1, 4096, 32), jnp.float32),
        spec((1, 4096, 32), jnp.float32),
        spec((1, 4096, 32, 128), jnp.float32),
        spec((1, 32, 128, 128), jnp.float32)).compile()
    text = compiled.as_text()
    calls = _named_calls(text, "gdn_scan")
    assert sorted(kernel for kernel, _ in calls) == [
        "gdn_scan", "gdn_scan_bwd"]
    assert [_first_operand_shape(text, line) for _, line in calls] == [
        "bf16[1,4096,8192]"] * 2
    assert " while(" not in text and "gdn_inverse" not in text
    assert _channel_copies(text, 2048) == []
    assert [info.dtype for info in compiled.out_info[2:]] == [
        jnp.dtype(jnp.bfloat16)] + [jnp.dtype(jnp.float32)] * 2


def _decode_tick_shapes(s_sz, t, b, h, d, sharding=None):
  kw = {} if sharding is None else {"sharding": sharding}
  lane = jax.ShapeDtypeStruct((b, h, d), jnp.float32, **kw)
  arena = jax.ShapeDtypeStruct((s_sz, t, h, d), jnp.float32, **kw)
  i32 = jax.ShapeDtypeStruct((b,), jnp.int32, **kw)
  lanes = jax.ShapeDtypeStruct((b,), jnp.bool_, **kw)
  return lane, lane, lane, arena, arena, i32, i32, lanes


# The experts' grouped products, [rows, K] x [G, K, N], in both expert
# cells: nemotron's up and down, qwen3next's gate-and-up and down.
_CELL_GROUPED = [(6144, 8, 2688, 1856), (6144, 8, 1856, 2688),
                 (20480, 32, 2048, 1024), (20480, 32, 512, 2048)]


def _grouped_calls(text: str) -> list:
  """The custom calls of a compiled program that run the op's kernels:
  (kernel, the instruction's line) a call. XLA names the instruction after
  the kernel (`%grouped_matmul_t.9`, `%transpose_jvp_grouped_matmul__.1`)."""
  calls = []
  for line in text.splitlines():
    name = line.split(" = ")[0]
    if "custom-call(" in line and "grouped_matmul" in name:
      calls.append(("grouped_matmul_t" if "grouped_matmul_t" in name
                    else "grouped_matmul", line))
  return calls


class TestGroupedMatmulMosaicLowering:
  """`ops/grouped_matmul.py`: the Mosaic lowering, and the chip's compiler
  on its three products at both expert cells' shapes."""

  def test_default_interpret_lowers_mosaic_for_tpu(self, tpu_lowering):
    lhs = jax.ShapeDtypeStruct((256, 128), jnp.bfloat16)
    rhs = jax.ShapeDtypeStruct((4, 128, 116), jnp.bfloat16)
    sizes = jax.ShapeDtypeStruct((4,), jnp.int32)
    module = _export_for_tpu(
        jax.grad(lambda a, b, s: grouped_matmul.grouped_matmul(
            a, b, s).sum(), argnums=(0, 1)), lhs, rhs, sizes).mlir_module()
    # the sum's gradient needs no forward product: one kernel a cotangent
    assert module.count('kernel_name = "grouped_matmul"') == 1
    assert module.count('kernel_name = "grouped_matmul_t"') == 1

  @pytest.mark.parametrize("rows,groups,k,n", _CELL_GROUPED)
  def test_three_products_compile_for_v5e(self, rows, groups, k, n,
                                          one_chip):
    """Forward, the rows' cotangent and the weights' cotangent: two calls
    of `grouped_matmul` and one of `grouped_matmul_t`, results float32
    and the cotangents bfloat16, and no copy of the weights beside them
    (a width of 1856 goes to the kernels behind the 2688: the docstring's
    rule)."""
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)

    def products(lhs, rhs, sizes, cotangent):
      out, vjp = jax.vjp(lambda a, b: grouped_matmul.grouped_matmul(
          a, b, sizes, interpret=False), lhs, rhs)
      return (out,) + vjp(cotangent)

    compiled = jax.jit(products).lower(
        shape((rows, k), jnp.bfloat16), shape((groups, k, n), jnp.bfloat16),
        shape((groups,), jnp.int32), shape((rows, n), jnp.float32)).compile()
    text = compiled.as_text()
    assert sorted(kernel for kernel, _ in _grouped_calls(text)) == [
        "grouped_matmul", "grouped_matmul", "grouped_matmul_t"]
    weights = {f"bf16[{groups},{k},{n}]", f"bf16[{groups},{n},{k}]"}
    copies = [line for line in text.splitlines() if " copy(" in line
              and any(w in line.split(" copy(")[0] for w in weights)]
    assert copies == [], copies
    out, dlhs, drhs = compiled.out_info
    assert (out.dtype, dlhs.dtype, drhs.dtype) == (
        jnp.float32, jnp.bfloat16, jnp.bfloat16)


# The two mixers' short convolution in both expert cells: (operand, first
# column, channels, bias): qwen3next's [q, k, v] of `in_proj_qkvz`,
# nemotron's [x, B, C] of `in_proj`.
_CELL_CONV = [((1, 4096, 12288), 0, 8192, False),
              ((1, 4096, 10304), 4096, 6144, True)]


def _named_calls(text: str, prefix: str) -> list:
  """(kernel, the instruction's line) for each custom call of a compiled
  program whose kernel's name starts with `prefix` (XLA names the
  instruction after the kernel: `%short_conv.6`, `%ssd_scan_bwd.3`)."""
  calls = []
  for line in text.splitlines():
    name = line.split(" = ")[0].strip().lstrip("ROOT ").lstrip("%")
    if "custom-call(" in line and name.startswith(prefix):
      calls.append((name.rsplit(".", 1)[0], line))
  return calls


def _first_operand_shape(text: str, line: str) -> str:
  """The shape, as `bf16[1,4096,12288]`, of the instruction's first
  operand, looked up by its name in the program's text."""
  first = re.match(r"%?([\w.\-]+)", line.split("custom-call(")[1]).group(1)
  found = re.search(rf"^\s*(?:ROOT )?%{re.escape(first)} = (\w+\[[\d,]*\])",
                    text, re.M)
  return found.group(1)


def _channel_copies(text: str, channels: int) -> list:
  """Synchronous copies of a [1, 4096, channels] array: a slice of the
  conv's channels laid out again beside the kernel."""
  return [line for line in text.splitlines()
          if re.search(rf"= \w+\[1,4096,{channels}\]\{{[^}}]*\}} copy\(",
                       line)]


class TestShortConvMosaicLowering:
  """`ops/short_conv.py`: the Mosaic lowering, and the chip's compiler on
  the forward and backward kernels at both expert cells' shapes."""

  def test_default_interpret_lowers_mosaic_for_tpu(self, tpu_lowering):
    x = jax.ShapeDtypeStruct((1, 64, 512), jnp.bfloat16)
    kernel = jax.ShapeDtypeStruct((4, 256), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((256,), jnp.bfloat16)
    # (a square, so that the cotangent needs the forward's result)
    module = _export_for_tpu(
        jax.grad(lambda a, k, b: jnp.square(short_conv.causal_conv_silu(
            a, k, b, 128).astype(jnp.float32)).sum(), argnums=(0, 1, 2)),
        x, kernel, bias).mlir_module()
    assert module.count('kernel_name = "short_conv"') == 1
    assert module.count('kernel_name = "short_conv_bwd"') == 1

  @pytest.mark.parametrize("shape,start,channels,bias", _CELL_CONV)
  def test_forward_and_backward_compile_for_v5e(self, shape, start,
                                                channels, bias, one_chip):
    """One call of each kernel, both reading the projection's result in
    place (its whole shape is their operand: no slice of the channels is
    written), the cotangents in the operands' dtypes."""
    spec = lambda dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, jnp.bfloat16, sharding=one_chip)

    def conv(x, kernel, b, dy):
      y, vjp = jax.vjp(lambda *a: short_conv.causal_conv_silu(
          *a, start=start, interpret=False), x, kernel, b)
      return (y,) + vjp(dy)

    compiled = jax.jit(conv).lower(
        spec(shape), spec((4, channels)), spec((channels,)) if bias else None,
        spec(shape[:2] + (channels,))).compile()
    text = compiled.as_text()
    calls = _named_calls(text, "short_conv")
    assert sorted(kernel for kernel, _ in calls) == [
        "short_conv", "short_conv_bwd"]
    whole = "bf16[" + ",".join(str(d) for d in shape) + "]"
    assert [_first_operand_shape(text, line) for _, line in calls] == [
        whole, whole]
    assert {info.dtype for info in jax.tree_util.tree_leaves(
        compiled.out_info)} == {jnp.dtype(jnp.bfloat16)}


class TestStateSpaceMosaicLowering:
  """`ops/state_space.py`: the Mosaic lowering, and the chip's compiler on
  the forward and backward kernels at the nemotron cell's shape."""

  def test_default_interpret_lowers_mosaic_for_tpu(self, tpu_lowering):
    """interpret=None takes the kernels per lowering platform; one forward
    (the one that keeps the states) and one backward a gradient."""
    shapes = (jax.ShapeDtypeStruct((1, 256, 2 * 64 + 2 * 128), jnp.bfloat16),
              jax.ShapeDtypeStruct((1, 256, 2), jnp.float32),
              jax.ShapeDtypeStruct((2,), jnp.float32),
              jax.ShapeDtypeStruct((2,), jnp.float32))
    module = _export_for_tpu(
        jax.grad(lambda *a: state_space.ssd_scan(
            *a, 1, 128, matmul_dtype=jnp.bfloat16)[0].sum(),
                 argnums=(0, 1, 2, 3)), *shapes).mlir_module()
    assert module.count('kernel_name = "ssd_scan"') == 1
    assert module.count('kernel_name = "ssd_scan_bwd"') == 1

  def test_forward_and_backward_compile_for_v5e(self, one_chip):
    """T 4096, 64 heads of 64, 8 groups, N 128, chunks of 128: one call of
    each kernel, both reading the convolution's whole result in place, the
    cotangent of that result in its dtype and dt's, a_log's and D's in
    float32; no loop over chunks and nothing laid out again."""
    spec = lambda dims, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    mixed = (1, 4096, 6144)

    def scan(m, dt, a_log, d, dy, dlast):
      out, vjp = jax.vjp(lambda *a: state_space.ssd_scan(
          *a, 8, 128, matmul_dtype=jnp.bfloat16, interpret=False),
                         m, dt, a_log, d)
      return out + vjp((dy, dlast))

    compiled = jax.jit(scan).lower(
        spec(mixed, jnp.bfloat16), spec((1, 4096, 64), jnp.float32),
        spec((64,), jnp.float32), spec((64,), jnp.float32),
        spec((1, 4096, 4096), jnp.float32),
        spec((1, 64, 64, 128), jnp.float32)).compile()
    text = compiled.as_text()
    calls = _named_calls(text, "ssd_scan")
    assert sorted(kernel for kernel, _ in calls) == [
        "ssd_scan", "ssd_scan_bwd"]
    assert [_first_operand_shape(text, line) for _, line in calls] == [
        "bf16[1,4096,6144]"] * 2
    assert " while(" not in text
    assert _channel_copies(text, 6144) == []
    assert [info.dtype for info in compiled.out_info[2:]] == [
        jnp.dtype(jnp.bfloat16)] + [jnp.dtype(jnp.float32)] * 3


class TestDecodeKernelMosaicLowering:
  """graftkern (ISSUE 20): the fused decode-tick kernel lowers via
  Mosaic for TPU. `interpret=None` resolves from the PROCESS backend at
  trace time (correct in the serving engine, which compiles for the
  backend it runs on), so a TPU-target program built on this CPU host
  must pass interpret=False explicitly — exactly what a real TPU serving
  process resolves to."""

  @pytest.mark.parametrize("t,block_k", [(32, 8), (96, 32), (512, 128)])
  def test_fused_decode_tick_lowers_mosaic(self, t, block_k,
                                           tpu_lowering):
    from tensor2robot_tpu.ops import decode_kernels

    exported = _export_for_tpu(
        lambda q, kn, vn, ka, va, sl, ix, mk:
            decode_kernels.fused_decode_attention(
                q, kn, vn, ka, va, sl, ix, mk, block_k=block_k,
                interpret=False),
        *_decode_tick_shapes(9, t, 4, 4, 64))
    assert "tpu_custom_call" in exported.mlir_module(), (
        "fused decode tick did not lower via Mosaic")

  # Lowering stops before Mosaic -> machine code, where the fast-memory
  # limit and the (8, 128) tile alignment are enforced: these COMPILE.
  # (s, t, b, h, d): the arena `SessionEngine` builds for
  # configs/serve_session.gin (64 sessions + the null slot, horizon 32,
  # 4 heads x 16 — a quarter lane tile — at its smallest and largest
  # tick bucket), then the widths ROADMAP S5 measures at (B 64, Tmax
  # 512, head_dim 64 and 128).
  @pytest.mark.parametrize("s_sz,t,b,h,d,block_k", [
      (65, 32, 1, 4, 16, 8),
      (65, 32, 8, 4, 16, 8),
      (129, 512, 64, 8, 64, 8),
      (129, 512, 64, 8, 64, 128),
      (129, 512, 64, 8, 128, 8),
      (129, 512, 64, 8, 128, 128),
  ])
  def test_fused_decode_tick_compiles_for_v5e(self, s_sz, t, b, h, d,
                                              block_k, one_chip):
    from tensor2robot_tpu.ops import decode_kernels

    compiled = jax.jit(
        lambda q, kn, vn, ka, va, sl, ix, mk:
            decode_kernels.fused_decode_attention(
                q, kn, vn, ka, va, sl, ix, mk, block_k=block_k,
                interpret=False),
        donate_argnums=(3, 4)).lower(
            *_decode_tick_shapes(s_sz, t, b, h, d, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()

  def test_serve_session_config_arena_is_the_shape_compiled_above(self):
    """The first two cases above ARE serve_session.gin's arena."""
    from tensor2robot_tpu import serving
    from tensor2robot_tpu.models import sequence_model
    from tensor2robot_tpu.utils import config

    config.clear_config()
    try:
      config.parse_config_files_and_bindings(
          [os.path.join(_PACKAGE, "configs", "serve_session.gin")], [])
      model = sequence_model.SequenceRegressionModel()
      slots = config.query_parameter("SessionEngine.max_sessions") + 1
      lanes = serving.engine.bucket_ladder(
          config.query_parameter("SessionEngine.max_tick_batch"))
    finally:
      config.clear_config()
    arena = model.init_session_state(slots)
    assert arena["k_0"].shape == (65, 32, 4, 16)
    assert (lanes[0], lanes[-1]) == (1, 8)


def _uniform_shapes(tree, sharding):
  """ShapeDtypeStructs for a tree with one sharding everywhere."""
  return jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
      tree, is_leaf=lambda x: hasattr(x, "shape"))


def _compile_step_for_mesh(model, mesh, batch, rules=None, donate=False):
  """Compiles the PRODUCTION-sharded program: state shardings from the
  model's partition rules (not replicated) and batches on the model's
  own batch_partition_spec (e.g. ('data', 'sp') for ring attention) —
  the same layout train_eval/create_train_state deploy."""
  return _lower_step_for_mesh(model, mesh, batch, rules, donate).compile()


def _cost_analysis(compiled) -> dict:
  """The compiler's cost record (older jax wraps it in a list)."""
  cost = compiled.cost_analysis()
  return cost[0] if isinstance(cost, (list, tuple)) else cost


def _lower_step_for_mesh(model, mesh, batch, rules=None, donate=False):
  """`_compile_step_for_mesh` up to the lowered program."""
  from tensor2robot_tpu import specs as specs_lib
  from tensor2robot_tpu.parallel import train_step as ts

  features = specs_lib.make_random_numpy(
      model.preprocessor.get_out_feature_specification("train"),
      batch_size=batch, seed=0)
  labels = specs_lib.make_random_numpy(
      model.preprocessor.get_out_label_specification("train"),
      batch_size=batch, seed=1)
  state_shape = jax.eval_shape(
      lambda rng, f: ts.create_train_state(model, rng, f)[0],
      jax.random.PRNGKey(0), features)
  shardings = ts.state_shardings(state_shape, mesh, rules=rules)
  batch_spec = getattr(model, "batch_partition_spec", None)
  batch_sh = NamedSharding(mesh, batch_spec or PartitionSpec("data"))

  def shapes(tree, sharding_tree):
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding_tree,
        is_leaf=lambda x: hasattr(x, "shape"))

  step = ts.make_train_step(model, mesh=mesh, shardings=shardings,
                            batch_spec=batch_spec, donate=donate)
  return step.lower(shapes(state_shape, shardings),
                    _uniform_shapes(features, batch_sh),
                    _uniform_shapes(labels, batch_sh))


def _compile_loop_for_mesh(model, mesh, batch, loop_k, rules=None):
  """Same production layout as `_compile_step_for_mesh` but through
  `make_train_loop`: the K-step scan loop must compile with the same
  sharded state + the scan-axis-extended batch sharding."""
  from tensor2robot_tpu import specs as specs_lib
  from tensor2robot_tpu.parallel import train_step as ts

  features = specs_lib.make_random_numpy(
      model.get_feature_specification("train"), batch_size=batch, seed=0)
  labels = specs_lib.make_random_numpy(
      model.get_label_specification("train"), batch_size=batch, seed=1)
  stack = lambda tree: jax.tree_util.tree_map(
      lambda x: np.stack([x] * loop_k), tree)
  features, labels = stack(features), stack(labels)
  state_shape = jax.eval_shape(
      lambda rng, f: ts.create_train_state(
          model, rng, jax.tree_util.tree_map(lambda x: x[0], f))[0],
      jax.random.PRNGKey(0), features)
  shardings = ts.state_shardings(state_shape, mesh, rules=rules)
  batch_spec = getattr(model, "batch_partition_spec", None)
  loop_sh = NamedSharding(mesh, ts.loop_batch_spec(batch_spec))

  def shapes(tree, sharding_tree):
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding_tree,
        is_leaf=lambda x: hasattr(x, "shape"))

  loop = ts.make_train_loop(model, loop_k, mesh=mesh, shardings=shardings,
                            batch_spec=batch_spec, donate=False)
  return loop.lower(shapes(state_shape, shardings),
                    _uniform_shapes(features, loop_sh),
                    _uniform_shapes(labels, loop_sh)).compile()


def _activation_transposes(lowered_text):
  """The transposes of a lowered step that move an activation: every
  `stablehlo.transpose` but the plain ones of a 2-D weight matrix (the
  dense layers' backward)."""
  return [line.strip() for line in lowered_text.splitlines()
          if "stablehlo.transpose" in line and "dims = [1, 0]" not in line]


def _trainer_mesh(devices):
  """The (data, fsdp, model) mesh `train_eval_model` builds by default."""
  return Mesh(np.asarray(devices).reshape(-1, 1, 1),
              ("data", "fsdp", "model"))


def _assert_convs_in_place(text, layers, operand, channels, scope):
  """The mixers' short convolution as `ops/short_conv.py`'s kernels, one
  call a layer in the forward, the recomputed forward and the backward
  (so the op table names them, under the mixer's scope), each reading the
  in-projection's whole result (no slice of the channels laid out again
  beside it)."""
  from tensor2robot_tpu.obs import xray

  calls = _named_calls(text, "short_conv")
  assert sorted(kernel for kernel, _ in calls) == (
      ["short_conv"] * 2 * layers + ["short_conv_bwd"] * layers)
  table = xray.build_op_table(text)
  entries = [xray.op_entry(table, line) for _, line in calls]
  assert {e["scope"] for e in entries} == {scope}
  assert sorted(e["phase"] for e in entries) == sorted(
      ["forward", "recompute", "backward"] * layers)
  whole = "bf16[" + ",".join(str(d) for d in operand) + "]"
  assert {_first_operand_shape(text, line) for _, line in calls} == {whole}
  assert _channel_copies(text, channels) == []


def _assert_scans_in_place(text, layers, operand):
  """The state-space scan as `ops/state_space.py`'s kernels under the
  mixer's scope: `ssd_scan` a layer in the forward and the recomputed
  forward, `ssd_scan_bwd` a layer in the backward, each reading the
  convolution's whole result; no XLA loop carrying a group's
  [8 heads, 64, 128] state over chunks, and none of the result's columns
  laid out again."""
  from tensor2robot_tpu.obs import xray

  calls = _named_calls(text, "ssd_scan")
  assert sorted(kernel for kernel, _ in calls) == (
      ["ssd_scan"] * 2 * layers + ["ssd_scan_bwd"] * layers)
  table = xray.build_op_table(text)
  entries = [xray.op_entry(table, line) for _, line in calls]
  assert {e["scope"] for e in entries} == {"ssm_scan"}
  assert sorted(e["phase"] for e in entries) == sorted(
      ["forward", "recompute", "backward"] * layers)
  whole = "bf16[" + ",".join(str(d) for d in operand) + "]"
  assert {_first_operand_shape(text, line) for _, line in calls} == {whole}
  loops = [line for line in text.splitlines()
           if " while(" in line and "f32[1,8,8,64,128]" in line]
  assert loops == [], loops
  assert _channel_copies(text, operand[2]) == []


def _assert_rules_in_place(text, layers, operand):
  """The gated delta rule as `ops/linear_attention.py`'s kernels under the
  mixer's scope: `gdn_scan` a layer in the forward and the recomputed
  forward, `gdn_scan_bwd` a layer in the backward, each reading the
  convolution's whole result; no XLA loop carrying the [32, 128, 128]
  state over chunks, and none of the result's columns laid out again."""
  from tensor2robot_tpu.obs import xray

  calls = _named_calls(text, "gdn_scan")
  assert sorted(kernel for kernel, _ in calls) == (
      ["gdn_scan"] * 2 * layers + ["gdn_scan_bwd"] * layers)
  table = xray.build_op_table(text)
  entries = [xray.op_entry(table, line) for _, line in calls]
  assert {e["scope"] for e in entries} == {"gdn_scan"}
  assert sorted(e["phase"] for e in entries) == sorted(
      ["forward", "recompute", "backward"] * layers)
  whole = "bf16[" + ",".join(str(d) for d in operand) + "]"
  assert {_first_operand_shape(text, line) for _, line in calls} == {whole}
  loops = [line for line in text.splitlines()
           if " while(" in line and "f32[1,32,128,128]" in line]
  assert loops == [], loops
  assert _channel_copies(text, operand[2]) == []


def _expert_products(text: str) -> tuple:
  """(forward products, rows' cotangents, weights' cotangents, XLA's own
  grouped products left) in a compiled step: four expert layers x up and
  down, each forward twice under rematerialisation, make (16, 8, 8, 0).
  The forward's result is float32, the rows' cotangent bfloat16."""
  calls = _grouped_calls(text)
  plain = [line for kernel, line in calls if kernel == "grouped_matmul"]
  forward = [line for line in plain if re.search(r" = f32\[", line)]
  return (len(forward), len(plain) - len(forward), len(calls) - len(plain),
          text.count("ragged-dot"))


class TestShippedStepsCompileForV5e:
  """The steps `chip_smoke.py` trains, from the shipped configs it
  parses, at their shipped size, state donated as the trainer donates
  it."""

  # (sequence length, sequences a step; None = the config's own 2).
  # T 4096 at 16 and 64 sequences was REFUSED until PR 27 ("Scoped
  # allocation with size 16.05M and limit 16.00M"), and T 8192 at every
  # batch (20.75 MB): the dK/dV kernel held `lse` and `delta` as whole-T
  # [T, 1] columns, each padded to 128 lanes (1 MB each at T 2048, 4 MB
  # at T 8192) and double-buffered. Since then they arrive as lane-dense
  # rows. Since PR 32 the backward is one kernel (`_flash_bwd_kernel`) that
  # also holds dQ's whole-T strip (float32 accumulator and output block,
  # 4 + 4 MB at T 8192 beside 8 MB of q and dO), so it states its own
  # VMEM limit from those byte counts (`_bwd_vmem_bytes`): the least
  # limit that compiles is 9.8 MB at T 2048, 13.6 at T 4096 and 21.9 at
  # T 8192 (described v5e, PR 32), past the default 16 MB at the last.
  @pytest.mark.parametrize("seq_len,batch", [
      (4096, None), (4096, 16), (4096, 64), (8192, None), (2048, 128),
      (8192, 32)])
  def test_longcontext_flash_train_step_compiles(self, seq_len, batch,
                                                 v5e_devices):
    """Flash forward and the backward kernel INSIDE the train step at
    the `train_longcontext_flash.gin` shape (B2, H8, T4096, d64), at the
    benchmark cell's 128 x T 2048, at the batches the next sequence cells
    need (64 x T 4096 is `pool_b64_T4096`, 32 x T 8192) and at the T=8192
    the roadmap's third sequence cell asks for. Since PR 30 the kernels
    read [B, T, H x D] as the projections write it: nothing is laid out
    again between the two, neither as the step is lowered nor as the
    chip's compiler leaves it."""
    model, config_batch = _model_from_config(
        "configs/train_longcontext_flash.gin",
        [f"SequenceRegressionModel.sequence_length = {seq_len}"])
    batch = batch or config_batch
    lowered = _lower_step_for_mesh(
        model, _trainer_mesh(v5e_devices[:1]), batch, donate=True)
    assert _activation_transposes(lowered.as_text()) == []
    compiled = lowered.compile()
    text = compiled.as_text()
    # 2 blocks x (forward, backward).
    assert text.count("tpu_custom_call") >= 4
    assert f"bf16[{batch},8,{seq_len},64]" not in text  # no [B, H, T, D]
    assert compiled.memory_analysis().temp_size_in_bytes < 16e9

  def test_head_split_transposes_are_what_the_count_finds(self,
                                                          v5e_devices):
    """The count above can see them: the same step under the `reference`
    backend splits heads by transposing q, k, v and the output, and
    their four cotangents, in each of its two blocks."""
    model, _ = _model_from_config(
        "configs/train_longcontext_flash.gin",
        ["SequenceRegressionModel.sequence_length = 256",
         "SequenceRegressionModel.attention_backend = 'reference'"])
    lowered = _lower_step_for_mesh(
        model, _trainer_mesh(v5e_devices[:1]), 4, donate=True)
    found = _activation_transposes(lowered.as_text())
    assert sum("dims = [0, 2, 1, 3]" in line for line in found) == 16, found

  def test_hybrid_decoder_train_step_fits_one_chip(self, v5e_devices):
    """`configs/train_qwen3next_ep16share.gin` as shipped (1 x T 4096, 626 M
    parameters under Adam): the step compiles for one v5e, state and
    temporaries under the chip's 16 GB, with the flash kernels, the
    experts' grouped products as `grouped_matmul` / `grouped_matmul_t` (none
    of XLA's left) and one sort a layer in it, the delta rule as
    `gdn_scan` / `gdn_scan_bwd` reading the convolution's result in place
    (no loop over chunks, no `gdn_inverse`, no product of the chunked
    layout left); the short convolution as `short_conv` / `short_conv_bwd`
    in place."""
    model, batch = _model_from_config(
        "configs/train_qwen3next_ep16share.gin")
    compiled = _lower_step_for_mesh(
        model, _trainer_mesh(v5e_devices[:1]), batch, donate=True).compile()
    memory = compiled.memory_analysis()
    assert 7.4e9 < memory.argument_size_in_bytes < 7.6e9   # 626 M x 12 bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15e9
    text = compiled.as_text()
    assert "flash_fwd" in text and "flash_bwd" in text
    assert _expert_products(text) == (16, 8, 8, 0) and " sort(" in text
    lines = text.splitlines()
    assert not any("custom-call(" in line and "gdn_inverse" in line
                   for line in lines)
    # `highest` products with a [.., 64, 64] float32 result: 40 a layer
    # with the inverse in XLA (10 + 10 recomputed + autodiff's 20), 120 in
    # the step; 6 with `gdn_inverse` (its closed-form backward); none with
    # the rule as one op.
    products = [line for line in lines if "highest" in line
                and re.search(r"= f32\[[\d,]*64,64\]", line)]
    assert products == [], products
    assert memory.temp_size_in_bytes <= 5.30e9   # 5.30 GB before PR 34
    _assert_convs_in_place(text, 3, (1, 4096, 12288), 8192, "gdn_conv")
    _assert_rules_in_place(text, 3, (1, 4096, 8192))

  def test_mamba_experts_decoder_train_step_fits_one_chip(self,
                                                          v5e_devices):
    """`configs/train_nemotron3nano_ep16share.gin` as shipped (1 x T 4096,
    667 M parameters under Adam): the step compiles for one v5e, state and
    temporaries under the chip's 16 GB, with the flash kernels, the
    experts' grouped products as `grouped_matmul` / `grouped_matmul_t` (none
    of XLA's left), one sort an expert layer, the state-space scan as
    `ssd_scan` / `ssd_scan_bwd` reading the convolution's result in place
    (no loop over chunks left); the short convolution as `short_conv` /
    `short_conv_bwd` in place."""
    model, batch = _model_from_config(
        "configs/train_nemotron3nano_ep16share.gin")
    compiled = _lower_step_for_mesh(
        model, _trainer_mesh(v5e_devices[:1]), batch, donate=True).compile()
    memory = compiled.memory_analysis()
    assert 7.9e9 < memory.argument_size_in_bytes < 8.1e9   # 667 M x 12 bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15e9
    assert memory.temp_size_in_bytes <= 3.0e9    # 2.59 GB (3.89 at PR 35)
    text = compiled.as_text()
    assert "flash_fwd" in text and "flash_bwd" in text
    assert _expert_products(text) == (16, 8, 8, 0) and " sort(" in text
    assert "gdn_inverse" not in text
    _assert_convs_in_place(text, 4, (1, 4096, 10304), 6144, "ssm_conv")
    _assert_scans_in_place(text, 4, (1, 4096, 6144))

  def test_window_and_global_decoder_train_step_fits_one_chip(
      self, v5e_devices):
    """`configs/train_laguna_xs2_ep16share.gin` as shipped (1 x T 4096, 565 M
    parameters under Adam): the step compiles for one v5e, state and
    temporaries under the chip's 16 GB (6.78 + 2.13 GB, described v5e),
    the three sliding layers on the windowed kernels and the two
    global ones on the causal kernels (a forward, a recomputed forward and
    a backward each), the experts' grouped products as `grouped_matmul` /
    `grouped_matmul_t` and one sort a sparse layer."""
    model, batch = _model_from_config(
        "configs/train_laguna_xs2_ep16share.gin")
    compiled = _lower_step_for_mesh(
        model, _trainer_mesh(v5e_devices[:1]), batch, donate=True).compile()
    memory = compiled.memory_analysis()
    assert 6.7e9 < memory.argument_size_in_bytes < 6.9e9   # 565 M x 12 bytes
    assert memory.temp_size_in_bytes <= 2.5e9
    text = compiled.as_text()
    assert _flash_calls(text) == {"flash_window_fwd": 6, "flash_window_bwd": 3,
                                  "flash_fwd": 4, "flash_bwd": 2}
    assert _expert_products(text) == (16, 8, 8, 0) and " sort(" in text

  @pytest.mark.parametrize("config_file,traffic,layers,mixers", [
      ("configs/train_qwen3next_ep16share.gin", "pool_b1_T4096", 4, 3),
      ("configs/train_nemotron3nano_ep16share.gin", "pool_b1_T4096_v16384",
       2, 1)])
  def test_experts_engage_the_op_at_the_rehearsals_sizes(
      self, config_file, traffic, layers, mixers, v5e_devices):
    """The step of each expert configuration at its traffic file's `tiny`
    sizes, compiled for the chip: eight `grouped_matmul*` kernels an expert
    layer (four forward, two and two for the cotangents), none of XLA's
    grouped products; three `short_conv*` kernels a linear mixer (128
    channels: nemotron's start at column 64, so they take the slice)."""
    with open(os.path.join(_REPO_ROOT, "benchmarks", "traffic",
                           traffic + ".json")) as f:
      tiny = json.load(f)["tiny"]
    model, _ = _model_from_config(config_file, [
        b for b in tiny["bindings"] if "device_type" not in b])
    lowered = _lower_step_for_mesh(
        model, _trainer_mesh(v5e_devices[:1]), tiny["batch_size"],
        donate=True)
    assert "ragged_dot" not in lowered.as_text()
    text = lowered.compile().as_text()
    assert _expert_products(text) == (4 * layers, 2 * layers, 2 * layers, 0)
    calls = _named_calls(text, "short_conv")
    assert sorted(kernel for kernel, _ in calls) == (
        ["short_conv"] * 2 * mixers + ["short_conv_bwd"] * mixers)

  def test_tuned_grasping44_train_step_fits_one_chip(self, v5e_devices):
    """Grasping44 @472, batch 256, bf16 (train_qtopt_tpu_tuned.gin): the
    program's arguments, outputs and temporaries together stay under a
    v5e's 16 GB."""
    model, batch = _model_from_config(
        "research/qtopt/configs/train_qtopt_tpu_tuned.gin")
    assert batch == 256
    memory = _compile_step_for_mesh(
        model, _trainer_mesh(v5e_devices[:1]), batch,
        donate=True).memory_analysis()
    total = (memory.argument_size_in_bytes + memory.output_size_in_bytes
             + memory.temp_size_in_bytes - memory.alias_size_in_bytes)
    assert 1e9 < total < 16e9, memory


class TestServingCompilesForV5e:
  """The on-device CEM action-selection loop (the serving hot path:
  Grasping44 critic scored over 64 samples x 3 iterations inside one
  jitted call) compiles for v5e — at a reduced image scale, and at the
  472 px `chip_smoke.py` serves, scored as it scores there (on the
  critic's logits: a fresh bf16 critic's Q is 0.5 in every row)."""

  @pytest.mark.parametrize("image_size,q_key", [(256, "q_predicted"),
                                                (472, "logits")])
  def test_device_cem_select_compiles(self, image_size, q_key, one_chip):
    from tensor2robot_tpu import modes, specs as specs_lib
    from tensor2robot_tpu.parallel import train_step as ts
    from tensor2robot_tpu.policies import device_cem
    from tensor2robot_tpu.research.qtopt import flagship

    # The ONE flagship constructor: this CI guard stays the twin of the
    # AOT script's serving mode.
    model = flagship.make_flagship_model("tpu", image_size=image_size)
    features = specs_lib.make_random_numpy(
        model.preprocessor.get_out_feature_specification(modes.TRAIN),
        batch_size=2, seed=0)
    state_shape = jax.eval_shape(
        lambda rng, f: ts.create_train_state(model, rng, f)[0],
        jax.random.PRNGKey(0), features)
    select = device_cem.make_device_cem_fn(
        model, action_size=flagship.ACTION_SIZE, q_key=q_key)
    obs = {"image": jax.ShapeDtypeStruct((image_size, image_size, 3),
                                         jnp.uint8, sharding=one_chip)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    select.lower(_uniform_shapes(state_shape, one_chip), obs,
                 rng).compile()


class TestParallelStacksCompileForV5e:
  """The REAL XLA:TPU compiler (local libtpu, AOT topology) compiles
  each parallel-execution stack for a multi-chip v5e mesh — actual ICI
  collectives (ppermute ring hops, all_to_all, the heterogeneous-PP
  lax.switch schedule), beyond what the CPU virtual-device dryrun
  executes. Each case is a few seconds of compile time."""

  def test_ring_attention_sp_compiles(self, v5e_devices):
    import optax

    from tensor2robot_tpu.models import sequence_model

    mesh = Mesh(v5e_devices.reshape(2, 2), ("data", "sp"))
    model = sequence_model.SequenceRegressionModel(
        obs_size=8, action_size=4, hidden_size=32, num_heads=4,
        sequence_length=64, attention_backend="ring", device_type="cpu",
        optimizer_fn=lambda: optax.adam(1e-3))
    model.set_mesh(mesh)
    _compile_step_for_mesh(model, mesh, batch=8)

  @pytest.mark.parametrize("bindings", [
      ("SequenceRegressionModel.attention_backend = 'ring'",),
      ("SequenceRegressionModel.attention_backend = 'ulysses'",
       "SequenceRegressionModel.ulysses_inner = 'flash'"),
  ], ids=["ring", "ulysses-flash"])
  def test_longcontext_sequence_parallel_step_compiles(self, bindings,
                                                       v5e_devices):
    """`chip_smoke.py --multichip` (ii): the long-context widths (hidden
    512, 8 heads, T 4096) over ('data', 'sp', 'model') = (2, 2, 1)."""
    model, batch = _model_from_config(
        "configs/train_longcontext_flash.gin", bindings)
    mesh = Mesh(v5e_devices.reshape(2, 2, 1), ("data", "sp", "model"))
    model.set_mesh(mesh)
    compiled = _compile_step_for_mesh(model, mesh, batch, donate=True)
    text = compiled.as_text()
    assert "collective-permute" in text or "all-to-all" in text

  def test_all_to_all_moe_compiles(self, v5e_devices):
    import optax

    from tensor2robot_tpu.models import moe_model

    mesh = Mesh(v5e_devices.reshape(4, 1, 1), ("data", "fsdp", "model"))
    model = moe_model.MoERegressionModel(
        obs_size=8, action_size=4, num_experts=8, hidden_size=32,
        dispatch="alltoall", capacity_factor=2.0, device_type="cpu",
        optimizer_fn=lambda: optax.adam(1e-3))
    model.set_mesh(mesh)
    _compile_step_for_mesh(model, mesh, batch=16)

  def test_heterogeneous_pp_bcz_compiles(self, v5e_devices):
    import optax

    from tensor2robot_tpu.models import pipelined_model
    from tensor2robot_tpu.research.bcz import models as bcz_models

    mesh = Mesh(v5e_devices.reshape(1, 4, 1), ("data", "pp", "model"))
    model = bcz_models.BCZModel(
        image_size=16, network="pipelined_berkeley", num_waypoints=2,
        pipeline_filters=(8,) * 4, pipeline_kernel_sizes=(3,) * 4,
        pipeline_strides=(2, 1, 1, 1), pipeline_microbatches=2,
        condition_mode="language", condition_size=4, device_type="cpu",
        optimizer_fn=lambda: optax.adam(1e-3))
    model.set_mesh(mesh)
    _compile_step_for_mesh(
        model, mesh, batch=4,
        rules=pipelined_model.pipeline_parallel_rules())

  def test_ulysses_with_flash_inner_compiles(self, v5e_devices):
    """The deepest combination: the Pallas flash kernel INSIDE the
    Ulysses all-to-all shard_map, compiled for a real v5e sp mesh —
    Mosaic kernel + ICI collectives in one program."""
    import optax

    from tensor2robot_tpu.models import sequence_model

    mesh = Mesh(v5e_devices.reshape(2, 2), ("data", "sp"))
    model = sequence_model.SequenceRegressionModel(
        obs_size=8, action_size=4, hidden_size=32, num_heads=4,
        sequence_length=256, attention_backend="ulysses",
        ulysses_inner="flash", device_type="cpu",
        optimizer_fn=lambda: optax.adam(1e-3))
    model.set_mesh(mesh)
    _compile_step_for_mesh(model, mesh, batch=8)

  def test_tuned_grasping44_dp_fsdp_step_compiles(self, v5e_devices):
    """`chip_smoke.py --multichip` (i): the tuned Grasping44 config over
    (data, fsdp, model) = (2, 2, 1), global batch 256, fsdp rules."""
    from tensor2robot_tpu.parallel import train_step as ts

    model, batch = _model_from_config(
        "research/qtopt/configs/train_qtopt_tpu_tuned.gin")
    mesh = Mesh(v5e_devices.reshape(2, 2, 1), ("data", "fsdp", "model"))
    memory = _compile_step_for_mesh(
        model, mesh, batch, rules=ts.fsdp_rules(),
        donate=True).memory_analysis()
    assert memory.temp_size_in_bytes < 16e9


class TestMultisliceDCNHybridCompilesForV5e:
  """parallel.mesh.create_mesh(dcn_data_parallelism=...) builds a
  hybrid mesh whose outer data axis crosses slices over DCN. This
  compiles the flagship train step for an actual 2-slice v5e topology
  (cross-slice dp all-reduce over DCN + in-slice fsdp collectives over
  ICI) at reduced image scale; the full-472 figure is the AOT script's
  `multislice` mode (AOT_ANALYSIS_r05.json)."""

  def test_dcn_dp_x_ici_fsdp_2slice_compiles(self, two_slice_devices):
    from tensor2robot_tpu.parallel import mesh as mesh_lib
    from tensor2robot_tpu.parallel import train_step as ts
    from tensor2robot_tpu.research.qtopt import flagship

    devices = two_slice_devices
    assert len({getattr(d, "slice_index", 0) for d in devices}) == 2
    mesh = mesh_lib.create_mesh(mesh_shape=[2, 4, 1],
                                axis_names=("data", "fsdp", "model"),
                                devices=list(devices),
                                dcn_data_parallelism=2)
    # The outer axis must actually cross slices (DCN), the inner must
    # stay inside one slice (ICI) — otherwise the "hybrid" mesh would
    # quietly put fsdp reduce-scatters on the slow network.
    slice_of = np.vectorize(lambda d: d.slice_index)
    mesh_slices = slice_of(mesh.devices)  # [data=2, fsdp=4, model=1]
    assert (mesh_slices == mesh_slices[:, :1, :]).all(), \
        "fsdp axis crosses slices"
    assert (mesh_slices[0] != mesh_slices[1]).all(), \
        "data axis does not cross slices"
    model = flagship.make_flagship_model("tpu", image_size=256)
    _compile_step_for_mesh(model, mesh, batch=16, rules=ts.fsdp_rules())


class TestAOTCostPins:
  """Compiler-cost regression guard: the flagship b64/b128/b256
  train-step flops and bytes accessed, as the v5e compiler counts them
  for the production-sharded one-chip step, must stay within 10% of the
  values committed in AOT_ANALYSIS_r04.json. Without this, a refactor
  that doubles bytes/step (e.g. re-introducing the round-2 f32
  activation leak, which was exactly a 1.5x bytes regression) passes
  every green test and silently burns chip time. Half a minute of
  compile each.

  On an intentional cost change (new stem, different fusion), re-commit
  the pin in AOT_ANALYSIS_r04.json with the reason in CHANGES.md — the
  failure message prints the compiler's new numbers to make that a
  copy-paste."""

  # 256 is the SHIPPED batch (train_qtopt_tpu_tuned.gin): the chip
  # measured 6.441 TF / 39.63 GB per step at b256 on 2026-07-31 (old
  # setup) — within 0.5% of this pin, so a pin breach is a real program
  # change.
  @pytest.mark.parametrize("batch", [64, 128, 256])
  def test_flagship_cost_within_10pct_of_committed(self, batch,
                                                   v5e_devices):
    from tensor2robot_tpu.research.qtopt import flagship

    with open(os.path.join(_REPO_ROOT, "AOT_ANALYSIS_r04.json")) as f:
      matrix = json.load(f)["flagship_lever_matrix"]
    pinned = {e["config"]: e for e in matrix}[
        f"grasping44_472_bf16_b{batch}"]
    cost = _cost_analysis(_compile_step_for_mesh(
        flagship.make_flagship_model("tpu"),
        Mesh(v5e_devices[:1], ("data",)), batch))
    got = {"flops_per_step_tf": cost["flops"] / 1e12,
           "bytes_per_step_gb": cost["bytes accessed"] / 1e9}
    for key, now in got.items():
      want = pinned[key]
      assert abs(now - want) <= 0.10 * want, (
          f"{key} at batch {batch} drifted >10% from the committed pin: "
          f"pinned={want}, now={now}. If intentional, re-commit "
          f"the pin in AOT_ANALYSIS_r04.json with these numbers: {got}")


class TestTrainLoopCompilesForV5e:
  """The iterations_per_loop scan loop, certified by the real v5e
  compiler under production dp x fsdp shardings (the same discipline as
  every other stack)."""

  def test_flagship_loop_compiles_sharded(self, v5e_devices):
    from tensor2robot_tpu.parallel import train_step as ts
    from tensor2robot_tpu.research.qtopt import flagship

    model = flagship.make_flagship_model("tpu", image_size=128)
    mesh = Mesh(v5e_devices.reshape(2, 2), ("data", "fsdp"))
    # Compile success IS the assertion (XLA may or may not unroll the
    # tiny trip count, so the HLO text carries no stable marker); the
    # cost analysis must price the real program.
    compiled = _compile_loop_for_mesh(model, mesh, batch=8, loop_k=4,
                                      rules=ts.fsdp_rules())
    cost = _cost_analysis(compiled)
    assert cost.get("flops", 0) > 0

  def test_flagship_eval_loop_compiles_sharded(self, v5e_devices):
    """The EVAL loop has its own jit signature (replicated summed
    metrics out, no donation) — certify it separately."""
    from tensor2robot_tpu import specs as specs_lib
    from tensor2robot_tpu.parallel import train_step as ts
    from tensor2robot_tpu.research.qtopt import flagship

    model = flagship.make_flagship_model("tpu", image_size=128)
    mesh = Mesh(v5e_devices.reshape(2, 2), ("data", "fsdp"))
    k = 4
    features = specs_lib.make_random_numpy(
        model.get_feature_specification("train"), batch_size=8, seed=0)
    labels = specs_lib.make_random_numpy(
        model.get_label_specification("train"), batch_size=8, seed=1)
    stack = lambda tree: jax.tree_util.tree_map(
        lambda x: np.stack([x] * k), tree)
    features, labels = stack(features), stack(labels)
    state_shape = jax.eval_shape(
        lambda rng, f: ts.create_train_state(
            model, rng, jax.tree_util.tree_map(lambda x: x[0], f))[0],
        jax.random.PRNGKey(0), features)
    shardings = ts.state_shardings(state_shape, mesh,
                                   rules=ts.fsdp_rules())
    loop_sh = NamedSharding(mesh, ts.loop_batch_spec())
    shapes = lambda tree, sh_tree: jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sh_tree, is_leaf=lambda x: hasattr(x, "shape"))
    loop = ts.make_eval_loop(model, k, mesh=mesh, shardings=shardings)
    loop.lower(shapes(state_shape, shardings),
               _uniform_shapes(features, loop_sh),
               _uniform_shapes(labels, loop_sh)).compile()


class TestSpaceToDepthStemCompilesForV5e:
  """`Grasping44.space_to_depth` is a shipped option no cell runs yet
  (ROADMAP D3): certify that it compiles for v5e with remat off and on
  (reduced image scale for CI time), so its first chip run cannot burn
  chip time on a compile failure."""

  @pytest.mark.parametrize("remat", [False, True])
  def test_s2d_grasping44_train_step_compiles(self, remat, v5e_devices):
    from tensor2robot_tpu.research.qtopt import flagship

    model = flagship.make_flagship_model(
        "tpu", remat=remat, space_to_depth=True, image_size=256)
    mesh = Mesh(v5e_devices[:1], ("data",))
    _compile_step_for_mesh(model, mesh, batch=8)
