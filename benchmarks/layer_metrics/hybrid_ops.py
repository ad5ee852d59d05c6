"""What the readers of the hybrid decoder's cell share: how its device ops
are told apart in a trace, and its sizes. No metric of its own.

`trace_reduce.read_xplane` keeps a device op's HLO instruction text only,
which carries no scope name, so an op is known by what its instruction says:
its name (a Pallas kernel's `name=`, such as the program's grouped products
`grouped_matmul` and `grouped_matmul_t`, or XLA's own `ragged-dot`), its opcode
(`sort`, `while`) or a shape only one part of the model has. All readers
count top-level ops only (a loop's body lies inside the loop's event).
"""

import re

from benchmarks.harness import trace_reduce

RAGGED_DOT = "ragged-dot-none"   # XLA's grouped product; `-metadata` is not it
# The program's own grouped products (`ops/grouped_matmul.py`): `grouped_matmul`
# (forward, and the rows' cotangent) and `grouped_matmul_t` (the weights').
GROUPED_MATMUL = "grouped_matmul"
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "f16": 2, "pred": 1,
          "s8": 1, "u8": 1}
_SHAPE = re.compile(r"\b(bf16|f32|s32|u32|f16|pred|s8|u8)\[([\d,]*)\]")
# Where the operand list closes and the attributes begin: `), name=`.
_ATTRIBUTES = re.compile(r"\), [a-z_]+=")


def sizes_of(run):
  """The run's sizes if they are a hybrid decoder's, else None."""
  sizes = run.get("sizes") or {}
  return sizes if "linear_num_value_heads" in sizes else None


def step_ops(run):
  """(top-level ops of the first device, steps in the trace) or (None, 0)."""
  events = run.get("events")
  planes = trace_reduce.device_planes(events or ())
  steps = len(trace_reduce.step_durations(events))
  if not planes or not steps:
    return None, 0
  ops = trace_reduce.top_level(trace_reduce.select(
      events, plane=planes[0], line=trace_reduce.OPS_LINE))
  return ops, steps


def shapes(text: str):
  """[(element type, dims)] of the instruction's results and operands, the
  results first. Its attributes are not read: a Pallas kernel's
  `operand_layout_constraints={...}` names every operand's shape again."""
  head = _ATTRIBUTES.split(text, maxsplit=1)[0]
  return [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
          for m in _SHAPE.finditer(head)]


def nbytes(shape) -> float:
  kind, dims = shape
  count = 1
  for d in dims:
    count *= d
  return float(count * _BYTES[kind])


def opcode(text: str) -> str:
  return trace_reduce.short_name(text).rpartition(" ")[2]


def own_name(text: str) -> str:
  """`%flash_fwd.3 = ...` -> `flash_fwd.3`: the instruction's own name, which
  a Pallas kernel takes from its `name=` and XLA's grouped product from its
  opcode; its operands' names, further on in the text, are not looked at."""
  return text.partition(" = ")[0].strip().lstrip("%")


def kernel_name(text: str) -> str:
  """`%short_conv_bwd.3 = ...` -> `short_conv_bwd`: the `name=` a Pallas
  kernel was given."""
  return own_name(text).partition(".")[0]


def is_grouped_product(text: str) -> bool:
  return own_name(text).startswith((RAGGED_DOT, GROUPED_MATMUL))


def counter_records(run, name: str):
  """Per stepstats record of the window, the layers' values of a counter the
  program returns with its step metrics (`<name>/layer_<i>`)."""
  out = []
  for _, record in run.get("stepstats", []):
    values = [v for k, v in record.items() if k.startswith(name + "/")]
    if values:
      out.append(values)
  return out


def flash_share(run, kernel: str, flops_fn, bytes_fn):
  """Roofline share of the flash kernel named `kernel` at this cell's shape,
  in %: calls x least time / summed device time."""
  sizes, peaks = sizes_of(run), run.get("peaks")
  ops, _ = step_ops(run)
  if not sizes or not peaks or not ops:
    return None
  calls = [e for e in ops if own_name(e[2]).startswith(kernel)]
  if not calls:
    return None
  bh = run["batch_size"] * sizes["num_attention_heads"]
  t, d = sizes["sequence_length"], sizes["head_dim"]
  least = max(flops_fn(bh, t, d) / peaks["bf16_flops_per_s"],
              bytes_fn(bh, t, d) / peaks["hbm_bytes_per_s"])
  return 100.0 * len(calls) * least / (sum(e[4] for e in calls) / 1e9)
