"""What the sequence cell's two flash roofline metrics share: how the trace
shows the kernels, and the arithmetic of a roofline share. No metric of its
own."""

from benchmarks.harness import trace_reduce
from benchmarks.layer_metrics import hybrid_ops

# The v5e's trace names a device op by its whole HLO instruction, and a
# `pallas_call`'s `name=` is the instruction's own name (`%flash_bwd.5 =
# ...`): `flash_bwd_roofline` finds the backward by it. A trace recorded
# before the kernels had names (`traces/seq_train_T2048_two_steps`) names
# them after their flax module (`%attn_1.5 = ...`); there what tells the
# forward from the backward is what it returns, (o bf16, log-sum-exp f32),
# and `flash_fwd_roofline` finds it that way, so that it reads in both.
FORWARD_OUTPUTS = ["bf16", "f32"]
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def _first_device_ops(events, **where):
  planes = trace_reduce.device_planes(events)
  if not planes:
    return []
  return trace_reduce.select(events, plane=planes[0],
                             line=trace_reduce.OPS_LINE, **where)


def forward_events(events):
  """The flash forward's calls: the Pallas kernels that return
  `FORWARD_OUTPUTS`."""
  return [e for e in _first_device_ops(events, name_has=PALLAS_TARGET)
          if trace_reduce.output_shapes(e[2]) == FORWARD_OUTPUTS]


def named_events(events, name: str):
  """The calls of the kernel whose `name=` is `name`."""
  return [e for e in _first_device_ops(events)
          if hybrid_ops.kernel_name(e[2]) == name]


def roofline_share(run, find, flops_fn, bytes_fn):
  """calls x least time of one call / their summed device time, in %, with
  `find(events)` the kernel's calls; None where it finds none."""
  events, peaks, sizes = run.get("events"), run.get("peaks"), run.get("sizes")
  if not events or not peaks or not sizes or "num_heads" not in sizes:
    return None
  calls = find(events)
  if not calls:
    return None  # the kernel is not on the path: nothing to read
  seconds = sum(e[4] for e in calls) / 1e9
  bh = run["batch_size"] * sizes["num_heads"]
  t = sizes["sequence_length"]
  d = sizes["hidden_size"] // sizes["num_heads"]
  least = max(flops_fn(bh, t, d) / peaks["bf16_flops_per_s"],
              bytes_fn(bh, t, d) / peaks["hbm_bytes_per_s"])
  return 100.0 * len(calls) * least / seconds
