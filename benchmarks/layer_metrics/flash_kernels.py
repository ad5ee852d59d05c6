"""What the two flash roofline metrics share: how the trace shows the three
kernels, and the arithmetic of a roofline share. No metric of its own."""

from benchmarks.harness import trace_reduce

# The three `pallas_call`s of `ops/attention.py` carry no `name=`. The v5e's
# trace names a device op by its whole HLO instruction, and a Pallas kernel
# is a `custom-call` with `custom_call_target="tpu_custom_call"` named after
# the flax module it sits in (`%attn_1.5 = ...`), not after its kernel
# function. What tells the three apart is what they return (PERF.md,
# Findings, PR 25, has the lines as read by hand):
#   forward  (o bf16, log-sum-exp f32)      -> ["bf16", "f32"]
#   dq       dq bf16                        -> ["bf16"]
#   dk, dv   (dk bf16, dv bf16)             -> ["bf16", "bf16"]
KERNEL_OUTPUTS = {
    "fwd": ["bf16", "f32"],
    "dq": ["bf16"],
    "dkv": ["bf16", "bf16"],
}
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def kernel_events(events, kernel: str):
  planes = trace_reduce.device_planes(events)
  if not planes:
    return []
  calls = trace_reduce.select(events, plane=planes[0],
                              line=trace_reduce.OPS_LINE,
                              name_has=PALLAS_TARGET)
  return [e for e in calls
          if trace_reduce.output_shapes(e[2]) == KERNEL_OUTPUTS[kernel]]


def roofline_share(run, kernels, flops_fn, bytes_fn, calls_of=None):
  events, peaks, sizes = run.get("events"), run.get("peaks"), run.get("sizes")
  if not events or not peaks or not sizes or "num_heads" not in sizes:
    return None
  chosen = {k: kernel_events(events, k) for k in kernels}
  if not all(chosen.values()):
    return None  # the kernel is not on the path: nothing to read
  seconds = sum(e[4] for es in chosen.values() for e in es) / 1e9
  calls = len(chosen[calls_of or kernels[0]])
  bh = run["batch_size"] * sizes["num_heads"]
  t = sizes["sequence_length"]
  d = sizes["hidden_size"] // sizes["num_heads"]
  least = max(flops_fn(bh, t, d) / peaks["bf16_flops_per_s"],
              bytes_fn(bh, t, d) / peaks["hbm_bytes_per_s"])
  return 100.0 * calls * least / seconds
