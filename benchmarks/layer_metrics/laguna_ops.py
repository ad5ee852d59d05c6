"""What the readers of the Laguna-XS.2 share's cell share: how its sizes are
known, and the roofline share of a windowed flash kernel. No metric of its
own. Ops are told apart as `hybrid_ops.py` says: a windowed kernel by its
`name=`, `flash_window_fwd` or `flash_window_bwd`, which the causal kernels
(`flash_fwd`, `flash_bwd`) of the same step do not share."""

from benchmarks.layer_metrics import hybrid_ops


def sizes_of(run):
  """The run's sizes if they are this configuration's, else None."""
  sizes = run.get("sizes") or {}
  return sizes if "sliding_window" in sizes else None


def band_keys(t: int, window: int) -> float:
  """Score entries of one head's band: sum over rows i of min(i + 1, W)."""
  w = min(int(window), t)
  return w * (w + 1) / 2.0 + (t - w) * float(w)


def window_share(run, kernel: str, products: int, operands: int,
                 rows: int):
  """Roofline share of the windowed flash kernel named `kernel`, in %: the
  calls' summed least time over their summed device time. A call's least
  time is the larger of its FLOPs over the peak (`products` [rows, d] x
  [d, keys] products over the band's entries a head) and its bytes over the
  bandwidth (`operands` bf16 [T, d] arrays a head read or written once, and
  `rows` float32 rows of T a head). Heads come from the call's own result,
  [B, T, H x D]; tiles visited outside the band are work the count leaves
  out, so they read as a lower share."""
  sizes, peaks = sizes_of(run), run.get("peaks")
  ops, _ = hybrid_ops.step_ops(run)
  if not sizes or not peaks or not ops:
    return None
  calls = [e for e in ops if hybrid_ops.kernel_name(e[2]) == kernel]
  if not calls:
    return None
  d, window = sizes["head_dim"], sizes["sliding_window"]
  least = 0.0
  for call in calls:
    _, (b, t, hd) = hybrid_ops.shapes(call[2])[0]
    bh = b * hd // d
    flops = products * 2.0 * bh * d * band_keys(t, window)
    nbytes = operands * bh * t * d * 2.0 + rows * bh * t * 4.0
    least += max(flops / peaks["bf16_flops_per_s"],
                 nbytes / peaks["hbm_bytes_per_s"])
  return 100.0 * least / (sum(e[4] for e in calls) / 1e9)
