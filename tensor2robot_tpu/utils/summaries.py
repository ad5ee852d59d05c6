"""Scalar/metric logging.

The reference relies on tf.summary + TPU host_call plumbing
(/root/reference/models/abstract_model.py:873-936); here metrics are
written to a JSONL events file (always) and mirrored to TensorBoard event
files when TensorFlow is importable. JSONL is the source of truth: cheap,
append-only, greppable, no runtime dependency.

Robustness contract (graftscope): a bad value must never kill a train
loop. Non-scalar and non-finite values are skipped — counted in the
metrics registry (`counter/summaries/dropped_non_scalar`,
`counter/summaries/dropped_non_finite`) and warned once per key — and
every written line stays strictly-valid JSON (NaN/Inf never reach the
file, so readers like `bin/graftscope` need no lenient parser). `close()`
fsyncs so a crash right after a run still leaves the records on disk;
the writer is also a context manager.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Dict, Mapping, Optional, Set

import numpy as np

from tensor2robot_tpu.obs import metrics as obs_metrics
from tensor2robot_tpu.obs import trace as trace_lib

__all__ = ["SummaryWriter"]


class SummaryWriter:
  def __init__(self, log_dir: str, use_tensorboard: bool = True):
    os.makedirs(log_dir, exist_ok=True)
    self._path = os.path.join(log_dir, "metrics.jsonl")
    self._file = open(self._path, "a")
    self._warned_keys: Set[str] = set()
    self._tb = None
    if use_tensorboard:
      try:
        import tensorflow as tf  # heavyweight; optional mirror only

        self._tb = tf.summary.create_file_writer(log_dir)
        with self._tb.as_default(), tf.summary.record_if(False):
          # TensorFlow resolves `tf.summary.scalar` lazily: the first call
          # imports ~110 modules and walks every installed distribution's
          # metadata, 0.5-1.4 s on the v5e's host (PERF.md, PR 26). Paid
          # here, with the rest of a run's set-up, and not behind the
          # trainer's first stepstats record, where the device waits for
          # it. Nothing is written.
          tf.summary.scalar("first_use", 0.0, step=0)
      except Exception:  # pragma: no cover - TF missing or broken
        self._tb = None

  @property
  def path(self) -> str:
    return self._path

  def __enter__(self) -> "SummaryWriter":
    return self

  def __exit__(self, exc_type, exc, tb) -> None:
    self.close()

  def _warn_once(self, key: str, reason: str) -> None:
    if key in self._warned_keys:
      return
    self._warned_keys.add(key)
    from absl import logging

    logging.warning("SummaryWriter: skipping %s value for %r "
                    "(further drops of this key counted silently in "
                    "counter/summaries/dropped_%s)", reason, key, reason)

  def _clean(self, scalars: Mapping[str, float]) -> Dict[str, float]:
    """Scalar-finite subset of `scalars`; drops are counted + warned."""
    out: Dict[str, float] = {}
    for key, value in scalars.items():
      try:
        arr = np.asarray(value, dtype=np.float64)
        if arr.size != 1:
          raise ValueError(f"size {arr.size}")
        scalar = float(arr.reshape(()))
      except (TypeError, ValueError):
        obs_metrics.counter("summaries/dropped_non_scalar").inc()
        self._warn_once(key, "non_scalar")
        continue
      if not math.isfinite(scalar):
        obs_metrics.counter("summaries/dropped_non_finite").inc()
        self._warn_once(key, "non_finite")
        continue
      out[key] = scalar
    return out

  def write_scalars(self, step: int, scalars: Mapping[str, float]) -> None:
    tracer = trace_lib.get_tracer()
    with tracer.span("summary/write", cat="summary"):
      with tracer.span("summary/jsonl", cat="summary"):
        record: Dict[str, float] = {"step": int(step), "time": time.time()}
        record.update(self._clean(scalars))
        self._file.write(json.dumps(record) + "\n")
        self._file.flush()
      if self._tb is not None:
        with tracer.span("summary/tensorboard", cat="summary"), \
            self._tb.as_default():
          import tensorflow as tf

          for key, value in record.items():
            if key not in ("step", "time"):
              tf.summary.scalar(key, value, step=int(step))
          self._tb.flush()

  def close(self) -> None:
    if not self._file.closed:
      self._file.flush()
      os.fsync(self._file.fileno())
      self._file.close()
    if self._tb is not None:
      self._tb.close()
