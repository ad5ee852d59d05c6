"""The one general generator of training traffic: a pool of whole host
batches drawn from the seed, cycled until the window closes.

A traffic mix is a data file (`benchmarks/traffic/<traffic>.json`):

    driver         which `drivers/<driver>.py` runs the cell
    batch_size     rows of a batch
    pool_batches   whole batches made at set-up and then cycled
    warmup_steps   trainer steps before the window opens (the first three are
                   the ones the plain reference follows)
    fields         optional, key -> how its values are drawn; without an entry
                   an integer image is uniform over 0..255 and a float is
                   standard normal. `row_ramp` [a, b] makes the rows differ in
                   kind (a label's scale or probability runs from a to b down
                   the batch), so that leaving rows out shows
    bindings       optional gin bindings laid over the configuration's
    model          optional sizes laid over the configuration's `model` block
                   for the plain reference (what `bindings` changes: a
                   sequence length)
    trace          traced runs only: {"start_step", "seconds", "bindings",
                   "host_tracer_level"}: the trace starts after that step,
                   lasts about that long between two barriers of its own, and
                   records host events at that level (2 unless given)
    tiny           sizes of the CPU rehearsal: {"batch_size", "bindings",
                   "model", "window_steps"}

Every seed gives the same shapes and the same amount of work; only the values
differ. The pool is made with numpy in the wire layout of the shipped
generators (uint8 images, float32 vectors) and goes through the model's
preprocessor once, at set-up; the window only hands out references.
"""

from __future__ import annotations

import time

import numpy as np


def _row_ramp(how: dict, rows: int, ndim: int):
  """[rows, 1, ...]: `row_ramp` [a, b] runs linearly from a at the first row
  to b at the last, so that the rows differ in kind and a mean taken over
  part of the batch is not the mean over all of it."""
  low, high = how.get("row_ramp", (1.0, 1.0))
  ramp = np.linspace(low, high, rows, dtype=np.float32)
  return ramp.reshape((rows,) + (1,) * (ndim - 1))


def draw(rng, shape, dtype, how=None):
  how = how or {}
  dist = how.get("dist")
  dtype = np.dtype(dtype)
  if dist is None:
    dist = "uniform_int" if np.issubdtype(dtype, np.integer) else "normal"
  if dist == "uniform_int":
    return rng.integers(how.get("low", 0), how.get("high", 256), size=shape,
                        dtype=dtype)
  ramp = _row_ramp(how, shape[0], len(shape))
  if dist == "normal":  # `row_ramp` scales the rows
    return (rng.standard_normal(shape, dtype=np.float32)
            * how.get("scale", 1.0) * ramp).astype(dtype)
  if dist == "uniform":
    return (rng.uniform(how.get("low", -1.0), how.get("high", 1.0),
                        size=shape) * ramp).astype(dtype)
  if dist == "bernoulli":  # `row_ramp`, where given, is the rows' probability
    p = ramp if "row_ramp" in how else how.get("p", 0.5)
    return (rng.random(shape) < p).astype(dtype)
  raise ValueError(f"unknown distribution {dist!r}")


def make_pool(flat_specs: dict, batch_size: int, pool_batches: int, seed: int,
              fields=None):
  """`pool_batches` flat dicts key -> numpy array [batch_size, ...], drawn
  key by key in sorted order from one generator seeded with `seed`.
  `flat_specs` maps `features/<key>` and `labels/<key>` to (shape, dtype)."""
  rng = np.random.default_rng(int(seed))
  fields = fields or {}
  pool = []
  for _ in range(pool_batches):
    batch = {}
    for key in sorted(flat_specs):
      shape, dtype = flat_specs[key]
      batch[key] = draw(rng, (batch_size,) + tuple(shape), dtype,
                        fields.get(key))
    pool.append(batch)
  return pool


class WindowClock:
  """Shared by the trainer's hook, which opens the window and notes which
  steps the device has finished, and the stream, which ends itself so that
  the window lasts `seconds`.

  The host runs ahead of the device (dispatch is asynchronous; the v5e's
  runtime lets some tens of steps queue), so "the seconds are up" on the
  host's clock would leave the queue's worth of steps still to run. The stream
  instead ends when the time already passed plus the steps handed out and not
  yet finished, at the window's own pace so far, reach `seconds`. Nothing
  blocks: finished steps are noticed by polling `is_ready()`. In a rehearsal
  the stream ends after `window_steps` batches past the warm-up."""

  def __init__(self, seconds: float, warmup_steps: int, window_steps=None):
    self.seconds = float(seconds)
    self.warmup_steps = int(warmup_steps)
    self.window_steps = window_steps
    self.opened_at = None
    self.handed_out = 0
    self.finished_step = 0
    self.finished_at = None

  def open(self, now: float) -> None:
    self.opened_at = now
    self.finished_step = self.warmup_steps
    self.finished_at = now

  def note_finished(self, step: int, now: float) -> None:
    if self.opened_at is not None and step > self.finished_step:
      self.finished_step, self.finished_at = step, now

  def stream_done(self, now=None) -> bool:
    if self.window_steps is not None:
      return self.handed_out >= self.warmup_steps + self.window_steps
    if self.opened_at is None:
      return False
    now = time.perf_counter() if now is None else now
    elapsed = now - self.opened_at
    finished = self.finished_step - self.warmup_steps
    if finished < 2:
      return elapsed >= self.seconds
    pace = (self.finished_at - self.opened_at) / finished
    queued = self.handed_out - self.finished_step
    return elapsed + queued * pace >= self.seconds


def make_pool_generator(traffic: dict, seed: int, clock: WindowClock,
                        batch_size: int):
  """The benchmark's input generator: a subclass of the program's
  `AbstractInputGenerator`, so the trainer injects the model's specs and
  preprocessor into it as into any shipped generator."""
  import jax

  from tensor2robot_tpu import specs as specs_lib
  from tensor2robot_tpu.data import input_generators

  class PoolInputGenerator(input_generators.AbstractInputGenerator):

    def __init__(self):
      super().__init__(batch_size=batch_size)
      self.raw_pool = None

    def create_dataset(self, mode):
      self._assert_specs_initialized()
      flat = {}
      for prefix, spec in (("features", self._feature_spec),
                           ("labels", self._label_spec)):
        for key, tensor_spec in (spec or {}).items():
          flat[f"{prefix}/{key}"] = (tuple(tensor_spec.shape),
                                     tensor_spec.dtype)
      self.raw_pool = make_pool(flat, self._batch_size,
                                int(traffic["pool_batches"]), seed,
                                traffic.get("fields"))
      pool = [self._preprocess(raw, mode) for raw in self.raw_pool]

      def _iterate():
        while not clock.stream_done():
          with jax.profiler.TraceAnnotation("bench/next_batch"):
            batch = pool[clock.handed_out % len(pool)]
            clock.handed_out += 1
          yield batch

      return _iterate()

    def _preprocess(self, raw, mode):
      features, labels = specs_lib.SpecStruct(), specs_lib.SpecStruct()
      for key, value in raw.items():
        prefix, _, name = key.partition("/")
        (features if prefix == "features" else labels)[name] = value
      if self._preprocess_fn is not None:
        features, labels = self._preprocess_fn(features, labels, mode)
      out = specs_lib.SpecStruct()
      out["features"] = features
      if labels is not None and len(labels):
        out["labels"] = labels
      return out

  return PoolInputGenerator()
