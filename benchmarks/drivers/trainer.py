"""Driver of the training cells: `train_eval.train_eval_model`, called as
`bin/run_t2r_trainer.py` calls it, under the configuration's gin files and the
cell's bindings, fed by the benchmark's pool generator and clocked by a hook of
the benchmark's own. The program receives only batches.

One call builds one object, the trainer's compiled step with its state, drives
it from the seed through `warmup_steps` steps (the first three are kept for
the comparison with the plain reference) and hands that same object to the
window:

    process start ... window opens        -> setup_s
      imports, pool, train_eval_model up to and through the warm-up steps
    window: opens on a barrier on the trainer's state after the warm-up
      steps, closes on a barrier after the last step the stream fed; the stream
      ends itself once `--seconds` have passed. Nothing the benchmark adds
      blocks in between (a traced run adds two barriers around its trace).
    after the window: the peak memory is read, the trainer's state is let go,
      and only then the reference follows the first three steps.
"""

from __future__ import annotations

import collections
import gc
import os
import shutil
import sys
import tempfile
import time

from benchmarks.harness import compare
from benchmarks.harness import traffic as traffic_lib

CHECK_STEPS = 3


def _host_rss_gb() -> float:
  try:
    with open("/proc/self/status") as f:
      for line in f:
        if line.startswith("VmRSS:"):
          return int(line.split()[1]) / 1e6
  except OSError:
    pass
  return float("nan")


def log(*parts) -> None:
  """Progress on standard error, with the host's resident memory: a run that
  the machine ends says where it was."""
  print(f"[bench {time.perf_counter():.1f}s rss {_host_rss_gb():.1f}GB]",
        *parts, file=sys.stderr, flush=True)


_COMPILED_AT = []  # host seconds of every backend compile of this process


def _compile_times() -> list:
  import jax

  if not _COMPILED_AT:
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _seconds, **_: _COMPILED_AT.append(time.perf_counter())
        if event.endswith("backend_compile_duration") else None)
    _COMPILED_AT.append(float("-inf"))  # marks the listener as installed
  return _COMPILED_AT


def _first_gradient(opt_state, how: dict):
  """The first gradient as the optimizer got it, from its state after one
  step: a momentum accumulator holds it whole (`trace`), Adam's first moment
  holds (1 - b1) of it (`mu`, `scale` 10)."""
  import jax

  field = how["from"]
  def holds(x):
    return field in getattr(x, "_fields", ())

  holders = [x for x in jax.tree_util.tree_leaves(opt_state, is_leaf=holds)
             if holds(x)]
  if len(holders) != 1:
    raise RuntimeError(f"{len(holders)} optimizer states hold {field!r}")
  scale = float(how.get("scale", 1.0))
  return jax.tree_util.tree_map(lambda x: x * scale,
                                jax.device_get(getattr(holders[0], field)))


def _make_hook_builder(clock, trace_plan, model_dir, first_gradient):
  import jax

  from tensor2robot_tpu.hooks import core as hooks_lib

  class BenchHook(hooks_lib.Hook):
    """Clock, the three checked steps, and the trace's start and stop."""

    def __init__(self):
      self.ctx = None
      self.called_at = None
      self.first_step_s = None
      self.program = {"losses": []}
      self.window_open = None
      self.live_bytes_at_open = None
      self.last_step = 0
      self.stepstats = []
      self.unfinished = collections.deque()  # (step, its loss on the device)
      self.trace_dir = None
      self.trace_started = None   # (step, host seconds)
      self.trace_stopped = None

    def begin(self, ctx):
      self.ctx = ctx
      self.program["params0"] = jax.device_get(ctx.get_state().params)
      log("trainer began; state is up")
      if ctx.step_stats is not None:
        ctx.step_stats.add_observer(
            lambda step, record: self.stepstats.append((step, dict(record))))

    def _barrier(self):
      jax.block_until_ready(self.ctx.get_state())
      return time.perf_counter()

    def after_step(self, ctx, step, metrics):
      with jax.profiler.TraceAnnotation("bench/after_step"):
        self._after_step(step, metrics)

    def _after_step(self, step, metrics):
      self.last_step = step
      self.unfinished.append((step, metrics["loss"]))
      while self.unfinished and self.unfinished[0][1].is_ready():
        clock.note_finished(self.unfinished.popleft()[0], time.perf_counter())
      if step <= CHECK_STEPS:
        self.program["losses"].append(float(metrics["loss"]))
        if step == 1:
          self.first_step_s = self._barrier() - self.called_at
          log(f"step 1 ended {self.first_step_s:.1f} s after the call")
          self.program["first_gradient"] = _first_gradient(
              self.ctx.get_state().opt_state, first_gradient)
          self.program["first_batch_stats"] = jax.device_get(
              self.ctx.get_state().mutable_state).get("batch_stats", {})
        if step == CHECK_STEPS:
          self.program["params"] = jax.device_get(self.ctx.get_state().params)
      if step == clock.warmup_steps:
        now = self._barrier()
        stats = jax.local_devices()[0].memory_stats() or {}
        self.live_bytes_at_open = int(stats.get("bytes_in_use", 0))
        self.window_open = now
        clock.open(now)
        log(f"window opened after step {step}")
      if trace_plan is None:
        return
      if self.trace_started is None and step == trace_plan["start_step"]:
        self.trace_dir = os.path.join(model_dir, "bench_trace")
        profiler_options = jax.profiler.ProfileOptions()
        profiler_options.python_tracer_level = 0
        profiler_options.host_tracer_level = int(
            trace_plan.get("host_tracer_level", 2))
        self._barrier()
        jax.profiler.start_trace(self.trace_dir,
                                 profiler_options=profiler_options)
        self.trace_started = (step, time.perf_counter())
        log(f"trace started after step {step}")
      elif (self.trace_started is not None and self.trace_stopped is None
            and time.perf_counter() - self.trace_started[1]
            >= trace_plan["seconds"]):
        self.stop_trace(step)

    def stop_trace(self, step):
      if self.trace_started is not None and self.trace_stopped is None:
        self._barrier()
        log(f"trace stopping after step {step}")
        jax.profiler.stop_trace()
        self.trace_stopped = (step, time.perf_counter())
        log("trace stopped")

  class Builder(hooks_lib.HookBuilder):

    def __init__(self):
      self.hook = BenchHook()

    def create_hooks(self, model, model_dir):
      return [self.hook]

  return Builder()


def _bindings(cell, options, model_dir: str):
  rehearse = options["rehearse"]
  tiny = cell.traffic.get("tiny", {}) if rehearse else {}
  out = list(cell.config.get("bindings", []))
  out += list(cell.traffic.get("bindings", []))
  out += list(tiny.get("bindings", []))
  if options["trace"]:
    out += list(cell.traffic.get("trace", {}).get("bindings", []))
  out += [
      f"train_eval_model.model_dir = '{model_dir}'",
      "train_eval_model.mode = 'train'",
      "train_eval_model.max_train_steps = 1000000000",
      f"train_eval_model.seed = {int(options['seed'])}",
  ]
  return out


def reference_sizes(cell, rehearse: bool) -> dict:
  values = dict(cell.config["model"])
  values.update(cell.traffic.get("model", {}))
  if rehearse:
    values.update(cell.traffic.get("tiny", {}).get("model", {}))
  return cell.reference().sizes_from_bindings(values)


def run(cell, options) -> dict:
  """Runs the cell once; returns the run's record (see run.py)."""
  import jax

  from tensor2robot_tpu import train_eval
  from tensor2robot_tpu.obs import xray as xray_lib
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.utils import config

  rehearse = options["rehearse"]
  root = options["root"]
  tiny = cell.traffic.get("tiny", {}) if rehearse else {}
  batch_size = int(tiny.get("batch_size", cell.traffic["batch_size"]))
  clock = traffic_lib.WindowClock(
      options["seconds"], cell.traffic["warmup_steps"],
      window_steps=tiny.get("window_steps") if rehearse else None)
  if clock.warmup_steps <= CHECK_STEPS:
    raise ValueError("warmup_steps must exceed the three checked steps")
  trace_plan = None
  if options["trace"]:
    trace_plan = dict(cell.traffic["trace"])
    trace_plan.pop("bindings", None)
    if rehearse:
      trace_plan = {"start_step": clock.warmup_steps + 1, "seconds": 0.0}
  model_dir = tempfile.mkdtemp(prefix=f"bench_{cell.name}_")
  generator = traffic_lib.make_pool_generator(
      cell.traffic, options["seed"], clock, batch_size)
  builder = _make_hook_builder(clock, trace_plan, model_dir,
                               cell.config["first_gradient"])
  hook = builder.hook

  config.clear_config()
  config.parse_config_files_and_bindings(
      [os.path.join(root, f) for f in cell.config["gin_files"]],
      _bindings(cell, options, model_dir))
  kwargs = {}
  if len(jax.devices()) != cell.chips:
    # Only a rehearsal gets here (run.py refuses a wrong device count): the
    # CPU stand-in has eight virtual devices, the cell is cut for `chips`.
    kwargs["mesh"] = mesh_lib.create_mesh(devices=jax.devices()[:cell.chips])
  record = {"batch_size": batch_size}
  compiled_at = _compile_times()
  try:
    hook.called_at = time.perf_counter()
    try:
      train_eval.train_eval_model(
          hook_builders=[builder], input_generator_train=generator, **kwargs)
      raise RuntimeError("the trainer returned before its stream ended")
    except StopIteration:
      pass  # the documented exit of a finite stream
    closed_at = hook._barrier()
    log(f"window closed after step {hook.last_step}")
    hook.stop_trace(hook.last_step)
    if hook.window_open is None:
      raise RuntimeError("the stream ended before the window opened")
    steps = hook.last_step - clock.warmup_steps
    record.update({
        "steps": steps,
        "window_s": closed_at - hook.window_open,
        "setup_s": hook.window_open - options["process_start"],
        "first_step_s": hook.first_step_s,
        "stepstats": [(s, r) for s, r in hook.stepstats
                      if s > clock.warmup_steps],
        # Nothing may compile inside the window; a later PR that lets
        # something does not pass unseen.
        "compiles_in_window": sum(
            hook.window_open <= t <= closed_at for t in compiled_at),
    })
    log("compiles:", len(compiled_at) - 1, "of them inside the window:",
        record["compiles_in_window"])
    device = jax.local_devices()[0]
    stats = device.memory_stats() or {}
    temp = max([float(r.get("temp_bytes") or 0.0)
                for r in xray_lib.records()
                if r.get("name") == "train_step"] or [0.0])
    record["memory"] = {
        "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
        "live_bytes_at_open": int(hook.live_bytes_at_open or 0),
        "step_temp_bytes": int(temp),
    }
    log("memory", record["memory"])
    if hook.trace_dir is not None:
      from benchmarks.harness import trace_reduce
      record["events"] = trace_reduce.read_xplane(hook.trace_dir)
      log(len(record["events"]), "trace events read")
    program = hook.program
    raw_batches = generator.raw_pool[:CHECK_STEPS]
    if len(generator.raw_pool) < CHECK_STEPS:
      raise ValueError("the pool holds fewer batches than the checked steps")
    # Let go of everything the trainer held on the device before the
    # reference takes its place.
    hook.ctx = None
    hook.unfinished.clear()
    generator.raw_pool = None
    del generator, builder
    config.clear_config()
    gc.collect()
    log("bytes in use before the reference:",
        (device.memory_stats() or {}).get("bytes_in_use"))
  finally:
    shutil.rmtree(model_dir, ignore_errors=True)

  started = time.perf_counter()
  sizes = reference_sizes(cell, rehearse)
  reference = cell.reference().train_steps(int(options["seed"]), sizes,
                                           raw_batches)
  numbers = compare.training_numbers(program, reference)
  log("reference followed the first steps")
  record["reference_s"] = time.perf_counter() - started
  record["numbers"] = numbers
  record["correct"], record["checks"] = compare.decide(numbers, cell.limits)
  record["attempted"] = steps
  record["failed"] = 0  # a step that fails ends the run with no result
  record["model_flops_per_step"] = cell.reference().model_flops(
      sizes, batch_size)
  record["sizes"] = sizes
  if options.get("keep_batches"):
    record["raw_batches"] = raw_batches  # calibrate.py's stand-ins reuse them
  return record
