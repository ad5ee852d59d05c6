"""TPU step tuning probes for the flagship Grasping44 train step.

Usage (needs a TPU) — each phase is a separate short process on
purpose (one compile each):

  python scripts/tpu_step_tuning.py roofline
  python scripts/tpu_step_tuning.py batch 32
  python scripts/tpu_step_tuning.py batch 128
  python scripts/tpu_step_tuning.py profile

Phases:
  roofline — XLA cost_analysis (FLOPs + bytes accessed) of the compiled
             bf16 train step + measured step time -> compute/memory
             bounds and MXU utilization (PERFORMANCE.md round-2 method).
  batch N  — train-step throughput at batch N (bench.py method: host
             fetch of the smallest param leaf as the barrier).
  profile  — jax.profiler trace over a few steps into profiles/
             (inspect with tensorboard --logdir profiles/).
"""
import sys

sys.path.insert(0, ".")  # run from the repo root

from tensor2robot_tpu.utils import backend


def _setup(batch_size, remat=False):
  import jax

  from tensor2robot_tpu import modes, specs as specs_lib
  from tensor2robot_tpu.parallel import train_step as ts
  from tensor2robot_tpu.research.qtopt import flagship

  device = jax.devices()[0]
  # The shared flagship config (research/qtopt/flagship.py) — the same
  # network bench.py times, so probe numbers compare apples-to-apples.
  model = flagship.make_flagship_model(device.platform, remat=remat)
  features = specs_lib.make_random_numpy(
      model.preprocessor.get_out_feature_specification(modes.TRAIN),
      batch_size=batch_size, seed=0)
  labels = specs_lib.make_random_numpy(
      model.preprocessor.get_out_label_specification(modes.TRAIN),
      batch_size=batch_size, seed=1)
  features = jax.device_put(features, device)
  labels = jax.device_put(labels, device)
  state, _ = ts.create_train_state(model, jax.random.PRNGKey(0), features)
  step = ts.make_train_step(model)
  return jax, state, step, features, labels


def _step_time(jax, state, step, features, labels, iters=20):
  del jax  # kept for call-site signature stability
  h1, h2, state = backend.time_train_steps_halves(
      step, state, features, labels, iters=iters)
  if h1 > 1.2 * h2:
    # The round-5 b128 cliff diagnostic: a slow FIRST half means
    # one-time effects (first-touch allocation/defrag) inside the timed
    # window; the second half is the steady state.
    print(f"  [halves: first {h1 * 1e3:.1f} ms/step, "
          f"second {h2 * 1e3:.1f} ms/step — steady-state is the second]")
  elif h2 > 1.2 * h1:
    # The opposite gap means the device DEGRADED mid-window
    # (thermal, contention); reporting the slower half is conservative.
    print(f"  [halves: first {h1 * 1e3:.1f} ms/step, "
          f"second {h2 * 1e3:.1f} ms/step — slowdown mid-window; "
          f"reporting the slower second half]")
  return h2, state


def roofline(batch_size=64):
  jax, state, step, features, labels = _setup(batch_size)
  compiled = step.lower(state, features, labels).compile()
  cost = compiled.cost_analysis()
  cost = cost[0] if isinstance(cost, (list, tuple)) else cost
  flops = cost.get("flops", float("nan"))
  bytes_accessed = cost.get("bytes accessed", float("nan"))
  # Time the AOT executable itself — calling `step` would jit-compile the
  # same computation a second time.
  sec, _ = _step_time(jax, state, compiled, features, labels)
  # TPU v5e public-spec peaks (shared constants in utils/backend).
  peak_flops = backend.V5E_PEAK_BF16_FLOPS
  peak_bw = backend.V5E_PEAK_HBM_BW
  print(f"batch={batch_size} step={sec * 1e3:.1f} ms  "
        f"flops={flops / 1e12:.3f} TF  bytes={bytes_accessed / 1e9:.2f} GB")
  print(f"compute bound={flops / peak_flops * 1e3:.1f} ms  "
        f"memory bound={bytes_accessed / peak_bw * 1e3:.1f} ms  "
        f"mxu util={flops / sec / peak_flops * 100:.1f}%  "
        f"hbm util={bytes_accessed / sec / peak_bw * 100:.1f}%")


def batch(batch_size):
  jax, state, step, features, labels = _setup(batch_size)
  sec, _ = _step_time(jax, state, step, features, labels)
  print(f"batch={batch_size}: {sec * 1e3:.1f} ms/step = "
        f"{batch_size / sec:.1f} examples/sec "
        f"(vs_baseline {batch_size / sec / 400.0:.3f})")


def remat(batch_size):
  """HBM lever probe: rematerialized forward trades FLOPs (cheap here —
  the step is ~14% MXU) for activation bytes between fwd and bwd (the
  bottleneck per the roofline). Compare against `batch` at equal size."""
  jax, state, step, features, labels = _setup(batch_size, remat=True)
  compiled = step.lower(state, features, labels).compile()
  cost = compiled.cost_analysis()
  cost = cost[0] if isinstance(cost, (list, tuple)) else cost
  sec, _ = _step_time(jax, state, compiled, features, labels)
  print(f"remat batch={batch_size}: {sec * 1e3:.1f} ms/step = "
        f"{batch_size / sec:.1f} examples/sec "
        f"(vs_baseline {batch_size / sec / 400.0:.3f}) "
        f"flops={cost.get('flops', float('nan')) / 1e12:.3f} TF "
        f"bytes={cost.get('bytes accessed', float('nan')) / 1e9:.2f} GB")


def profile(batch_size):
  jax, state, step, features, labels = _setup(batch_size)
  # warm up + compile outside the trace window
  sec, state = _step_time(jax, state, step, features, labels, iters=5)
  with jax.profiler.trace("profiles"):
    for _ in range(5):
      state, _ = step(state, features, labels)
    backend.state_barrier(state)
  print(f"trace written to profiles/ (step ~{sec * 1e3:.1f} ms); view "
        f"with: tensorboard --logdir profiles")


def main():
  backend.require_tpu()
  phase = sys.argv[1] if len(sys.argv) > 1 else "roofline"
  if phase == "roofline":
    roofline(int(sys.argv[2]) if len(sys.argv) > 2 else 64)
  elif phase == "batch":
    batch(int(sys.argv[2]))
  elif phase == "remat":
    remat(int(sys.argv[2]) if len(sys.argv) > 2 else 64)
  elif phase == "profile":
    profile(int(sys.argv[2]) if len(sys.argv) > 2 else 64)
  else:
    raise SystemExit(f"unknown phase {phase!r}")


if __name__ == "__main__":
  main()
