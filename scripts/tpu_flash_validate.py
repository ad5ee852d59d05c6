"""Validate + time the Pallas flash-attention kernels on real TPU.

Usage (needs a TPU; fails where jax finds none):

  python scripts/tpu_flash_validate.py correctness
  python scripts/tpu_flash_validate.py time 1024
  python scripts/tpu_flash_validate.py time 4096
  python scripts/tpu_flash_validate.py time 16384

Phases are separate short processes: one compile each, and each phase
bounds its own work.

Checks (non-interpret, Mosaic-compiled):
  correctness: fwd + jax.grad through flash match XLA reference attention
  time T:      wall-clock flash fwd / fwd+bwd vs XLA attention at seq T
All timings use utils/backend.sync (host fetch) as the barrier.
"""
import sys

sys.path.insert(0, ".")  # run from the repo root

from tensor2robot_tpu.utils import backend  # noqa: E402 (before jax use)


def timed(fn, *args, iters=10):
  """Shared fetch-cancel micro-op timer (see backend.time_op)."""
  return backend.time_op(fn, *args, iters=iters)


def _qkv(shape, dtype, seed):
  # Host numpy + device_put: one transfer, no eager device ops.
  import jax
  import numpy as np
  rng = np.random.RandomState(seed)
  return tuple(
      jax.device_put((rng.randn(*shape) * 0.3).astype(dtype))
      for _ in range(3))


def correctness():
  import jax
  import numpy as np
  from tensor2robot_tpu.ops.attention import attention, flash_attention

  b, h, t, d = 2, 4, 384, 64  # non-multiple of 128 exercises the pad path
  q, k, v = _qkv((b, h, t, d), "float32", 0)

  for causal in (False, True):
    f_flash = jax.jit(lambda q, k, v, c=causal: flash_attention(
        q, k, v, causal=c, interpret=False))
    f_ref = jax.jit(lambda q, k, v, c=causal: attention(q, k, v, causal=c))
    o1, o2 = backend.sync(f_flash(q, k, v)), backend.sync(f_ref(q, k, v))
    err = np.max(np.abs(o1 - o2))
    print(f"fwd causal={causal}: max_err={err:.2e}", flush=True)
    assert err < 2e-2, err

    def loss_flash(q, k, v, c=causal):
      return flash_attention(q, k, v, causal=c, interpret=False).sum()

    def loss_ref(q, k, v, c=causal):
      return attention(q, k, v, causal=c).sum()

    g1 = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for name, a, bb in zip("qkv", g1, g2):
      ga, gb = backend.sync(a), backend.sync(bb)
      err = np.max(np.abs(ga - gb)) / (np.max(np.abs(gb)) + 1e-9)
      print(f"grad d{name} causal={causal}: rel_err={err:.2e}", flush=True)
      assert err < 5e-2, err
  print("CORRECTNESS OK (non-interpret, real TPU)")


def time_at(t):
  import jax
  import jax.numpy as jnp
  from tensor2robot_tpu.ops.attention import attention, flash_attention

  b = 2 if t <= 4096 else 1
  h, d = 8, 64
  q, k, v = _qkv((b, h, t, d), jnp.bfloat16, t)

  # Sub-ms kernels need a long loop leg: the fetch-cancel difference is
  # noise-dominated otherwise (negative ms in the round-5 capture).
  iters = 50 if t <= 4096 else 10
  f_flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
  ms_flash = timed(f_flash, q, k, v, iters=iters) * 1e3
  print(f"T={t} B={b}: flash_fwd={ms_flash:.2f} ms", flush=True)

  try:
    def loss(q, k, v):
      return flash_attention(q, k, v,
                             interpret=False).astype(jnp.float32).sum()
    f_grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    ms_flash_bwd = timed(lambda q, k, v: f_grad(q, k, v)[0], q, k, v,
                         iters=iters) * 1e3
    print(f"T={t} B={b}: flash_fwd+bwd={ms_flash_bwd:.2f} ms", flush=True)
  except Exception as e:
    # Round-5 captured fact: the T=16384 bwd dies in the terminal's
    # REMOTE compiler (HTTP 500 from tpu_compile_helper — the
    # scoped-VMEM ceiling the local compiler also needs a flag for).
    # Record and continue: fwd + the XLA comparison are still captures.
    print(f"T={t}: flash bwd failed: {type(e).__name__}: {e}", flush=True)

  try:
    f_ref = jax.jit(lambda q, k, v: attention(q, k, v))
    ms_ref = timed(f_ref, q, k, v) * 1e3
  except Exception as e:  # OOM at long T is expected
    print(f"T={t}: XLA reference failed: {type(e).__name__}", flush=True)
    return
  speedup = (f"(flash speedup {ms_ref / ms_flash:.2f}x)" if ms_flash > 0
             else "(flash below measurement floor)")
  print(f"T={t} B={b}: xla_fwd={ms_ref:.2f} ms {speedup}", flush=True)


def main():
  backend.require_tpu()
  phase = sys.argv[1] if len(sys.argv) > 1 else "correctness"
  if phase == "correctness":
    correctness()
  elif phase == "time":
    time_at(int(sys.argv[2]))
  else:
    raise SystemExit(f"unknown phase {phase!r}")


if __name__ == "__main__":
  main()
