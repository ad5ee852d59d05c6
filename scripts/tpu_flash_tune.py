"""On-chip flash-attention block-size duel at the shipped shape.

Times the raw kernels, forward and forward+backward, across block
combinations on the real chip and prints the winner beside plain XLA
attention at the same shape. It times whole calls on the host's clock: the
layout copies XLA puts around a lone kernel are in its numbers (a third of
them at bh 1024, T 2048; PERF.md section 6, PR 27), so it ranks blocks and
does not price a kernel. `ops/attention._DEFAULT_BLOCKS` is set from the
whole train step, and a kernel's own time is read from a device trace
(`benchmarks/run.py --trace 1`, `breakdown.device_ops`).

Usage (needs a TPU: `chiprun -- python scripts/tpu_flash_tune.py [T]`):
  python scripts/tpu_flash_tune.py [T]        # default 4096
"""
import sys

sys.path.insert(0, ".")  # run from the repo root

from tensor2robot_tpu.utils import backend  # noqa: E402


def timed(fn, *args, iters=30):
  """Shared fetch-cancel micro-op timer (see backend.time_op)."""
  return backend.time_op(fn, *args, iters=iters)


def main():
  backend.require_tpu()
  import jax
  import jax.numpy as jnp
  import numpy as np

  from tensor2robot_tpu.ops.attention import attention, flash_attention

  t = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
  b, h, d = 2, 8, 64  # the shipped train_longcontext_flash.gin shape
  rng = np.random.default_rng(0)
  mk = lambda: jax.device_put(
      rng.standard_normal((b, h, t, d), dtype=np.float32).astype(
          jnp.bfloat16))
  q, k, v = mk(), mk(), mk()

  def fwd_bwd(fn):
    def loss(q, k, v):
      return fn(q, k, v).astype(jnp.float32).sum()
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    return lambda q, k, v: g(q, k, v)[0]

  ref_fwd = jax.jit(lambda q, k, v: attention(q, k, v, causal=True))
  ms = timed(ref_fwd, q, k, v) * 1e3
  print(f"T={t} xla fwd: {ms:.2f} ms", flush=True)
  ms_ref_fb = timed(fwd_bwd(lambda q, k, v: attention(q, k, v, causal=True)),
                    q, k, v) * 1e3
  print(f"T={t} xla fwd+bwd: {ms_ref_fb:.2f} ms", flush=True)

  combos = [(128, 128), (256, 256), (512, 512), (256, 512), (512, 1024),
            (1024, 1024)]
  best = None
  for bq, bk in combos:
    if bq > t or bk > t:
      continue
    try:
      f = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
          q, k, v, causal=True, block_q=bq, block_k=bk, interpret=False))
      ms_f = timed(f, q, k, v) * 1e3
      fb = fwd_bwd(lambda q, k, v, bq=bq, bk=bk: flash_attention(
          q, k, v, causal=True, block_q=bq, block_k=bk, interpret=False))
      ms_fb = timed(fb, q, k, v) * 1e3
      print(f"T={t} flash bq={bq} bk={bk}: fwd={ms_f:.2f} ms "
            f"fwd+bwd={ms_fb:.2f} ms", flush=True)
      if ms_fb <= 0.0:
        # time_op clamps a noise-swamped measurement to 0.0 (below the
        # measurement floor) — unrankable, and dividing by it would
        # crash the summary after the window minutes are already spent.
        print(f"T={t} flash bq={bq} bk={bk}: below measurement floor; "
              "excluded from the duel", flush=True)
        continue
      if best is None or ms_fb < best[0]:
        best = (ms_fb, bq, bk)
    except Exception as e:  # compile failure at a combo is itself data
      print(f"T={t} flash bq={bq} bk={bk}: FAILED {type(e).__name__}: {e}",
            flush=True)
  if best:
    print(f"T={t} WINNER flash bq={best[1]} bk={best[2]}: {best[0]:.2f} ms "
          f"fwd+bwd vs xla {ms_ref_fb:.2f} ms "
          f"({ms_ref_fb / best[0]:.2f}x)", flush=True)


if __name__ == "__main__":
  main()
