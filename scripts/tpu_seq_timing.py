"""Full sequence-model train-step timing at the SHIPPED long-context
shape (configs/train_longcontext_flash.gin: T=4096, h512, 8 heads, 2
blocks, bf16, batch 2), backend 'reference' vs 'flash' — the wall-clock
confirmation of the compile-fact ship decision in
AOT_ANALYSIS_r05.json `seqattn` (flash ceiling 546 vs 118 ex/s, ~4.6x).

Usage (needs a TPU; one attention backend per process):

  python scripts/tpu_seq_timing.py reference
  python scripts/tpu_seq_timing.py flash
  python scripts/tpu_seq_timing.py flash 8192   # needs the scoped-vmem
                                                # option, applied below
"""
import sys

sys.path.insert(0, ".")

from tensor2robot_tpu.utils import backend


def time_backend(attention_backend: str, seq_len: int) -> None:
  import jax
  import optax

  from tensor2robot_tpu import modes, specs as specs_lib
  from tensor2robot_tpu.models import sequence_model
  from tensor2robot_tpu.parallel import train_step as ts

  device = jax.devices()[0]
  model = sequence_model.SequenceRegressionModel(
      obs_size=16, action_size=7, sequence_length=seq_len,
      hidden_size=512, num_blocks=2, num_heads=8,
      attention_backend=attention_backend, device_type=device.platform,
      use_bfloat16=True, optimizer_fn=lambda: optax.adam(1e-3))
  batch_size = 2
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
  # Compile once (AOT) so the timing loop never re-jits;
  # T>=8192 single-chip flash needs the larger scoped-VMEM budget
  # (AOT_ANALYSIS_r05.json compile_blockers).
  opts = ({"xla_tpu_scoped_vmem_limit_kib": "65536"}
          if seq_len >= 8192 and attention_backend == "flash" else None)
  compiled = step.lower(state, features, labels).compile(
      compiler_options=opts)
  cost = compiled.cost_analysis()
  cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
  sec, _ = backend.time_train_steps(compiled, state, features, labels,
                                    iters=20)
  flops = float(cost.get("flops", float("nan")))
  byts = float(cost.get("bytes accessed", float("nan")))
  print(f"seq {attention_backend} T={seq_len} h512 b{batch_size}: "
        f"{sec * 1e3:.1f} ms/step = {batch_size / sec:.1f} ex/s  "
        f"flops={flops / 1e12:.3f} TF  bytes={byts / 1e9:.2f} GB  "
        f"hbm util={byts / sec / backend.V5E_PEAK_HBM_BW * 100:.0f}%")


def main():
  backend.require_tpu()
  attention_backend = sys.argv[1] if len(sys.argv) > 1 else "flash"
  seq_len = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
  time_backend(attention_backend, seq_len)


if __name__ == "__main__":
  main()
