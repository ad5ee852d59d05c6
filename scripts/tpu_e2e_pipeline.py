"""End-to-end input-pipeline bench: TFRecords -> native parse + jpeg
decode -> host preprocess -> DevicePrefetcher -> TPU train step.

VERDICT r2 item 3: the synthetic-batch bench (bench.py) spins the chip
on one resident batch; reference parity means FEEDING the chip
(/root/reference/utils/tfdata.py:629-689 infeed design). This script
measures examples/sec through the full data path and how much of the
host time the background prefetcher hides.

Usage (each phase one short process):

  python scripts/tpu_e2e_pipeline.py gen [num_examples]   # CPU only
  python scripts/tpu_e2e_pipeline.py run [steps]          # needs a TPU
  python scripts/tpu_e2e_pipeline.py cpu [steps]          # pipeline-only
                                        # (no device): host-side ceiling

`gen` writes a QT-Opt wire-format dataset (jpeg-encoded images + grasp
params + labels) under DATA_DIR. `run` fails (`backend.require_tpu`)
where jax finds no TPU.
"""

import os
import sys
import time

sys.path.insert(0, ".")  # run from the repo root

from tensor2robot_tpu.utils import backend

# T2R_E2E_FORMAT=jpeg (default) stores jpeg-encoded images (decode on
# the host, smallest records); =raw stores pre-extracted uint8 planes
# (`is_extracted` specs — no decode, the reference's pod-scale feed
# option). On a 1-core host the jpeg path is decode-bound; raw shows the
# pipeline's rate without that single-core floor.
FORMAT = os.environ.get("T2R_E2E_FORMAT", "jpeg")
DATA_DIR = os.environ.get("T2R_E2E_DATA_DIR",
                          f"/tmp/t2r_e2e_qtopt_{FORMAT}")
IMAGE_SIZE = 472
BATCH_SIZE = 64
NUM_SHARDS = 4


def _model(device_platform: str):
  from tensor2robot_tpu.research.qtopt import models as qtopt_models

  return qtopt_models.QTOptModel(
      image_size=IMAGE_SIZE, device_type=device_platform,
      network="grasping44", action_size=5,
      grasp_param_names={"world_vector": (0, 3),
                         "vertical_rotation": (3, 2)},
      use_bfloat16=device_platform != "cpu", use_ema=True)


def _wire_specs(model):
  """The generator/writer wire specs for the chosen FORMAT."""
  from tensor2robot_tpu import modes, specs as specs_lib

  features = specs_lib.flatten_spec_structure(
      model.preprocessor.get_in_feature_specification(modes.TRAIN))
  labels = specs_lib.flatten_spec_structure(
      model.preprocessor.get_in_label_specification(modes.TRAIN))
  if FORMAT == "raw":
    out = specs_lib.SpecStruct()
    for key, spec in features.items():
      out[key] = (spec.replace(is_extracted=True)
                  if spec.is_image else spec)
    features = out
  return features, labels


def gen(num_examples: int = 512) -> None:
  """Writes `num_examples` wire-format records (no TPU, no jax devices)."""
  import numpy as np

  from tensor2robot_tpu import specs as specs_lib
  from tensor2robot_tpu.data import codec, tfrecord

  model = _model("cpu")
  in_features, in_labels = _wire_specs(model)
  # _wire_specs returns flat SpecStructs; merge once outside the loop.
  all_specs = specs_lib.SpecStruct(
      {**dict(in_features.items()), **dict(in_labels.items())})
  os.makedirs(DATA_DIR, exist_ok=True)
  rng = np.random.RandomState(0)
  per_shard = -(-num_examples // NUM_SHARDS)
  written = 0
  for shard in range(NUM_SHARDS):
    path = os.path.join(DATA_DIR, f"train-{shard:05d}-of-{NUM_SHARDS:05d}")
    with tfrecord.RecordWriter(path) as writer:
      for _ in range(min(per_shard, num_examples - written)):
        seed = int(rng.randint(0, 2**31 - 1))
        features = specs_lib.make_random_numpy(in_features, batch_size=None,
                                               seed=seed)
        labels = specs_lib.make_random_numpy(in_labels, batch_size=None,
                                             seed=seed + 1)
        values = {**dict(specs_lib.flatten_spec_structure(features).items()),
                  **dict(specs_lib.flatten_spec_structure(labels).items())}
        # codec routes is_extracted specs to raw bytes automatically.
        record = codec.encode_example(values, all_specs)
        writer.write(record)
        written += 1
  print(f"gen: wrote {written} examples ({IMAGE_SIZE}x{IMAGE_SIZE} "
        f"{FORMAT}) to {DATA_DIR}/train-*")


def _pipeline_iter(model, batch_size: int, overlap: bool = False):
  from tensor2robot_tpu import modes
  from tensor2robot_tpu.data import input_generators

  import jax

  # overlap=False by default: this script's 'cpu pipeline' ceiling and
  # 'e2e serial' phases price the SERIAL host chain on the consumer
  # thread — the auto-on overlap plane (data/overlap.py) would hide
  # exactly the work they exist to measure. The prefetched phase turns
  # it on explicitly, measuring the full PR-8 overlapped stack.
  generator = input_generators.DefaultRecordInputGenerator(
      file_patterns=os.path.join(DATA_DIR, "train-*"),
      batch_size=batch_size, shuffle_buffer_size=128, seed=0,
      overlap=overlap, prefetch_size=2 if overlap else 0)
  features, labels = _wire_specs(model)
  generator.set_specification(features, labels)
  generator.set_preprocess_fn(model.preprocessor.preprocess)
  # Per-host file sharding, as train_eval.py wires it: a no-op on this
  # single-host window, load-bearing the day this runs on a pod.
  generator.set_process_info(jax.process_index(), jax.process_count())
  return generator.create_dataset(modes.TRAIN)


def cpu(steps: int = 20) -> None:
  """Host-side pipeline ceiling: parse+decode+preprocess only, no device.
  This is the rate the host can FEED; compare against the device step
  rate to predict whether infeed can hide."""
  backend.pin_cpu()
  model = _model("cpu")
  dataset = _pipeline_iter(model, BATCH_SIZE)
  next(dataset)  # warm the pipeline (file open, first parse)
  start = time.perf_counter()
  for _ in range(steps):
    next(dataset)
  dt = time.perf_counter() - start
  print(f"cpu pipeline: {steps * BATCH_SIZE / dt:.1f} examples/sec host "
        f"parse+decode+preprocess ({dt / steps * 1e3:.1f} ms/batch of "
        f"{BATCH_SIZE})")


def run(steps: int = 30) -> None:
  """Full e2e on the device: pipeline -> DevicePrefetcher -> train step.

  Prints three rates: synthetic (resident batch, bench.py-style),
  e2e WITHOUT prefetch (serial host->device->step), and e2e WITH the
  background prefetcher — the delta between the last two is what the
  infeed thread hides."""
  backend.require_tpu()
  import jax

  from tensor2robot_tpu import modes, specs as specs_lib
  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.parallel import train_step as ts

  device = jax.devices()[0]
  model = _model(device.platform)
  mesh = mesh_lib.create_mesh(mesh_shape=(1, 1, 1))

  features = specs_lib.make_random_numpy(
      model.preprocessor.get_out_feature_specification(modes.TRAIN),
      batch_size=BATCH_SIZE, seed=0)
  labels = specs_lib.make_random_numpy(
      model.preprocessor.get_out_label_specification(modes.TRAIN),
      batch_size=BATCH_SIZE, seed=1)
  state, shardings = ts.create_train_state(
      model, jax.random.PRNGKey(0), features, mesh=mesh)
  step = ts.make_train_step(model, mesh=mesh, shardings=shardings,
                            donate=False)
  barrier = lambda s: backend.sync(
      min(jax.tree_util.tree_leaves(s.params), key=lambda a: a.size))

  # 1. Synthetic resident batch (compile + reference rate).
  f = mesh_lib.put_host_batch(mesh, features)
  l = mesh_lib.put_host_batch(mesh, labels)
  state, _ = step(state, f, l)  # compile
  barrier(state)
  start = time.perf_counter()
  for _ in range(steps):
    state, _ = step(state, f, l)
  barrier(state)
  synthetic = steps * BATCH_SIZE / (time.perf_counter() - start)
  print(f"synthetic resident batch: {synthetic:.1f} examples/sec")

  # 2. e2e serial: next(dataset) -> place -> step, no overlap.
  dataset = _pipeline_iter(model, BATCH_SIZE)
  batch = next(dataset)  # warm file/parse path
  start = time.perf_counter()
  for _ in range(steps):
    batch = next(dataset)
    f, l = mesh_lib.place_batch(mesh, batch)
    state, _ = step(state, f, l)
  barrier(state)
  serial = steps * BATCH_SIZE / (time.perf_counter() - start)
  if hasattr(dataset, "close"):
    dataset.close()
  print(f"e2e serial (no prefetch): {serial:.1f} examples/sec")

  # 3. e2e with the pipelined loader + DevicePrefetcher hiding host time.
  dataset = _pipeline_iter(model, BATCH_SIZE, overlap=True)
  prefetcher = mesh_lib.DevicePrefetcher(dataset, mesh, depth=2,
                                         max_batches=steps + 1,
                                         close_source=True)
  f, l = next(prefetcher)  # warm
  start = time.perf_counter()
  count = 0
  for f, l in prefetcher:
    state, _ = step(state, f, l)
    count += 1
    if count >= steps:
      break
  barrier(state)
  overlapped = count * BATCH_SIZE / (time.perf_counter() - start)
  prefetcher.close()
  print(f"e2e prefetched: {overlapped:.1f} examples/sec "
        f"(hides {overlapped / max(serial, 1e-9):.2f}x of serial; "
        f"{overlapped / max(synthetic, 1e-9) * 100:.0f}% of synthetic)")


def main():
  phase = sys.argv[1] if len(sys.argv) > 1 else "run"
  arg = int(sys.argv[2]) if len(sys.argv) > 2 else None
  if phase == "gen":
    backend.pin_cpu()  # record writing never needs (or takes) the chip
    gen(arg or 512)
  elif phase == "cpu":
    backend.pin_cpu()
    cpu(arg or 20)
  elif phase == "run":
    run(arg or 30)
  else:
    raise SystemExit(f"unknown phase {phase!r}")


if __name__ == "__main__":
  main()
