"""LOCAL (no-hardware) XLA:TPU AOT compile + roofline analysis.

The image's libtpu supports jax AOT compilation against a described TPU
topology (`jax.experimental.topologies`), so the REAL v5e compiler runs
locally: full Mosaic machine-code compilation of the Pallas kernels and
exact per-step cost analysis (flops / bytes accessed / temp memory) of
the flagship train step — the quantities the round-2/3 rooflines had to
measure on the chip. Wall-clock still needs the chip (the chip tool);
this script closes the compile-risk
and bytes-side analysis loop without it.

Usage (CPU-pinned; needs no chip):
  python scripts/tpu_aot_analysis.py flash        # flash fwd+bwd compile
  python scripts/tpu_aot_analysis.py step 64      # train step @ batch
  python scripts/tpu_aot_analysis.py step 64 remat
  python scripts/tpu_aot_analysis.py sweep        # the lever matrix
  python scripts/tpu_aot_analysis.py multichip    # 4-chip dp + 16-chip
                                                  #   dp x fsdp compiles
  python scripts/tpu_aot_analysis.py multislice   # 2-slice DCN hybrid
  python scripts/tpu_aot_analysis.py families     # per-family rooflines
  python scripts/tpu_aot_analysis.py serving      # CEM policy roofline
  python scripts/tpu_aot_analysis.py seqattn      # flash vs XLA attn duel
"""

import json
import sys
import time

sys.path.insert(0, ".")

from tensor2robot_tpu.utils import backend

backend.pin_cpu()

PEAK_FLOPS = backend.V5E_PEAK_BF16_FLOPS
PEAK_BW = backend.V5E_PEAK_HBM_BW


def _mesh():
  import jax
  from jax.experimental import topologies
  from jax.sharding import Mesh

  topo = topologies.get_topology_desc(platform="tpu",
                                      topology_name="v5e:2x2")
  return Mesh(topo.devices[:1], ("data",))


def _shapes_with_sharding(tree, sharding):
  import jax

  return jax.tree_util.tree_map(
      lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
      tree,
      is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
      or hasattr(x, "shape"))


def _replicated_shapes(mesh, tree):
  from jax.sharding import NamedSharding, PartitionSpec

  return _shapes_with_sharding(tree, NamedSharding(mesh, PartitionSpec()))


def _cost(compiled):
  cost = compiled.cost_analysis()
  cost = cost[0] if isinstance(cost, (list, tuple)) else (cost or {})
  return (float(cost.get("flops", float("nan"))),
          float(cost.get("bytes accessed", float("nan"))))


def _compile_train_step(model, batch_size: int, tag: str,
                        compiler_options=None) -> dict:
  """AOT-compiles one model's train step for v5e; returns the roofline
  record (shared by the flagship sweep and the per-family mode)."""
  import jax

  from tensor2robot_tpu import modes, specs as specs_lib
  from tensor2robot_tpu.parallel import train_step as ts

  mesh = _mesh()
  features = specs_lib.make_random_numpy(
      model.preprocessor.get_out_feature_specification(modes.TRAIN),
      batch_size=batch_size, seed=0)
  labels = specs_lib.make_random_numpy(
      model.preprocessor.get_out_label_specification(modes.TRAIN),
      batch_size=batch_size, seed=1)
  state_shape = jax.eval_shape(
      lambda rng, f: ts.create_train_state(model, rng, f)[0],
      jax.random.PRNGKey(0), features)
  start = time.time()
  compiled = ts.make_train_step(model, donate=False).lower(
      _replicated_shapes(mesh, state_shape),
      _replicated_shapes(mesh, features),
      _replicated_shapes(mesh, labels)).compile(
          compiler_options=compiler_options)
  flops, byts = _cost(compiled)
  mem = compiled.memory_analysis()
  out = {
      "config": tag,
      "compile_secs": round(time.time() - start, 1),
      "flops_per_step_tf": round(flops / 1e12, 3),
      "bytes_per_step_gb": round(byts / 1e9, 3),
      "bytes_per_example_mb": round(byts / batch_size / 1e6, 1),
      "compute_bound_ms": round(flops / PEAK_FLOPS * 1e3, 2),
      "memory_bound_ms": round(byts / PEAK_BW * 1e3, 2),
      "ceiling_examples_per_sec": round(
          batch_size / max(flops / PEAK_FLOPS, byts / PEAK_BW), 0),
      "temp_memory_mb": (round(mem.temp_size_in_bytes / 1e6, 0)
                         if mem is not None
                         and hasattr(mem, "temp_size_in_bytes") else None),
  }
  print(json.dumps(out))
  return out


def step_analysis(batch_size: int, remat: bool) -> dict:
  from tensor2robot_tpu.research.qtopt import flagship

  model = flagship.make_flagship_model("tpu", remat=remat)
  return _compile_train_step(
      model, batch_size,
      f"grasping44_472_bf16_b{batch_size}" + ("_remat" if remat else ""))


def families_analysis() -> None:
  """The BASELINE.md table's TPU column, compiler-computed: AOT-compile
  each driver gin config's train step AT ITS TPU-TARGET SCALE for v5e
  and report the roofline (VERDICT r3 weak #6 — per-family TPU numbers
  without the chip; wall-clock confirmation stays a chip run)."""
  import family_baselines as fb  # sibling script; scripts/ is sys.path[0]

  from tensor2robot_tpu.utils import config

  for name, config_file, _ in fb.FAMILIES:
    try:
      config.clear_config()
      config.parse_config_file(f"{fb.CONFIG_ROOT}/{config_file}")
      model = config.query_parameter("train_eval_model.model")
      batch_size = int(config.query_parameter(
          "DefaultRandomInputGenerator.batch_size"))
      _compile_train_step(model, batch_size, f"family_{name}_v5e")
    except Exception as exc:  # noqa: BLE001 - keep the other families
      print(json.dumps({"config": f"family_{name}_v5e",
                        "error": f"{type(exc).__name__}: {exc}"[:300]}))


def serving_analysis() -> None:
  """Compile the on-device CEM action-selection call (Grasping44 @472,
  64 samples x 3 iterations — the reference serving cost) for v5e and
  report the compiler cost: a roofline bound for window item 7's
  wall-clock actions/sec measurement."""
  import jax
  from jax.sharding import NamedSharding, PartitionSpec

  from tensor2robot_tpu import modes, specs as specs_lib
  from tensor2robot_tpu.parallel import train_step as ts
  from tensor2robot_tpu.policies import device_cem
  from tensor2robot_tpu.research.qtopt import flagship

  mesh = _mesh()
  repl = NamedSharding(mesh, PartitionSpec())
  model = flagship.make_flagship_model("tpu")
  features = specs_lib.make_random_numpy(
      model.preprocessor.get_out_feature_specification(modes.TRAIN),
      batch_size=2, seed=0)
  state_shape = jax.eval_shape(
      lambda rng, f: ts.create_train_state(model, rng, f)[0],
      jax.random.PRNGKey(0), features)
  select = device_cem.make_device_cem_fn(
      model, action_size=flagship.ACTION_SIZE)
  shapes = _shapes_with_sharding(state_shape, repl)
  obs = {"image": jax.ShapeDtypeStruct(
      (flagship.IMAGE_SIZE, flagship.IMAGE_SIZE, 3), "uint8",
      sharding=repl)}
  rng = jax.ShapeDtypeStruct((2,), "uint32", sharding=repl)
  start = time.time()
  compiled = select.lower(shapes, obs, rng).compile()
  flops, byts = _cost(compiled)
  bound_ms = max(flops / PEAK_FLOPS, byts / PEAK_BW) * 1e3
  print(json.dumps({
      "config": "device_cem_grasping44_472_64x3",
      "compile_secs": round(time.time() - start, 1),
      "flops_per_action_gf": round(flops / 1e9, 2),
      "bytes_per_action_mb": round(byts / 1e6, 1),
      "roofline_bound_ms_per_action": round(bound_ms, 2),
      "roofline_actions_per_sec": round(1e3 / max(bound_ms, 1e-9), 0),
  }))


def flash_analysis() -> None:
  import jax
  import jax.numpy as jnp
  from jax.sharding import NamedSharding, PartitionSpec

  from tensor2robot_tpu.ops import attention

  mesh = _mesh()
  repl = NamedSharding(mesh, PartitionSpec())

  def run(name, fn, t):
    s = jax.ShapeDtypeStruct((2, t, 4 * 64), jnp.bfloat16, sharding=repl)
    start = time.time()
    compiled = jax.jit(fn).lower(s, s, s).compile()
    _, byts = _cost(compiled)
    print(json.dumps({
        "config": f"flash_{name}_T{t}",
        "compile_secs": round(time.time() - start, 1),
        "bytes_accessed_mb": round(byts / 1e6, 1),
    }))

  def fwd(q, k, v):
    return attention.flash_attention(q, k, v, 4, causal=True,
                                     interpret=False)

  def bwd(q, k, v):
    return jax.grad(
        lambda a, b, c: fwd(a, b, c).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)

  for t in (1024, 4096, 16384):
    run("fwd", fwd, t)
  for t in (1024, 4096):
    run("fwd_bwd", bwd, t)


def _compile_sharded_step(model, mesh, batch_size: int, tag: str,
                          note: str, rules=None, batch_spec=None) -> None:
  """Compiles the production-sharded train step for `mesh` (state
  shardings from `rules` — replicated when None; batches over 'data'
  unless the model commits a different `batch_spec`, e.g. the sequence
  models' ('data','sp')) and prints the per-chip cost record. The ONE
  scaffolding for every multichip/multislice/SP mode, and the
  full-scale twin of tests/test_mosaic_lowering.py
  `_compile_step_for_mesh`."""
  import jax
  from jax.sharding import NamedSharding, PartitionSpec

  from tensor2robot_tpu import modes, specs as specs_lib
  from tensor2robot_tpu.parallel import train_step as ts

  features = specs_lib.make_random_numpy(
      model.preprocessor.get_out_feature_specification(modes.TRAIN),
      batch_size=batch_size, seed=0)
  labels = specs_lib.make_random_numpy(
      model.preprocessor.get_out_label_specification(modes.TRAIN),
      batch_size=batch_size, seed=1)
  state_shape = jax.eval_shape(
      lambda rng, f: ts.create_train_state(model, rng, f)[0],
      jax.random.PRNGKey(0), features)
  shardings = ts.state_shardings(state_shape, mesh, rules=rules)
  state_sh = jax.tree_util.tree_map(
      lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
      state_shape, shardings, is_leaf=lambda x: hasattr(x, "shape"))
  data_sh = NamedSharding(mesh, batch_spec or PartitionSpec("data"))
  start = time.time()
  compiled = ts.make_train_step(model, mesh=mesh, shardings=shardings,
                                batch_spec=batch_spec,
                                donate=False).lower(
      state_sh, _shapes_with_sharding(features, data_sh),
      _shapes_with_sharding(labels, data_sh)).compile()
  flops, byts = _cost(compiled)
  print(json.dumps({
      "config": tag,
      "compile_secs": round(time.time() - start, 1),
      "flops_per_step_tf": round(flops / 1e12, 3),
      "bytes_per_step_gb": round(byts / 1e9, 3),
      "note": note,
  }))


def multichip_analysis(batch_size: int = 128) -> None:
  """Compile the REAL dp-sharded train step for a 4-chip v5e mesh —
  actual TPU collectives/layouts, not the CPU-virtual-device dryrun —
  then the 16-chip dp4 x fsdp2 scale-out on v5e:4x4 (the mesh carries a
  model axis but the flagship declares no model-axis spec shardings and
  fsdp_rules only shard 'fsdp', so that axis is replication — the
  compiled collectives are dp all-reduce + fsdp
  all-gather/reduce-scatter at 16-chip scale)."""
  import numpy as np
  from jax.experimental import topologies
  from jax.sharding import Mesh

  from tensor2robot_tpu.parallel import train_step as ts
  from tensor2robot_tpu.research.qtopt import flagship

  model = flagship.make_flagship_model("tpu")
  topo = topologies.get_topology_desc(platform="tpu",
                                      topology_name="v5e:2x2")
  mesh = Mesh(np.array(topo.devices).reshape(4, 1, 1),
              ("data", "fsdp", "model"))
  _compile_sharded_step(
      model, mesh, batch_size,
      f"grasping44_472_bf16_b{batch_size}_dp4_v5e_2x2",
      "per-chip cost; REAL TPU collectives compiled (4-chip dp)")

  topo16 = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:4x4")
  mesh16 = Mesh(np.array(topo16.devices).reshape(4, 2, 2),
                ("data", "fsdp", "model"))
  _compile_sharded_step(
      model, mesh16, batch_size,
      f"grasping44_472_bf16_b{batch_size}_dp4xfsdp2_v5e_4x4",
      "per-chip cost; 16-chip dp x fsdp compiled "
      "(model axis replicated: no tp annotations on this net)",
      rules=ts.fsdp_rules())


def multislice_analysis(batch_size: int = 128) -> None:
  """Compile the flagship step for a 2-SLICE v5e hybrid mesh: dp over
  DCN (the outer axis create_hybrid_device_mesh routes across slices),
  fsdp over ICI inside each slice — through the repo's own
  `parallel.mesh.create_mesh(dcn_data_parallelism=...)` path, so the
  claimed DCN hybrid support meets the real compiler (VERDICT r4 item
  8). The compiled program carries cross-slice dp all-reduce over DCN +
  in-slice fsdp all-gather/reduce-scatter over ICI."""
  from jax.experimental import topologies

  from tensor2robot_tpu.parallel import mesh as mesh_lib
  from tensor2robot_tpu.parallel import train_step as ts
  from tensor2robot_tpu.research.qtopt import flagship

  topo = topologies.get_topology_desc(platform="tpu",
                                      topology_name="v5e:2x2",
                                      num_slices=2)
  mesh = mesh_lib.create_mesh(mesh_shape=[2, 4, 1],
                              axis_names=("data", "fsdp", "model"),
                              devices=topo.devices,
                              dcn_data_parallelism=2)
  _compile_sharded_step(
      model=flagship.make_flagship_model("tpu"), mesh=mesh,
      batch_size=batch_size,
      tag=f"grasping44_472_bf16_b{batch_size}_dcn2x_fsdp4_v5e_2slice",
      note="per-chip cost; 2-slice hybrid mesh (dp over DCN, fsdp "
           "over ICI) via parallel.mesh.create_mesh "
           "dcn_data_parallelism=2; 8 chips total",
      rules=ts.fsdp_rules())


def seqattn_analysis() -> None:
  """Compiler-cost duel: the sequence model's FULL train step with
  attention_backend='reference' (plain XLA attention, O(T^2) score
  materialization) vs 'flash' (the Pallas kernel, O(T) memory) at
  long-context shapes on v5e. Decides VERDICT r4 item 4's compile-fact
  half — which backend the long-context configs should ship; the
  wall clock is `benchmarks/`' to read (PERF.md)."""
  import optax

  from tensor2robot_tpu.models import sequence_model

  for t in (1024, 4096, 8192):
    for backend in ("reference", "flash"):
      # At T=8192 XLA:TPU's scoped-memory pass promotes the 16 MB
      # flash-bwd custom-call outputs to VMEM "stack" and overruns the
      # default budget; a 64 MiB scoped budget fixes the compile (set
      # XLA_FLAGS=--xla_tpu_scoped_vmem_limit_kib=65536 for runtime
      # use). The production path for T>=8k is SP (row below).
      opts = ({"xla_tpu_scoped_vmem_limit_kib": "65536"}
              if t >= 8192 and backend == "flash" else None)
      model = sequence_model.SequenceRegressionModel(
          obs_size=16, action_size=7, sequence_length=t,
          hidden_size=512, num_blocks=2, num_heads=8,
          attention_backend=backend, device_type="tpu",
          use_bfloat16=True, optimizer_fn=lambda: optax.adam(1e-3))
      try:
        _compile_train_step(model, 2, f"seq_{backend}_T{t}_h512",
                            compiler_options=opts)
      except Exception as exc:  # noqa: BLE001 - record OOM-class failures
        print(json.dumps({"config": f"seq_{backend}_T{t}_h512",
                          "error": f"{type(exc).__name__}: {exc}"[:200]}))

  # The production long-context path: Ulysses SP over a 4-way 'sp' axis
  # with the flash kernel inside — each device holds T/4, far from any
  # single-chip memory edge, and the all_to_alls are real ICI
  # collectives. Uses the model's own ('data','sp') infeed commitment.
  import numpy as np
  from jax.experimental import topologies
  from jax.sharding import Mesh

  topo = topologies.get_topology_desc(platform="tpu",
                                      topology_name="v5e:2x2")
  mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "sp"))
  for backend, inner, tag, note in [
      ("ulysses", "flash", "seq_ulysses_flash_T8192_h512_sp4",
       "per-chip cost; flash kernel inside the Ulysses all_to_all "
       "shard_map over a real 4-way v5e sp axis"),
      ("ring", "reference", "seq_ring_T8192_h512_sp4",
       "per-chip cost; ppermute K/V ring over a real 4-way v5e sp "
       "axis, online-softmax accumulation per hop"),
  ]:
    model = sequence_model.SequenceRegressionModel(
        obs_size=16, action_size=7, sequence_length=8192,
        hidden_size=512, num_blocks=2, num_heads=8,
        attention_backend=backend, ulysses_inner=inner,
        device_type="tpu", use_bfloat16=True,
        optimizer_fn=lambda: optax.adam(1e-3))
    model.set_mesh(mesh)
    _compile_sharded_step(model, mesh, batch_size=2, tag=tag, note=note,
                          batch_spec=model.batch_partition_spec)


def main():
  mode = sys.argv[1] if len(sys.argv) > 1 else "sweep"
  if mode == "flash":
    flash_analysis()
  elif mode == "step":
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 64
    step_analysis(batch, remat="remat" in sys.argv)
  elif mode == "multichip":
    multichip_analysis(int(sys.argv[2]) if len(sys.argv) > 2 else 128)
  elif mode == "multislice":
    multislice_analysis(int(sys.argv[2]) if len(sys.argv) > 2 else 128)
  elif mode == "seqattn":
    seqattn_analysis()
  elif mode == "families":
    families_analysis()
  elif mode == "serving":
    serving_analysis()
  else:  # sweep: the round-3 lever matrix, fully local
    for batch, remat in [(64, False), (128, False), (256, False),
                         (64, True), (128, True)]:
      step_analysis(batch, remat)


if __name__ == "__main__":
  main()
