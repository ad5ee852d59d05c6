"""Pipeline parallelism: GPipe and interleaved-1F1B schedules over a mesh axis.

Beyond the reference (SURVEY.md §2.5: PP absent there). Stage parameters
carry a leading stage dim sharded over the `pp` axis; microbatches flow
through a `lax.scan` of compute+`ppermute` ticks, so activations hop
stage-to-stage over ICI while every stage works on a different
microbatch. Differentiable: the scan/ppermute pair transposes cleanly,
so the same function trains (the backward is the reverse schedule over
the same ring).

Two SCHEDULES share one tick skeleton (`_tick_plan`):

* GPipe fill/drain (`num_virtual_stages == 1`): one stage per rank,
  microbatches stream once around the ring. Bubble fraction
  (S-1)/(M+S-1) — grows with stage count.
* Interleaved 1F1B (`num_virtual_stages == v > 1`): each pp rank holds
  `v` virtual stage CHUNKS (stacked [S*v, ...] params sharded over
  `pp`), and microbatches stream around the ring `v` times in groups of
  S, so while early microbatches are deep in their later chunks the
  ring keeps admitting later microbatches — the interleaved schedule of
  Megatron-LM / "Scaling Deep Learning Training with MPMD Pipeline
  Parallelism" (arXiv:2412.14374). The fill is paid ONCE (S-1 ticks)
  instead of once per loop, cutting bubble fraction to
  (S-1)/(v*ceil(M/S)*S + S - 1) -> (S-1)/(v*M) for S | M, and only S
  microbatches are in flight on the ring at any tick (the O(S) live
  working set; the autodiff transpose replays the same schedule in
  reverse, so its in-flight set mirrors the forward's). `lax.scan`
  still stashes one per-tick residual set for the backward — remat the
  stage fn when that dominates.

`schedule_accounting` prices any (S, M, v) statically — total ticks,
per-rank busy/idle ticks, bubble fraction — and every pipelined apply
registers the result as `pp/*` gauges so the schedule win is observable
in runs.jsonl (the wall-clock side has no cell on the chip yet:
ROADMAP W13).

Two PARAM LAYOUTS feed the same schedules:

* `pipelined_apply` — homogeneous: one shape-preserving stage function,
  stage params stacked with a leading [S*v] dim (transformer/MLP
  blocks).
* `pipelined_apply_heterogeneous` — per-stage DIFFERENT functions,
  param pytrees, and activation shapes (e.g. a conv tower whose spatial
  dims and channel counts change every stage). Each stage's params are
  raveled to a flat vector, zero-padded to the widest stage, and stacked
  into one [S*v, P_max] leaf sharded over `pp`; activations travel as
  zero-padded flat [mb, A_max] buffers so every ppermute hop moves a
  same-shape array. Inside the SPMD program a `lax.switch` on the
  global layer index dispatches each rank to the right stage's
  computation — XLA compiles all S*v branches everywhere (static
  shapes, MXU-friendly: the branch unravels to the TRUE shapes before
  any matmul/conv), each rank executes its own `v` per step.

Interleaved placement: ring traversal must compose layers in depth
order, so loop j's visit to rank r executes layer j*S + r — rank r
holds layers {r, S+r, ..., (v-1)S+r}, NOT a contiguous depth block.
Stacks arrive in natural depth order (`params_layout="layer"`) and are
permuted to the sharded interleaved layout on the fly, or pre-permuted
once via `interleave_stage_stack` (`params_layout="interleaved"`) to
keep the per-step permute gather off the hot path.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.parallel import mesh as mesh_lib

__all__ = ["pipelined_apply", "stack_stage_params",
           "shard_pipeline_tree", "make_pipelined_train_step",
           "ravel_stage_stack", "pipelined_apply_heterogeneous",
           "sequential_apply_heterogeneous", "schedule_accounting",
           "interleave_order", "interleave_stage_stack"]


# ---------------------------------------------------------------------------
# Static schedule accounting (pure Python — backend-free by construction;
# the poisoned-platform trap in tests/test_moe_pipeline.py runs it with no
# usable jax backend).
# ---------------------------------------------------------------------------


def schedule_accounting(num_stages: int, num_micro: int,
                        num_virtual_stages: int = 1) -> Dict[str, Any]:
  """Prices a pipeline schedule from its static structure.

  Tick model: every tick, every rank runs exactly one stage-chunk
  compute and one ppermute hop (the SPMD lockstep `lax.scan` below), so
  wall time is total_ticks * per-tick cost and the bubble fraction is
  the fraction of compute slots that hold no real microbatch work.

  Returns a JSON-safe dict: `schedule`, `total_ticks`,
  `busy_ticks_per_rank`, `idle_ticks_per_rank`, `bubble_fraction`, and
  `padded_microbatches` (interleaved schedules admit microbatches in
  groups of S; a ragged last group pays idle slots, counted here).
  """
  s, m, v = int(num_stages), int(num_micro), int(num_virtual_stages)
  if s < 1 or m < 1 or v < 1:
    raise ValueError(
        f"schedule_accounting needs num_stages >= 1, num_micro >= 1, "
        f"num_virtual_stages >= 1; got ({s}, {m}, {v})")
  if v == 1:
    total = m + s - 1
    padded = 0
  else:
    groups = -(-m // s)
    total = groups * s * v + s - 1
    padded = groups * s - m
  busy = m * v
  return {
      "schedule": "gpipe" if v == 1 else "interleaved-1f1b",
      "num_stages": s,
      "num_micro": m,
      "num_virtual_stages": v,
      "total_ticks": total,
      "busy_ticks_per_rank": busy,
      "idle_ticks_per_rank": total - busy,
      "bubble_fraction": (total - busy) / total,
      "padded_microbatches": padded,
  }


def interleave_order(num_stages: int, num_virtual_stages: int) -> np.ndarray:
  """Permutation mapping sharded-stack position -> depth-order layer.

  Position r*v + j (rank r's j-th local chunk under contiguous `pp`
  sharding of the leading [S*v] dim) holds layer j*S + r, so loop j's
  ring traversal executes layers jS..jS+S-1 in depth order. Identity
  for v == 1.
  """
  s, v = int(num_stages), int(num_virtual_stages)
  return np.array([(k % v) * s + k // v for k in range(s * v)])


def interleave_stage_stack(stacked: Any, num_stages: int,
                           num_virtual_stages: int) -> Any:
  """Permutes depth-ordered stacked stage params (leading [S*v] dim on
  every leaf) into the interleaved sharded layout (see
  `interleave_order`). Do this ONCE before `shard_pipeline_tree` and
  pass `params_layout="interleaved"` to keep the permute gather out of
  the per-step program."""
  perm = interleave_order(num_stages, num_virtual_stages)
  return jax.tree_util.tree_map(lambda leaf: leaf[perm], stacked)


def _registry():
  return metrics_lib.get_registry()


def _validate_and_account(num_stages: int, num_micro: int,
                          num_virtual_stages: int,
                          batch_axis: Optional[str]) -> Dict[str, Any]:
  """Shared host-side validation + `pp/*` telemetry for both apply paths
  (runs at trace time — Python ints only, never tracers)."""
  if num_micro < 1:
    raise ValueError(f"num_micro must be >= 1, got {num_micro}")
  if num_virtual_stages < 1:
    raise ValueError(
        f"num_virtual_stages must be >= 1, got {num_virtual_stages}")
  if batch_axis is not None and not isinstance(batch_axis, str):
    raise TypeError(f"batch_axis must be a mesh-axis name or None, "
                    f"got {batch_axis!r}")
  accounting = schedule_accounting(num_stages, num_micro,
                                   num_virtual_stages)
  reg = _registry()
  if num_micro < num_stages:
    # Silently degenerate before this warning existed: M < S leaves the
    # ring >50% idle under GPipe ((S-1)/(M+S-1) > (S-1)/(2S-2) >= 1/2).
    reg.counter("pp/degenerate_microbatching").inc()
    from absl import logging

    logging.warning(
        "pipeline schedule is bubble-dominated: num_micro=%d < "
        "num_stages=%d gives bubble fraction %.2f — raise the "
        "microbatch count (or num_virtual_stages) to fill the ring",
        num_micro, num_stages, accounting["bubble_fraction"])
  reg.gauge("pp/bubble_fraction").set(accounting["bubble_fraction"])
  reg.gauge("pp/total_ticks").set(float(accounting["total_ticks"]))
  reg.gauge("pp/num_virtual_stages").set(float(num_virtual_stages))
  return accounting


def _tick_plan(num_stages: int, num_micro: int, num_virtual_stages: int):
  """The static tick schedule both apply paths scan over.

  Returns (total_ticks, out_ticks, plan) where `plan(t, idx)` maps the
  scan tick `t` and pp rank `idx` (both traced int32) to
  `(valid, m, chunk)`:

  * `valid` — this (rank, tick) slot holds a real microbatch (idle
    fill/drain/padding slots compute on zeros and are masked off the
    wire so garbage can never reach a valid item, forward or backward);
  * `m` — the microbatch index (clipped into range when invalid);
  * `chunk` — which of the rank's `v` local chunks runs this tick.

  Schedule: work item u = t - idx enumerates rank 0's injection order.
  GPipe (v == 1): u IS the microbatch — one pass around the ring.
  Interleaved (v > 1): microbatches are admitted in groups of S and
  each group streams around the ring v times back-to-back
  (u = g*S*v + j*S + i -> microbatch g*S + i, chunk j). Group stride
  S*v matches the ring latency S exactly, so loop j+1's item arrives
  back at rank 0 on the tick it is scheduled — no buffering, and the
  fill cost (S-1 ticks) is paid once for the whole run.

  `out_ticks[m]` is the tick whose rank-(S-1) output is microbatch m's
  final-layer result.
  """
  s, m_count, v = num_stages, num_micro, num_virtual_stages
  if v == 1:
    span = m_count
    ms = np.arange(m_count)
    out_ticks = ms + s - 1
  else:
    groups = -(-m_count // s)
    span = groups * s * v
    ms = np.arange(m_count)
    out_ticks = (ms // s) * (s * v) + (v - 1) * s + (ms % s) + s - 1
  total_ticks = span + s - 1

  def plan(t, idx):
    u = t - idx
    valid = (u >= 0) & (u < span)
    u = jnp.clip(u, 0, span - 1)
    if v == 1:
      micro_index = u
      chunk = jnp.zeros_like(u)
    else:
      group = u // (s * v)
      within = u % (s * v)
      chunk = within // s
      micro_index = group * s + within % s
      valid = valid & (micro_index < m_count)
    return valid, jnp.clip(micro_index, 0, m_count - 1), chunk

  return total_ticks, out_ticks, plan


def _io_specs(mesh: Mesh, axis_name: str, batch_axis: Optional[str]):
  """(params spec, activation spec) for the shard_map boundary."""
  params_spec = PartitionSpec(axis_name)
  if batch_axis is not None and mesh.shape.get(batch_axis, 1) > 1:
    replicated_spec = PartitionSpec(None, batch_axis)
  else:
    replicated_spec = PartitionSpec()
  return params_spec, replicated_spec


def stack_stage_params(params_list):
  """Stacks per-stage param pytrees into leading-[S] arrays (the layout
  `pp` sharding expects), in natural depth order. For interleaved
  schedules follow with `interleave_stage_stack` (or pass
  `params_layout="layer"` and let the apply permute per step)."""
  return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)


def pipelined_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                    stage_params: Any,
                    microbatches: jnp.ndarray,
                    mesh: Mesh,
                    axis_name: str = "pp",
                    batch_axis: Optional[str] = None,
                    num_virtual_stages: int = 1,
                    params_layout: str = "layer") -> jnp.ndarray:
  """Runs microbatches through a pipeline of homogeneous stages.

  Args:
    stage_fn: (one stage chunk's params, activation [mb, ...]) ->
      activation of the same shape.
    stage_params: pytree with leading [num_stages * num_virtual_stages]
      dim on every leaf; sharded over `axis_name`.
    microbatches: [num_microbatches, mb, ...] global input (replicated
      over the pp axis; when `batch_axis` is given, the mb dim stays
      sharded over it so PP composes with data parallelism instead of
      all-gathering the batch).
    mesh: mesh containing `axis_name`; its size S is the pp rank count.
    batch_axis: optional mesh axis the microbatch (second) dim is sharded
      over.
    num_virtual_stages: chunks per rank (v). 1 = GPipe fill/drain;
      >1 = interleaved 1F1B (see module docstring).
    params_layout: "layer" (leading dim in depth order; permuted to the
      interleaved layout inside the program) or "interleaved" (already
      permuted via `interleave_stage_stack` — no per-step gather).

  Returns:
    [num_microbatches, mb, ...] outputs (replicated over the pp axis,
    mb dim sharded over `batch_axis` when given).
  """
  num_stages = mesh.shape[axis_name]
  num_micro = microbatches.shape[0]
  v = int(num_virtual_stages)
  if params_layout not in ("layer", "interleaved"):
    raise ValueError(f"params_layout must be 'layer' or 'interleaved', "
                     f"got {params_layout!r}")
  _validate_and_account(num_stages, num_micro, v, batch_axis)
  leading = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
  if leading != num_stages * v:
    raise ValueError(
        f"stage_params leading dim {leading} != num_stages {num_stages} "
        f"* num_virtual_stages {v}")
  if v > 1 and params_layout == "layer":
    stage_params = interleave_stage_stack(stage_params, num_stages, v)
  total_ticks, out_ticks, plan = _tick_plan(num_stages, num_micro, v)

  params_spec, replicated_spec = _io_specs(mesh, axis_name, batch_axis)
  perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

  def local_fn(local_params, micro):
    # local_params leaves: [v, ...] (this rank's chunks, loop-major).
    idx = jax.lax.axis_index(axis_name)
    my_chunk0 = jax.tree_util.tree_map(lambda p: p[0], local_params)

    def tick(carry, t):
      valid, m, chunk = plan(t, idx)
      # Injection only on VALID chunk-0 slots at rank 0: drain ticks no
      # longer re-run a clipped re-read of the last microbatch through
      # stage 0 — the idle slot computes on the (masked-to-zero) wire
      # value instead, so no stale microbatch data re-enters the ring
      # and the idle compute is a foldable constant-operand op.
      inject = (idx == 0) & valid & (chunk == 0)
      x = jnp.where(inject, micro[m], carry)
      # v == 1 uses the hoisted static slice; v > 1 pays one dynamic
      # chunk gather per tick (cheaper than a lax.switch over chunks,
      # whose VJP materializes cotangents for every branch).
      my_params = (my_chunk0 if v == 1 else jax.tree_util.tree_map(
          lambda p: p[chunk], local_params))
      y = stage_fn(my_params, x)
      y = jnp.where(valid, y, jnp.zeros_like(y))
      shifted = jax.lax.ppermute(y, axis_name, perm)
      return shifted, y

    zeros = jnp.zeros_like(micro[0])
    _, ys = jax.lax.scan(tick, zeros, jnp.arange(total_ticks))
    # The last rank's outputs at the (static) final-chunk ticks are the
    # results for microbatches [0, M). Broadcast to every pp rank.
    outs = ys[jnp.asarray(out_ticks)]
    outs = jnp.where(idx == num_stages - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs, axis_name)

  return mesh_lib.shard_map(
      local_fn, mesh=mesh,
      in_specs=(params_spec, replicated_spec),
      out_specs=replicated_spec)(stage_params, microbatches)


def make_pipelined_train_step(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    loss_fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "pp",
    batch_axis: Optional[str] = None,
    num_virtual_stages: int = 1,
    params_layout: str = "layer",
    donate: bool = True,
    audit_name: Optional[str] = None,
    cache=None) -> Callable:
  """Builds a jitted *training* step over the pipelined schedule.

  The forward runs microbatches through `pipelined_apply`; the backward
  is the autodiff transpose of the same scan+ppermute schedule (reverse
  activation hops over the ICI ring), and microbatch gradients
  accumulate into one optimizer update, i.e. microbatch gradient
  accumulation is the sum inside the mean loss.

  Args:
    stage_fn: (stage chunk params, activation [mb, ...]) -> same-shape
      activation (homogeneous stages; see module docstring for scope).
    loss_fn: (outputs [M, mb, ...], targets [M, mb, ...]) -> scalar mean
      loss over all microbatches.
    optimizer: optax transformation over the stacked stage params.
    mesh: mesh containing `axis_name`.
    batch_axis / num_virtual_stages / params_layout: schedule and
      PP x DP composition knobs, as in `pipelined_apply`.
    donate: donate (params, opt_state) buffers to the step — the
      pp-sharded state updates in place instead of doubling its HBM
      footprint.
    audit_name: when set, the step is wrapped in `obs.xray`'s
      `XrayedFunction` under this name: first dispatch AOT-compiles via
      `analyze_jit`, so the per-stage donation layout (args_info
      donated/undonated bytes), compile cost, and flops land in the
      telemetry registry and runs.jsonl next to the `pp/*` schedule
      gauges. graftlint's `pp-schedule-unaudited` rule flags call sites
      that skip this.
    cache: optional `obs.excache` cache for the audited executable
      (donating-mesh steps skip the unsafe tiers automatically).

  Returns:
    jitted (stage_params, opt_state, microbatches, targets) ->
    (stage_params, opt_state, loss). Place stage params / optimizer
    state with `shard_pipeline_tree` first; jit follows the committed
    input shardings, so params and moments stay pp-sharded throughout.
  """

  def step(stage_params, opt_state, microbatches, targets):
    def total_loss(p):
      outputs = pipelined_apply(stage_fn, p, microbatches, mesh,
                                axis_name=axis_name,
                                batch_axis=batch_axis,
                                num_virtual_stages=num_virtual_stages,
                                params_layout=params_layout)
      return loss_fn(outputs, targets)

    loss, grads = jax.value_and_grad(total_loss)(stage_params)
    updates, new_opt_state = optimizer.update(grads, opt_state,
                                              stage_params)
    new_params = optax.apply_updates(stage_params, updates)
    return new_params, new_opt_state, loss

  jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())
  if audit_name is None:
    return jitted
  from tensor2robot_tpu.obs import xray as xray_lib

  return xray_lib.XrayedFunction(audit_name, jitted, cache=cache)


def ravel_stage_stack(stage_params_list: Sequence[Any]):
  """Packs heterogeneous per-stage param pytrees into one [S, P_max]
  leaf, in natural depth order.

  Each stage's pytree is raveled (jax.flatten_util) to a flat vector,
  zero-padded to the widest stage, and the vectors stacked. Returns
  (stacked [S, P_max] array, unravel_fns, sizes): `unravel_fns[s]`
  rebuilds stage s's pytree from `stacked[s, :sizes[s]]`.
  """
  flats, unravels = [], []
  for params in stage_params_list:
    flat, unravel = ravel_pytree(params)
    flats.append(flat)
    unravels.append(unravel)
  sizes = [int(f.size) for f in flats]
  p_max = max(sizes)
  stacked = jnp.stack(
      [jnp.pad(f, (0, p_max - f.size)) for f in flats])
  return stacked, unravels, sizes


def pipelined_apply_heterogeneous(
    stage_fns: Sequence[Callable[[Any, jnp.ndarray], jnp.ndarray]],
    unravel_fns: Sequence[Callable[[jnp.ndarray], Any]],
    param_sizes: Sequence[int],
    stacked_params: jnp.ndarray,
    microbatches: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "pp",
    batch_axis: Optional[str] = None,
    num_virtual_stages: int = 1,
    params_layout: str = "layer") -> jnp.ndarray:
  """Pipelines stages with DIFFERENT functions/params/activation shapes.

  Args:
    stage_fns: per-stage (stage params pytree, flat activation
      [mb, A_max]) -> flat activation [mb, out_size_s] with
      out_size_s <= A_max, in depth order; len == S * v. Each stage
      slices/reshapes what it consumes from the padded buffer and
      returns its (unpadded) flat output; zero-padding back to A_max
      happens here.
    unravel_fns / param_sizes: from `ravel_stage_stack`, depth order.
    stacked_params: [S * v, P_max], sharded over `axis_name`
      (`params_layout` as in `pipelined_apply`).
    microbatches: [num_micro, mb, A_max] — stage 0's inputs, already
      flat-padded to the common buffer width.
    mesh: mesh whose `axis_name` has size S == len(stage_fns) // v.
    batch_axis: optional mesh axis the mb dim stays sharded over (PP x DP
      composition, as in `pipelined_apply`).
    num_virtual_stages: chunks per rank (v); 1 = GPipe, >1 =
      interleaved 1F1B over the same `lax.switch` flat-buffer skeleton.

  Returns:
    [num_micro, mb, A_max] final-stage outputs (zero-padded), replicated
    over the pp axis.
  """
  num_layers = len(stage_fns)
  v = int(num_virtual_stages)
  num_stages = mesh.shape[axis_name]
  if num_stages * v != num_layers:
    raise ValueError(
        f"mesh axis {axis_name!r} has size {mesh.shape[axis_name]} and "
        f"num_virtual_stages={v}, but {num_layers} stage functions were "
        f"given (want num_stages * num_virtual_stages stage functions)")
  if params_layout not in ("layer", "interleaved"):
    raise ValueError(f"params_layout must be 'layer' or 'interleaved', "
                     f"got {params_layout!r}")
  if stacked_params.shape[0] != num_layers:
    # Without this, jax's clamping gather semantics would silently reuse
    # a neighboring chunk's params instead of raising (same guard as the
    # homogeneous path's leading-dim check).
    raise ValueError(
        f"stacked_params leading dim {stacked_params.shape[0]} != "
        f"num_stages {num_stages} * num_virtual_stages {v}")
  num_micro, _, a_max = microbatches.shape
  _validate_and_account(num_stages, num_micro, v, batch_axis)
  if v > 1 and params_layout == "layer":
    stacked_params = interleave_stage_stack(stacked_params, num_stages, v)
  total_ticks, out_ticks, plan = _tick_plan(num_stages, num_micro, v)

  params_spec, replicated_spec = _io_specs(mesh, axis_name, batch_axis)
  perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

  def local_fn(local_params, micro):
    # local_params: [v, P_max] — this rank's chunk vectors, loop-major.
    idx = jax.lax.axis_index(axis_name)

    def branch(layer):
      def run(operands):
        vec, x = operands
        params = unravel_fns[layer](vec[:param_sizes[layer]])
        y = stage_fns[layer](params, x)
        return jnp.pad(y, ((0, 0), (0, a_max - y.shape[-1])))
      return run

    branches = [branch(layer) for layer in range(num_layers)]

    def tick(carry, t):
      valid, m, chunk = plan(t, idx)
      inject = (idx == 0) & valid & (chunk == 0)
      x = jnp.where(inject, micro[m], carry)
      # The global layer this rank runs this tick: loop `chunk`'s visit
      # to rank `idx` is layer chunk*S + idx (see interleave_order).
      layer = chunk * num_stages + idx
      pvec = local_params[chunk]
      y = jax.lax.switch(layer, branches, (pvec, x))
      y = jnp.where(valid, y, jnp.zeros_like(y))
      shifted = jax.lax.ppermute(y, axis_name, perm)
      return shifted, y

    zeros = jnp.zeros_like(micro[0])
    _, ys = jax.lax.scan(tick, zeros, jnp.arange(total_ticks))
    outs = ys[jnp.asarray(out_ticks)]
    outs = jnp.where(idx == num_stages - 1, outs, jnp.zeros_like(outs))
    return jax.lax.psum(outs, axis_name)

  return mesh_lib.shard_map(
      local_fn, mesh=mesh,
      in_specs=(params_spec, replicated_spec),
      out_specs=replicated_spec)(stacked_params, microbatches)


def sequential_apply_heterogeneous(
    stage_fns: Sequence[Callable[[Any, jnp.ndarray], jnp.ndarray]],
    unravel_fns: Sequence[Callable[[jnp.ndarray], Any]],
    param_sizes: Sequence[int],
    stacked_params: jnp.ndarray,
    microbatches: jnp.ndarray) -> jnp.ndarray:
  """The mathematically identical no-mesh schedule: every microbatch
  through every stage in depth order (GPipe and interleaved 1F1B are
  execution schedules, not different functions). Used on a single chip
  and as the equivalence oracle in tests. `stacked_params` is the
  depth-ordered stack from `ravel_stage_stack`."""
  num_micro, _, a_max = microbatches.shape
  outs = []
  for m in range(num_micro):
    x = microbatches[m]
    for s, fn in enumerate(stage_fns):
      y = fn(unravel_fns[s](stacked_params[s, :param_sizes[s]]), x)
      x = jnp.pad(y, ((0, 0), (0, a_max - y.shape[-1])))
    outs.append(x)
  return jnp.stack(outs)


def shard_pipeline_tree(tree: Any, mesh: Mesh,
                        axis_name: str = "pp",
                        num_virtual_stages: int = 1) -> Any:
  """Places a pytree for pipeline training: leaves whose leading dim is
  a positive multiple of the `axis_name` rank count — stage stacks, for
  ANY virtual-chunk factor — are sharded over `axis_name`; everything
  else (optimizer scalars like adam's count) is replicated.

  `num_virtual_stages` is accepted for call-site clarity but no longer
  narrows the match: a v>1 stack placed by a caller with the old 3-arg
  habit used to fall silently into the replicated branch (v× memory on
  every rank + a reshard at each step's shard_map boundary)."""
  del num_virtual_stages  # any rank-count multiple is a stage stack
  num_ranks = mesh.shape[axis_name]
  staged = NamedSharding(mesh, PartitionSpec(axis_name))
  replicated = NamedSharding(mesh, PartitionSpec())

  def _place(x):
    dim0 = x.shape[0] if getattr(x, "ndim", 0) >= 1 else 0
    if dim0 >= num_ranks and dim0 % num_ranks == 0:
      return jax.device_put(x, staged)
    return jax.device_put(x, replicated)

  return jax.tree_util.tree_map(_place, tree)
