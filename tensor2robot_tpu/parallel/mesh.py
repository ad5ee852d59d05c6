"""Device mesh construction and host<->device data placement.

The TPU-native replacement for the reference's distribution machinery
(SURVEY.md §2.5): where the reference splits batches across TPU shards via
TPUEstimator + CrossShardOptimizer
(/root/reference/models/tpu_model_wrapper.py:45-49) and aggregates
multi-worker gradients with SyncReplicasOptimizer
(/root/reference/models/abstract_model.py:864-871), this framework lays
out a `jax.sharding.Mesh` over ICI (+ a DCN axis for multislice) and lets
XLA insert the collectives from sharding annotations.

Axes (any may be size 1):
* `data`  — data parallelism (batch dim), the default;
* `fsdp`  — parameter/optimizer-state sharding (ZeRO-style), a new
            capability the reference lacks;
* `model` — tensor parallelism on annotated layers, also new.
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.utils import config

__all__ = ["create_mesh", "data_sharding", "replicated",
           "put_host_batch", "place_batch", "local_batch_size",
           "DevicePrefetcher", "shard_map", "replica_device_groups",
           "initialize_multihost"]

DEFAULT_AXES = ("data", "fsdp", "model")


def shard_map(f, mesh: Mesh, in_specs, out_specs):
  """THE repo's shard_map entry point: `jax.shard_map` with replication
  checking off. Every explicit SPMD region in this repo (pipeline
  schedules, ring/ulysses attention, MoE all_to_all dispatch) routes
  through this one wrapper — these regions use psum-broadcast outputs
  the checker cannot prove replicated.
  """
  return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)


@config.configurable
def create_mesh(mesh_shape: Optional[Sequence[int]] = None,
                axis_names: Sequence[str] = DEFAULT_AXES,
                devices: Optional[Sequence[jax.Device]] = None,
                dcn_data_parallelism: int = 1) -> Mesh:
  """Builds a Mesh over the available devices.

  With `mesh_shape=None`, all devices go on the first ('data') axis and the
  rest are size 1 — pure DP, the reference's only TPU strategy. For
  multislice pods, `dcn_data_parallelism > 1` builds a hybrid mesh whose
  outermost data axis rides DCN while the inner axes stay on ICI
  (mesh_utils.create_hybrid_device_mesh).
  """
  devices = list(devices if devices is not None else jax.devices())
  n = len(devices)
  if mesh_shape is None:
    mesh_shape = [n] + [1] * (len(axis_names) - 1)
  mesh_shape = list(mesh_shape)
  needed = math.prod(mesh_shape)
  if needed > n:
    raise ValueError(
        f"mesh_shape {mesh_shape} does not cover {n} devices.")
  if needed < n:
    # Explicit smaller meshes use a device prefix (debug / smoke runs).
    devices = devices[:needed]
    n = needed
  if len(mesh_shape) != len(axis_names):
    raise ValueError(
        f"mesh_shape rank {len(mesh_shape)} != axis_names "
        f"{len(axis_names)}.")
  if dcn_data_parallelism > 1:
    ici_shape = list(mesh_shape)
    ici_shape[0] //= dcn_data_parallelism
    dcn_shape = [dcn_data_parallelism] + [1] * (len(axis_names) - 1)
    device_array = mesh_utils.create_hybrid_device_mesh(
        ici_shape, dcn_shape, devices=devices)
  else:
    device_array = mesh_utils.create_device_mesh(mesh_shape,
                                                 devices=devices)
  return Mesh(device_array, tuple(axis_names))


def replica_device_groups(num_replicas: int,
                          devices: Optional[Sequence[jax.Device]] = None
                          ) -> list:
  """Carves the device list into disjoint per-replica groups (the
  graftserve fleet's device carve-out, `serving/fleet.py`).

  Groups are CONTIGUOUS runs of the platform device order, so each
  replica's devices stay within one ICI neighborhood — the same locality
  assumption `create_mesh` makes. Multislice seam: on a DCN-connected
  pod the device order groups by slice first (jax sorts by
  process_index), so `num_replicas == num_slices` puts one replica per
  slice with no cross-DCN dispatch inside a replica; a finer carve-out
  composes with `create_mesh(devices=group)` exactly like the
  single-slice case.

  A remainder (len(devices) % num_replicas) is spread one extra device
  over the FIRST groups rather than left idle — replica capacities may
  then differ by one device, which the fleet's least-outstanding-work
  router absorbs by construction.
  """
  devices = list(devices if devices is not None else jax.devices())
  if num_replicas < 1:
    raise ValueError(f"num_replicas must be >= 1, got {num_replicas}")
  if num_replicas > len(devices):
    raise ValueError(
        f"cannot carve {num_replicas} replica device groups out of "
        f"{len(devices)} devices (>= 1 device per replica required)")
  base, remainder = divmod(len(devices), num_replicas)
  groups = []
  offset = 0
  for index in range(num_replicas):
    size = base + (1 if index < remainder else 0)
    groups.append(devices[offset:offset + size])
    offset += size
  return groups


def data_sharding(mesh: Mesh, batch_axis: str = "data") -> NamedSharding:
  """Sharding for batch leaves: leading dim over the data axis."""
  return NamedSharding(mesh, PartitionSpec(batch_axis))


def replicated(mesh: Mesh) -> NamedSharding:
  return NamedSharding(mesh, PartitionSpec())


def local_batch_size(global_batch_size: int, mesh: Mesh) -> int:
  """Per-host batch size (reference per-host batch override,
  /root/reference/utils/tfdata.py:38-61)."""
  process_count = max(
      1, len({d.process_index for d in mesh.devices.flat}))
  if global_batch_size % process_count:
    raise ValueError(
        f"Global batch {global_batch_size} not divisible by host count "
        f"{process_count}.")
  return global_batch_size // process_count


def put_host_batch(mesh: Mesh, batch, batch_axis: str = "data",
                   spec_structure: Optional[specs_lib.SpecStructLike] = None,
                   batch_spec: Optional[PartitionSpec] = None) -> Any:
  """Forms the global on-device array from each host's local numpy batch.

  Single-host: a plain sharded device_put. Multi-host: every process
  passes its local shard and `jax.make_array_from_process_local_data`
  assembles the global array — the infeed path that replaces
  TPUEstimator's per-host infeed threads.

  `batch_spec` overrides the default batch-dim-only placement for every
  leaf (e.g. PartitionSpec('data', 'sp') for sequence-parallel infeed);
  it must match the step's committed in_shardings.
  """
  flat_partition = None
  if spec_structure is not None:
    flat_partition = specs_lib.partition_specs(spec_structure, batch_axis)

  def _put(path_key, x):
    pspec = batch_spec if batch_spec is not None \
        else PartitionSpec(batch_axis)
    if flat_partition is not None and path_key in flat_partition:
      pspec = flat_partition[path_key]
    sharding = NamedSharding(mesh, pspec)
    if jax.process_count() == 1:
      return jax.device_put(x, sharding)
    return jax.make_array_from_process_local_data(sharding, np.asarray(x))

  if isinstance(batch, specs_lib.SpecStruct):
    out = specs_lib.SpecStruct()
    for key, value in specs_lib.flatten_spec_structure(batch).items():
      out[key] = _put(key, value)
    return out
  return jax.tree_util.tree_map(lambda x: _put(None, x), batch)


def place_batch(mesh: Mesh, batch, batch_spec=None):
  """Places one host batch dict: -> (features, labels) device trees.

  Missing labels become an empty SpecStruct. The single shared
  implementation behind both the train loop's inline path and the
  DevicePrefetcher worker, so the two can never diverge.
  """
  features = put_host_batch(mesh, batch["features"], batch_spec=batch_spec)
  labels = (put_host_batch(mesh, batch["labels"], batch_spec=batch_spec)
            if "labels" in batch else specs_lib.SpecStruct())
  return features, labels


class DevicePrefetcher:
  """Background-thread device infeed: places finished host batches ahead.

  The train loop's async dispatch already overlaps ONE host batch with
  device compute; on a slow host feeding a fast chip that single step of
  lookahead is not enough — the loop thread still serializes
  next(dataset) + put_host_batch between dispatches. This wraps the host
  iterator in a daemon thread that keeps up to `depth` batches already
  resident on device (the JAX-native replacement for TPUEstimator's
  per-host infeed threads, /root/reference/models/tpu_model_wrapper.py
  infeed path). It is also the device-side consumer of the pipelined
  host loader (`data/overlap.py`): upstream stages hand it finished
  numpy batches, it pays only the device transfer.

  Iterating yields (features, labels) pairs already placed with
  `put_host_batch` — or, with a custom `place_fn`, whatever that
  returns (the train loop's stacked-group path places K-step groups
  under the loop spec).
  Exceptions in the worker re-raise in the consumer; `close()` (also
  called on exhaustion) stops the worker promptly, and with
  `close_source` also closes a closable `dataset` (e.g. an
  `OverlappedLoader`, joining its stage threads) once the worker is
  down. `close()` is MANDATORY for library users — an abandoned
  prefetcher pins `depth` device-resident batches until its finalizer
  runs. The context-manager protocol closes on exit; a
  `weakref.finalize` backstop stops the worker of a
  collected-but-unclosed instance.

  graftscope telemetry: `data/overlap_place_ms` (device-placement time
  per batch, worker-side) and `data/overlap_device_queue_depth`
  (device-resident batches ready) ride the standard registry into
  runs.jsonl with the host-stage `data/overlap_*` metrics.
  """

  _STOP = object()

  def __init__(self, dataset, mesh: Optional[Mesh] = None, batch_spec=None,
               depth: int = 2, max_batches: Optional[int] = None,
               place_fn=None, close_source: bool = False, source=None,
               overlap_place: bool = True):
    import itertools
    import queue
    import threading
    import time as time_lib
    import weakref

    from tensor2robot_tpu.obs import metrics as obs_metrics
    from tensor2robot_tpu.obs import trace as trace_lib

    if depth < 1:
      raise ValueError(f"depth must be >= 1, got {depth}")
    if place_fn is None:
      if mesh is None:
        raise ValueError("DevicePrefetcher needs a mesh (default "
                         "place_batch) or an explicit place_fn.")
      place_fn = lambda batch: place_batch(mesh, batch,  # noqa: E731
                                           batch_spec=batch_spec)
    # What close() closes under close_source: by default the dataset
    # itself; pass `source=` when `dataset` is a derived generator and
    # the closable thing is the loader BEHIND it — a generator that is
    # mid-`next` in the worker thread cannot be closed from another
    # thread (ValueError: generator already executing), while a loader
    # close is thread-safe and unsticks the worker.
    self._source = (source if source is not None else dataset) \
        if close_source else None
    if max_batches is not None:
      # Bound the worker to what the consumer will actually take —
      # otherwise it eagerly parses + device-places `depth` extra batches
      # past the end of a bounded loop, pure waste discarded by close().
      dataset = itertools.islice(dataset, max_batches)
    out_queue = queue.Queue(maxsize=depth)
    stop = threading.Event()
    # Worker phase, readable by close(): "source" while blocked in
    # next(dataset), "transfer" during place_fn (an in-flight TPU
    # op — NEVER safe to abandon), "queue"/"done"
    # otherwise. A plain one-slot list: writes are atomic under the GIL.
    phase = ["source"]
    self._queue = out_queue
    self._stop = stop
    self._phase = phase
    self._done = False
    sentinel = self._STOP
    place_hist = obs_metrics.histogram("data/overlap_place_ms")
    depth_gauge = obs_metrics.gauge("data/overlap_device_queue_depth")
    tracer = trace_lib.get_tracer()
    perf_counter_ns = time_lib.perf_counter_ns

    def _host_batches():
      """`dataset`, each `next` of it inside a `data/next_host` span."""
      source = iter(dataset)
      while True:
        with tracer.span("data/next_host", cat="data"):
          try:
            batch = next(source)
          except StopIteration:
            return
        yield batch

    def _place(batch):
      """`place_fn`, timed: the `data/place` span (arg `bytes`) beside the
      histogram. The batch's leaves are walked only for a live tracer."""
      nbytes = sum(int(getattr(leaf, "nbytes", 0))
                   for leaf in jax.tree_util.tree_leaves(batch)
                   ) if tracer.enabled else 0
      with tracer.span("data/place", cat="data", bytes=nbytes):
        t0 = perf_counter_ns()
        placed = place_fn(batch)
        place_hist.record((perf_counter_ns() - t0) * 1e-6)
      return placed

    # The workers close over locals only — never `self` — so an
    # abandoned-without-close() prefetcher is actually collectable (a
    # live thread would otherwise keep `self` reachable forever and the
    # finalizer below could never fire).
    def _put_final(item):
      while not stop.is_set():
        try:
          out_queue.put(item, timeout=0.1)
          return
        except queue.Full:
          continue

    def _worker():
      # Serial fallback (overlap_place=False): one thread does
      # next(dataset) then place_fn — the pre-ROADMAP-6 shape, kept for
      # A/Bs and for place_fns that must not overlap their source.
      try:
        for batch in _host_batches():
          if stop.is_set():
            # Checked between next(dataset) and place_fn so a stop
            # requested while the source was producing skips the device
            # transfer and exits without touching the queue.
            return
          phase[0] = "transfer"
          placed = _place(batch)
          phase[0] = "queue"
          while not stop.is_set():
            try:
              out_queue.put(placed, timeout=0.1)
              break
            except queue.Full:
              continue
          if stop.is_set():
            return
          depth_gauge.set(float(out_queue.qsize()))
          phase[0] = "source"
        _put_final(sentinel)
      except BaseException as e:  # noqa: BLE001 - surfaced to consumer
        _put_final(e)
      finally:
        phase[0] = "done"

    # Overlapped placement (ROADMAP item 6: "unserialize device_put
    # placement"): the single worker used to SERIALIZE next(dataset)
    # with place_fn, so the device transfer of batch N blocked the
    # host-pipeline dequeue of batch N+1. Split into a feeder (host
    # dequeue) and a placer (device_put) over a bounded host queue —
    # batch N+1's source wait now overlaps batch N's transfer. FIFO
    # hand-off on both sides keeps the stream byte-identical to the
    # serial worker (tests/test_overlap.py pins it).
    host_queue = queue.Queue(maxsize=depth) if overlap_place else None
    host_depth_gauge = obs_metrics.gauge("data/overlap_host_queue_depth")

    def _hq_put(item) -> bool:
      while not stop.is_set():
        try:
          host_queue.put(item, timeout=0.1)
          return True
        except queue.Full:
          continue
      return False

    def _feeder():
      try:
        for batch in _host_batches():
          if stop.is_set():
            return
          if not _hq_put(batch):
            return
          host_depth_gauge.set(float(host_queue.qsize()))
        _hq_put(sentinel)
      except BaseException as e:  # noqa: BLE001 - forwarded to consumer
        _hq_put(e)

    def _placer():
      try:
        while not stop.is_set():
          try:
            item = host_queue.get(timeout=0.1)
          except queue.Empty:
            continue
          if item is sentinel:
            _put_final(sentinel)
            return
          if isinstance(item, BaseException):
            _put_final(item)
            return
          phase[0] = "transfer"
          placed = _place(item)
          phase[0] = "queue"
          while not stop.is_set():
            try:
              out_queue.put(placed, timeout=0.1)
              break
            except queue.Full:
              continue
          if stop.is_set():
            return
          depth_gauge.set(float(out_queue.qsize()))
          phase[0] = "host"
      except BaseException as e:  # noqa: BLE001 - surfaced to consumer
        _put_final(e)
      finally:
        phase[0] = "done"

    if overlap_place:
      self._feeder = threading.Thread(target=_feeder, daemon=True,
                                      name="device-prefetch-feed")
      self._thread = threading.Thread(target=_placer, daemon=True,
                                      name="device-prefetch")
      self._feeder.start()
    else:
      self._feeder = None
      self._thread = threading.Thread(target=_worker, daemon=True,
                                      name="device-prefetch")
    self._thread.start()
    # Backstop for abandoned instances: stop (but never join, which is
    # illegal from a GC callback) the workers so they cannot spin at
    # 10 Hz holding device batches forever. close() remains the correct
    # path.
    self._finalizer = weakref.finalize(self, stop.set)

  def __iter__(self):
    return self

  def __next__(self):
    if self._done:
      raise StopIteration
    item = self._queue.get()
    if item is self._STOP:
      self.close()
      raise StopIteration
    if isinstance(item, BaseException):
      self.close()
      raise item
    return item

  def __enter__(self):
    return self

  def __exit__(self, exc_type, exc_value, traceback):
    self.close()
    return False

  def close(self, timeout: float = 60.0):
    """Stops the worker and WAITS for it to finish its in-flight batch.

    The join matters: a daemon thread killed at interpreter shutdown
    mid device_put is a killed TPU client. The workers check the
    stop event at least every 0.1 s, so the joins are normally bounded
    by one in-flight batch. The `timeout` applies ONLY to a thread
    blocked inside next(dataset) on a stalled data source (the FEEDER
    under the default overlapped placement, the single worker in the
    `overlap_place=False` serial mode — the placer never touches the
    source): close() then returns, logging loudly, rather than hang —
    which matters on the preemption save-and-exit path where a timely
    SystemExit beats a clean thread shutdown. While the placer is mid
    device transfer ("transfer" phase), close() keeps waiting
    regardless of `timeout` — abandoning a thread with an in-flight TPU
    op is the wedging hazard itself.
    """
    self._done = True
    self._stop.set()
    import time

    deadline = None
    while True:
      self._thread.join(timeout=1.0)
      if not self._thread.is_alive():
        break
      if self._phase[0] == "transfer":
        deadline = None  # device op in flight: wait it out, full stop
        continue
      if deadline is None:
        deadline = time.monotonic() + timeout
      elif time.monotonic() >= deadline:
        break
    stalled = self._thread if self._thread.is_alive() else None
    if stalled is None and self._feeder is not None:
      # Placer down; the feeder sees the stop event within 0.1 s unless
      # it is blocked in next(dataset) on a stalled source.
      self._feeder.join(timeout=timeout)
      if self._feeder.is_alive():
        stalled = self._feeder
    if stalled is None:
      self._close_source()
      return
    # Stalled inside next(dataset): closing a closable source (e.g. an
    # OverlappedLoader — its get() watches the loader's own stop event)
    # is exactly what unsticks the thread, so try that before giving up
    # on it (only when this prefetcher actually owns a source).
    if self._close_source():
      stalled.join(timeout=5.0)
      if not stalled.is_alive():
        return
    from absl import logging

    logging.error(
        "DevicePrefetcher.close(): %s still alive after %.0fs in "
        "phase %r — blocked in next(dataset) on a stalled data source; "
        "abandoning the daemon thread.", stalled.name, timeout,
        self._phase[0])

  def _close_source(self) -> bool:
    """Closes a `close_source=True` source exactly once (best-effort:
    teardown must not mask the consumer's own error path). Returns
    True when the close succeeded (so close() knows a stalled worker
    may now be unstuck and a short rejoin is worth it)."""
    source, self._source = self._source, None
    if source is None or not hasattr(source, "close"):
      return False
    try:
      source.close()
      return True
    except ValueError:
      # A plain generator currently executing in the worker thread:
      # not closable from here (and closing it would not unstick
      # anything anyway). Expected on the stalled path when no
      # loader-backed `source=` was provided.
      return False
    except Exception:  # noqa: BLE001
      from absl import logging

      logging.exception("DevicePrefetcher: closing the data source "
                        "failed")
      return False


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None,
                         initialization_timeout_secs: float = 300.0,
                         heartbeat_timeout_secs: Optional[float] = None
                         ) -> None:
  """jax.distributed bring-up for multi-host pods (replaces the
  reference's TF_CONFIG cluster plumbing,
  /root/reference/models/abstract_model.py:440-443). No-op when
  single-process or already initialized.

  Failure detection (SURVEY §5): `initialization_timeout_secs` bounds
  how long a worker waits for the coordinator at bring-up — a dead or
  unreachable coordinator surfaces as a clear RuntimeError instead of
  an opaque multi-minute hang. After bring-up, the coordination
  service's own heartbeats detect peers that die mid-training;
  `heartbeat_timeout_secs` tunes how long a silent peer is tolerated
  before the job errors out (None keeps jax's default).
  """
  if num_processes in (None, 1):
    return
  import time

  deadline = time.monotonic() + initialization_timeout_secs
  if process_id not in (None, 0) and coordinator_address:
    # Pre-probe the coordinator over plain TCP within the SAME deadline
    # budget: jax's distributed client handles its init deadline with a
    # FATAL abort (client.h LOG(FATAL)), which no Python except-clause
    # can turn into a diagnosable error. Retrying the probe also
    # tolerates the normal startup race where workers launch before
    # process 0.
    import socket

    host, sep, port_str = coordinator_address.rpartition(":")
    host = host.strip("[]")  # bracketed IPv6 literals
    if not sep or not port_str.isdigit():
      raise ValueError(
          f"coordinator_address {coordinator_address!r} must be "
          "'<host>:<port>' (e.g. '10.0.0.1:8476').")
    port = int(port_str)
    while True:
      try:
        socket.create_connection((host, port), timeout=5.0).close()
        break
      except OSError as exc:
        if time.monotonic() >= deadline:
          raise RuntimeError(
              f"multi-host bring-up failed for process {process_id}/"
              f"{num_processes}: coordinator {coordinator_address!r} "
              "did not become reachable within "
              f"{initialization_timeout_secs:.0f}s "
              f"({type(exc).__name__}: {exc}). Check that process 0 is "
              "alive and the address/port is reachable from this "
              "host.") from exc
        time.sleep(0.5)
  kwargs = {}
  if heartbeat_timeout_secs is not None:
    kwargs["heartbeat_timeout_seconds"] = int(heartbeat_timeout_secs)
  # Hand jax only the RESIDUAL budget so probe + init together respect
  # the caller's bound (jax's own deadline handling is a process abort,
  # so it is the backstop, not the primary detector).
  jax.distributed.initialize(
      coordinator_address=coordinator_address,
      num_processes=num_processes,
      process_id=process_id,
      initialization_timeout=max(1, int(deadline - time.monotonic())),
      **kwargs)
