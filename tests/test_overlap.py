"""Pipelined host data plane (ISSUE 9): parity, teardown, attribution.

Covers `data/overlap.py` (OverlappedLoader stages), the generalized
`parallel.mesh.DevicePrefetcher` (place_fn / close_source), the
stepstats data_wait attribution contract under an overlapped producer,
the graftlint thread-stage rules, and the backend-free trap for the
whole overlapped chain.
"""

import gc
import os
import threading
import time

import numpy as np
import pytest

from tensor2robot_tpu.analysis import thread_check
from tensor2robot_tpu.data import codec, input_generators, overlap, parsing
from tensor2robot_tpu.data import pipeline, tfrecord
from tensor2robot_tpu.obs import metrics as metrics_lib
from tensor2robot_tpu.obs import stepstats as stepstats_lib
from tensor2robot_tpu.specs import SpecStruct, TensorSpec

NUM_RECORDS = 60
BATCH = 5


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
  root = tmp_path_factory.mktemp("overlap_corpus")
  spec = SpecStruct({
      "idx": TensorSpec(shape=(), dtype=np.int64, name="idx"),
      "payload": TensorSpec(shape=(8,), dtype=np.float32, name="payload"),
  })
  rng = np.random.RandomState(0)
  per_file = NUM_RECORDS // 2
  for shard in range(2):
    path = os.path.join(str(root), f"c-{shard:05d}.tfr")
    with tfrecord.RecordWriter(path) as writer:
      for i in range(per_file):
        writer.write(codec.encode_example(
            {"idx": np.array(shard * per_file + i, np.int64),
             "payload": rng.randn(8).astype(np.float32)}, spec))
  return os.path.join(str(root), "c-*.tfr"), spec


def _pipe(corpus, preprocess_fn=None, **overrides):
  patterns, spec = corpus
  kwargs = dict(batch_size=BATCH, mode="train", seed=11,
                shuffle_buffer_size=16, repeat=False, prefetch_size=2,
                preprocess_fn=preprocess_fn)
  kwargs.update(overrides)
  return pipeline.RecordBatchPipeline(patterns,
                                      parsing.create_parse_fn(spec),
                                      **kwargs)


def _flat_batches(pipe):
  out = []
  for batch in pipe:
    out.append({k: np.asarray(v) for k, v in batch["features"].items()})
  return out


def _assert_batches_equal(got, want):
  assert len(got) == len(want)
  for g, w in zip(got, want):
    assert g.keys() == w.keys()
    for key in g:
      np.testing.assert_array_equal(g[key], w[key])


def _wait_for_thread_baseline(baseline, timeout=5.0):
  deadline = time.monotonic() + timeout
  while time.monotonic() < deadline:
    if threading.active_count() <= baseline:
      return True
    time.sleep(0.05)
  return threading.active_count() <= baseline


class TestOverlapParity:
  """ISSUE 9 satellite: byte/order parity of the overlapped loader vs
  the serial chain — same records, same seed determinism, eval mode
  byte-identical."""

  def test_eval_mode_byte_identical_to_serial_chain(self, corpus):
    overlapped = _flat_batches(_pipe(corpus, mode="eval",
                                     shuffle_buffer_size=0))
    serial = _flat_batches(_pipe(corpus, mode="eval",
                                 shuffle_buffer_size=0, overlap=False,
                                 prefetch_size=0))
    _assert_batches_equal(overlapped, serial)

  def test_train_mode_byte_identical_same_seed(self, corpus):
    overlapped = _flat_batches(_pipe(corpus))
    serial = _flat_batches(_pipe(corpus, overlap=False, prefetch_size=0))
    _assert_batches_equal(overlapped, serial)

  def test_train_seed_determinism_and_sensitivity(self, corpus):
    a = _flat_batches(_pipe(corpus, seed=23))
    b = _flat_batches(_pipe(corpus, seed=23))
    c = _flat_batches(_pipe(corpus, seed=24))
    _assert_batches_equal(a, b)
    same_multiset = sorted(
        int(i) for batch in a for i in batch["idx"].tolist()) == sorted(
        int(i) for batch in c for i in batch["idx"].tolist())
    assert same_multiset
    assert any((x["idx"] != y["idx"]).any() for x, y in zip(a, c))

  def test_preprocess_runs_serial_in_stream_order(self, corpus):
    """Stateful/seeded preprocessors keep deterministic behavior: ONE
    preprocess worker applies batches in raw-stream order, so a
    stateful counter stamps the same values the serial chain stamps."""

    def make_preprocess():
      counter = [0]

      def preprocess(features, labels, mode):
        features["order"] = np.full((len(features["idx"]),),
                                    counter[0], np.int64)
        counter[0] += 1
        return features, labels

      return preprocess

    overlapped = _flat_batches(
        _pipe(corpus, preprocess_fn=make_preprocess(),
              num_parallel_parses=3))
    serial = _flat_batches(
        _pipe(corpus, preprocess_fn=make_preprocess(), overlap=False,
              prefetch_size=0, num_parallel_parses=1))
    _assert_batches_equal(overlapped, serial)


class TestFusedPreprocess:
  """ISSUE 12 satellite (ROADMAP item 6's last slice): preprocess moves
  into the parse pool when purity is declared — byte-identical to the
  serial-worker chain, with the auto gate keeping stateful preprocess
  fns on the ordered single worker."""

  def test_fused_byte_identical_to_serial_worker(self, corpus):
    def pure(features, labels, mode):
      features["doubled"] = np.asarray(features["payload"]) * 2.0
      return features, labels

    pure.stateless = True  # declared purity: the auto gate fuses
    fused = _flat_batches(_pipe(corpus, preprocess_fn=pure,
                                num_parallel_parses=3))
    serial_worker = _flat_batches(_pipe(corpus, preprocess_fn=pure,
                                        num_parallel_parses=3,
                                        fused_preprocess=False))
    fully_serial = _flat_batches(_pipe(corpus, preprocess_fn=pure,
                                       overlap=False, prefetch_size=0))
    _assert_batches_equal(fused, serial_worker)
    _assert_batches_equal(fused, fully_serial)

  def test_auto_gate_on_declared_purity_only(self, corpus):
    from tensor2robot_tpu.preprocessors import base as preprocessors_base

    # Bound AbstractPreprocessor.preprocess: pure by contract -> fused.
    patterns, spec = corpus
    pre = preprocessors_base.NoOpPreprocessor(
        model_feature_specification_fn=lambda mode: spec,
        model_label_specification_fn=lambda mode: SpecStruct())
    bound = _pipe(corpus, preprocess_fn=pre.preprocess)
    assert bound._fuse_preprocess_enabled() is True
    # Bare callable: may close over cross-batch state -> serial worker.
    bare = _pipe(corpus, preprocess_fn=lambda f, l, m: (f, l))
    assert bare._fuse_preprocess_enabled() is False
    # Declared stateless attribute -> fused; explicit override wins.
    fn = lambda f, l, m: (f, l)  # noqa: E731
    fn.stateless = True
    declared = _pipe(corpus, preprocess_fn=fn)
    assert declared._fuse_preprocess_enabled() is True
    forced_off = _pipe(corpus, preprocess_fn=fn, fused_preprocess=False)
    assert forced_off._fuse_preprocess_enabled() is False
    # No preprocess at all: trivially pure.
    assert _pipe(corpus)._fuse_preprocess_enabled() is True

  def test_stateful_preprocess_keeps_stream_order_under_auto(self, corpus):
    """The auto gate must leave a stateful bare callable on the single
    ordered worker — the same stamps the serial chain produces even
    with a 3-thread parse pool racing ahead."""

    def make_stateful():
      counter = [0]

      def preprocess(features, labels, mode):
        features["order"] = np.full((len(features["idx"]),),
                                    counter[0], np.int64)
        counter[0] += 1
        return features, labels

      return preprocess

    auto = _flat_batches(_pipe(corpus, preprocess_fn=make_stateful(),
                               num_parallel_parses=3))
    serial = _flat_batches(_pipe(corpus, preprocess_fn=make_stateful(),
                                 overlap=False, prefetch_size=0,
                                 num_parallel_parses=1))
    _assert_batches_equal(auto, serial)

  def test_fused_mode_records_stage_telemetry(self, corpus):
    def pure(features, labels, mode):
      return features, labels

    pure.stateless = True
    with metrics_lib.isolated() as registry:
      batches = _flat_batches(_pipe(corpus, preprocess_fn=pure))
      snap = registry.snapshot()
    assert batches
    # Per-stage attribution survives fusion: parse AND preprocess
    # histograms both populated.
    assert snap.get("hist/data/overlap_parse_ms/count", 0.0) > 0.0
    assert snap.get("hist/data/overlap_preprocess_ms/count", 0.0) > 0.0

  def test_generator_seam_carries_fused_knob(self, corpus):
    patterns, spec = corpus
    generator = input_generators.DefaultRecordInputGenerator(
        file_patterns=patterns, batch_size=BATCH)
    generator.set_overlap_options(fused_preprocess=False)
    assert generator._overlap_options["fused_preprocess"] is False


class TestOverlapTeardown:
  """ISSUE 9 satellite: close() joins every stage with zero leaked
  threads; errors propagate; abandoned loaders are backstopped."""

  def test_close_joins_every_stage_thread(self, corpus):
    baseline = threading.active_count()
    loader = iter(_pipe(corpus, repeat=True))
    assert isinstance(loader, overlap.OverlappedLoader)
    next(loader)
    assert threading.active_count() > baseline
    loader.close()
    assert _wait_for_thread_baseline(baseline), (
        f"leaked threads: {[t.name for t in threading.enumerate()]}")

  def test_exhaustion_closes_stages(self, corpus):
    baseline = threading.active_count()
    loader = iter(_pipe(corpus))
    batches = list(loader)
    assert len(batches) == NUM_RECORDS // BATCH
    assert _wait_for_thread_baseline(baseline)

  def test_close_is_idempotent_and_context_managed(self, corpus):
    baseline = threading.active_count()
    with iter(_pipe(corpus, repeat=True)) as loader:
      next(loader)
    loader.close()  # second close is a no-op
    assert _wait_for_thread_baseline(baseline)

  def test_parse_error_propagates_and_joins(self, corpus):
    baseline = threading.active_count()

    def boom(_):
      raise RuntimeError("parse exploded")

    loader = overlap.OverlappedLoader(iter([1, 2, 3]), boom, lambda x: x)
    with pytest.raises(RuntimeError, match="parse exploded"):
      next(loader)
    assert _wait_for_thread_baseline(baseline)

  def test_source_error_propagates(self):
    def bad_source():
      yield [1]
      raise IOError("disk gone")

    loader = overlap.OverlappedLoader(bad_source(), lambda x: x,
                                      lambda x: x)
    assert next(loader) == [1]
    with pytest.raises(IOError, match="disk gone"):
      while True:
        next(loader)

  def test_finalizer_stops_abandoned_loader(self, corpus):
    loader = iter(_pipe(corpus, repeat=True))
    next(loader)
    stop = loader._stop
    del loader  # abandoned without close()
    gc.collect()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not stop.is_set():
      gc.collect()
      time.sleep(0.05)
    assert stop.is_set()

  def test_byte_cap_admits_oversize_batch(self):
    """A byte-capped hand-off queue must always admit an item when
    empty — one over-cap batch flows alone instead of deadlocking (the
    native stager's reader-queue rule)."""
    big = {"x": np.zeros((1 << 20,), np.uint8)}  # 1 MiB >> 1 KiB cap
    loader = overlap.OverlappedLoader(
        iter([big, big, big]), lambda x: x, lambda x: x,
        max_bytes=1 << 10)
    got = [next(loader) for _ in range(3)]
    assert all(g["x"].nbytes == 1 << 20 for g in got)
    loader.close()


class TestDevicePrefetcherGeneralized:
  """The prefetcher as the consumer of the pipelined loader: custom
  place_fn, close_source propagation (no mesh required)."""

  def test_place_fn_without_mesh(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    items = [{"x": np.full((2,), i, np.float32)} for i in range(4)]
    pf = mesh_lib.DevicePrefetcher(iter(items),
                                   place_fn=lambda b: ("placed", b))
    got = list(pf)
    assert [g[0] for g in got] == ["placed"] * 4
    np.testing.assert_array_equal(got[2][1]["x"], items[2]["x"])

  @pytest.mark.parametrize("overlap_place", [False, True])
  def test_spans_per_batch_only_under_a_live_tracer(self, overlap_place):
    """`data/next_host` and `data/place` (arg `bytes`), one a batch and on
    the workers' own threads; a tracer that is off records nothing, and
    the placement histogram counts either way."""
    from tensor2robot_tpu.obs import trace as trace_lib
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    def run():
      metrics_lib.reset()
      items = [{"x": np.full((2,), i, np.float32)} for i in range(4)]
      assert len(list(mesh_lib.DevicePrefetcher(
          iter(items), place_fn=lambda b: b,
          overlap_place=overlap_place))) == 4
      assert metrics_lib.snapshot()[
          "hist/data/overlap_place_ms/count"] == 4.0
      return trace_lib.get_tracer().events()

    trace_lib.clear()
    assert run() == []
    trace_lib.enable()
    try:
      events = [e for e in run() if e["ph"] == "X"]
    finally:
      trace_lib.disable()
      trace_lib.clear()
    places = [e for e in events if e["name"] == "data/place"]
    assert [e["args"]["bytes"] for e in places] == [8] * 4
    # One more pull than batches: the one that finds the source empty.
    assert len([e for e in events if e["name"] == "data/next_host"]) == 5
    assert all("parent" not in e and e["tid"] != threading.get_ident()
               for e in events)

  def test_requires_mesh_or_place_fn(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    with pytest.raises(ValueError, match="place_fn"):
      mesh_lib.DevicePrefetcher(iter(()))

  def test_close_source_closes_loader(self, corpus):
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    baseline = threading.active_count()
    loader = iter(_pipe(corpus, repeat=True))
    pf = mesh_lib.DevicePrefetcher(loader, place_fn=lambda b: b,
                                   depth=1, close_source=True)
    next(pf)
    pf.close()
    assert _wait_for_thread_baseline(baseline), (
        f"leaked threads: {[t.name for t in threading.enumerate()]}")

  def test_stalled_worker_unstuck_by_source_close(self):
    """Worker blocked in next(dataset) where dataset is a DERIVED
    generator: the executing generator cannot be closed from another
    thread, but closing the `source=` loader behind it (train_eval's
    shape) unsticks the worker — close() returns with the thread
    joined instead of abandoning it after the full timeout."""
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    class FakeLoader:
      def __init__(self):
        self.closed = threading.Event()

      def __iter__(self):
        return self

      def __next__(self):
        self.closed.wait(timeout=30)  # stalled source
        raise StopIteration

      def close(self):
        self.closed.set()

    loader = FakeLoader()

    def derived():
      yield {"x": np.zeros((2,), np.float32)}
      for item in loader:  # pragma: no cover - never yields
        yield item

    pf = mesh_lib.DevicePrefetcher(derived(), place_fn=lambda b: b,
                                   depth=1, close_source=True,
                                   source=loader)
    next(pf)
    start = time.perf_counter()
    pf.close(timeout=0.5)
    assert time.perf_counter() - start < 10.0
    assert loader.closed.is_set()
    assert not pf._thread.is_alive()

  def test_without_close_source_loader_stays_open(self, corpus):
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    loader = iter(_pipe(corpus, repeat=True))
    pf = mesh_lib.DevicePrefetcher(loader, place_fn=lambda b: b, depth=1)
    next(pf)
    pf.close()
    try:
      assert not loader._done  # caller still owns the loader
    finally:
      loader.close()

  def test_overlapped_placement_stream_identical_to_serial(self):
    """ROADMAP item 6 (PR 11 slice): the split feeder/placer pipeline
    must hand the consumer the SAME stream, in order, as the serial
    worker — and actually overlap (source pull of batch N+1 starts
    while batch N is still inside place_fn)."""
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    def make_items():
      return [{"x": np.full((3,), i, np.float32)} for i in range(8)]

    overlap_seen = []
    pulled = []

    def tracking_source():
      for item in make_items():
        pulled.append(int(item["x"][0]))
        yield item

    in_place = threading.Event()

    def slow_place(batch):
      in_place.set()
      time.sleep(0.02)  # window for the feeder to pull ahead
      overlap_seen.append(len(pulled))
      return ("placed", batch)

    serial = list(mesh_lib.DevicePrefetcher(
        iter(make_items()), place_fn=lambda b: ("placed", b),
        overlap_place=False))
    overlapped = list(mesh_lib.DevicePrefetcher(
        tracking_source(), place_fn=slow_place, depth=2))
    assert len(overlapped) == len(serial) == 8
    for (tag_a, a), (tag_b, b) in zip(serial, overlapped):
      np.testing.assert_array_equal(a["x"], b["x"])
    # Overlap proof: by the time some batch finished placing, the
    # feeder had already pulled batches beyond it from the source.
    placed_count = list(range(1, 9))
    assert any(seen > placed for seen, placed
               in zip(overlap_seen, placed_count)), (
        overlap_seen, "feeder never ran ahead of the placer")

  def test_overlapped_placement_close_joins_both_threads(self, corpus):
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    baseline = threading.active_count()
    loader = iter(_pipe(corpus, repeat=True))
    pf = mesh_lib.DevicePrefetcher(loader, place_fn=lambda b: b,
                                   depth=1, close_source=True)
    assert pf._feeder is not None  # overlapped by default
    next(pf)
    pf.close()
    assert not pf._thread.is_alive() and not pf._feeder.is_alive()
    assert _wait_for_thread_baseline(baseline), (
        f"leaked threads: {[t.name for t in threading.enumerate()]}")

  def test_overlapped_placement_source_error_propagates(self):
    from tensor2robot_tpu.parallel import mesh as mesh_lib

    def bad_source():
      yield {"x": np.zeros((2,), np.float32)}
      raise RuntimeError("source died")

    pf = mesh_lib.DevicePrefetcher(bad_source(), place_fn=lambda b: b,
                                   depth=1)
    next(pf)
    with pytest.raises(RuntimeError, match="source died"):
      next(pf)
    assert not pf._thread.is_alive() and not pf._feeder.is_alive()


class TestStepStatsOverlapAttribution:
  """ISSUE 9 satellite: host work that overlaps device compute must
  inflate NEITHER data_wait_ms NOR device_wait_ms. Synthetic overlapped
  producer: each batch costs PRODUCE_MS of background host work, each
  "device step" BARRIER_MS at the closing barrier; the loop's
  data_wait wraps only the dequeue, so in steady state it reads ~0 and
  device_wait_ms reads ~BARRIER_MS."""

  PRODUCE_MS = 40.0
  BARRIER_MS = 70.0
  STEPS = 6

  def test_overlapped_producer_attribution(self):
    from queue import Queue

    q = Queue(maxsize=2)
    stop = threading.Event()

    def producer():
      i = 0
      while not stop.is_set() and i < self.STEPS + 2:
        time.sleep(self.PRODUCE_MS / 1e3)  # the host data work
        q.put({"batch": i})
        i += 1

    thread = threading.Thread(target=producer, daemon=True)

    def barrier(_state):
      time.sleep(self.BARRIER_MS / 1e3)  # the device compute wait
      return np.float32(1.0)

    with metrics_lib.isolated() as registry:
      rec = stepstats_lib.StepStatsRecorder(
          batch_size=4, every_n_steps=1, barrier=barrier,
          registry=registry, device_gauges=False)
      thread.start()
      try:
        rec.start()
        with rec.data_wait():
          placed = q.get()
        for step in range(1, self.STEPS + 1):
          rec.before_dispatch()
          _ = placed  # async dispatch returns immediately
          rec.after_dispatch()
          if step < self.STEPS:
            # Stage the next batch while the "device" runs: the
            # producer works during the barrier below.
            with rec.data_wait():
              placed = q.get()
          rec.end_step(step, state=None)
      finally:
        stop.set()
        thread.join()
      records = [r for _, r in rec.drain()]
    assert len(records) == self.STEPS
    # Steady-state windows (skip the first: the producer had no device
    # window to hide behind yet).
    steady = records[1:]
    mean_wait = np.mean([r["data_wait_ms"] for r in steady])
    mean_device = np.mean([r["device_wait_ms"] for r in steady])
    # The producer's PRODUCE_MS/batch of host work ran DURING the
    # barrier window: data_wait must show only the residual dequeue
    # wait, far below the actual host cost...
    assert mean_wait < 0.5 * self.PRODUCE_MS, [
        r["data_wait_ms"] for r in steady]
    # ...and device_wait_ms must reflect the barrier, not barrier + host.
    assert mean_device >= 0.7 * self.BARRIER_MS
    assert mean_device < self.BARRIER_MS + 0.5 * self.PRODUCE_MS, [
        r["device_wait_ms"] for r in steady]

  def test_starved_consumer_shows_data_wait(self):
    """Inverse contract: when the producer CANNOT keep up (no device
    window to hide behind), the stall lands in data_wait_ms — the
    starvation signal obs.sentinel keys on."""
    with metrics_lib.isolated() as registry:
      rec = stepstats_lib.StepStatsRecorder(
          batch_size=4, every_n_steps=1, barrier=lambda s: None,
          registry=registry, device_gauges=False)
      rec.start()
      for step in range(1, 4):
        rec.before_dispatch()
        rec.after_dispatch()
        with rec.data_wait():
          time.sleep(0.05)  # serial host staging, nothing overlapped
        rec.end_step(step, state=None)
      records = [r for _, r in rec.drain()]
    assert all(r["data_wait_ms"] >= 40.0 for r in records)


class TestTrainEvalOverlapKnobs:
  """ISSUE 9 satellite: prefetch depth / worker count / queue byte-caps
  as gin configurables on train_eval_model, flowing generator ->
  pipeline -> loader."""

  def test_set_overlap_options_reaches_loader(self, corpus):
    patterns, spec = corpus
    gen = input_generators.DefaultRecordInputGenerator(
        patterns, batch_size=BATCH, seed=3)
    gen.set_specification(spec)
    gen.set_overlap_options(num_parallel_parses=3, prefetch_size=4,
                            overlap_queue_mb=1)
    loader = gen.create_dataset("train")
    try:
      assert isinstance(loader, overlap.OverlappedLoader)
      assert loader._pool._max_workers == 3
      assert loader._out_q._max_items == 4
      assert loader._out_q._max_bytes == 1 << 20
    finally:
      loader.close()

  def test_train_eval_model_accepts_overlap_knobs(self):
    """The gin-exposed parameters exist on train_eval_model with None
    defaults (None = keep the generator's own tuning)."""
    import inspect

    from tensor2robot_tpu import train_eval

    sig = inspect.signature(train_eval.train_eval_model.__wrapped__) \
        if hasattr(train_eval.train_eval_model, "__wrapped__") else \
        inspect.signature(train_eval.train_eval_model)
    params = sig.parameters
    assert params["host_overlap_workers"].default is None
    assert params["host_overlap_queue_mb"].default is None
    assert params["device_prefetch_depth"].default == 2

  def test_trainer_fed_by_records_through_the_overlapped_plane(self,
                                                              tmp_path):
    """`train_eval_model` on TFRecords: with the overlap plane on (the
    default) every batch reaches the step through the OverlappedLoader's
    stages and the DevicePrefetcher's place stage, and the run ends on
    the very loss the serial chain (`overlap=False`) ends on."""
    from tensor2robot_tpu import train_eval
    from tensor2robot_tpu.utils import mocks

    model_spec = mocks.MockT2RModel(device_type="cpu")
    merged = SpecStruct(dict(
        model_spec.get_feature_specification("train").items(),
        y=model_spec.get_label_specification("train")["y"]))
    x, y = mocks.make_separable_data(64)
    path = str(tmp_path / "d.tfr")
    with tfrecord.RecordWriter(path) as writer:
      for i in range(64):
        writer.write(codec.encode_example({"x": x[i], "y": y[i]}, merged))
    steps = 6

    def run(name, overlap_on):
      with metrics_lib.isolated():
        metrics = train_eval.train_eval_model(
            model=mocks.MockT2RModel(device_type="cpu"),
            model_dir=str(tmp_path / name), mode="train",
            max_train_steps=steps, checkpoint_every_n_steps=steps,
            log_every_n_steps=steps, mesh_shape=(1, 1, 1),
            executable_cache_dir=None,
            input_generator_train=(
                input_generators.DefaultRecordInputGenerator(
                    file_patterns=path, batch_size=8, seed=0,
                    overlap=overlap_on)))
        return metrics, metrics_lib.snapshot(prefix="data/")

    overlapped, seen = run("overlapped", None)
    assert seen["counter/data/overlap_batches"] >= steps
    assert seen["hist/data/overlap_parse_ms/count"] >= steps
    # The trainer draws its first batch itself, to shape the state.
    assert seen["hist/data/overlap_place_ms/count"] >= steps - 1
    serial, seen = run("serial", False)
    assert "counter/data/overlap_batches" not in seen
    assert np.isfinite(overlapped["loss"])
    assert overlapped["loss"] == serial["loss"]

  def test_generators_without_record_pipeline_accept_options(self):
    gen = input_generators.DefaultRandomInputGenerator(batch_size=2)
    gen.set_overlap_options(num_parallel_parses=4)  # accepted, ignored


class TestThreadStageLintRule:
  """ISSUE 9 satellite: the graftlint rule mechanizing the
  DevicePrefetcher thread discipline for new loader/stage classes."""

  def _findings(self, source):
    return thread_check.check_python_source("<test>", source)

  def test_missing_close_flagged(self):
    src = ("import threading\n"
           "class Stage:\n"
           "  def start(self):\n"
           "    self._t = threading.Thread(target=print)\n"
           "    self._t.start()\n")
    rules = [f.rule for f in self._findings(src)]
    assert rules == ["thread-stage-missing-close"]

  def test_close_without_backstop_flagged(self):
    src = ("import threading\n"
           "class Stage:\n"
           "  def start(self):\n"
           "    self._t = threading.Thread(target=print)\n"
           "  def close(self):\n"
           "    self._t.join()\n")
    rules = [f.rule for f in self._findings(src)]
    assert rules == ["thread-stage-missing-backstop"]

  def test_context_manager_or_finalizer_satisfies(self):
    cm = ("import threading\n"
          "class Stage:\n"
          "  def start(self):\n"
          "    self._t = threading.Thread(target=print)\n"
          "  def close(self):\n"
          "    self._t.join()\n"
          "  def __enter__(self):\n"
          "    return self\n")
    fin = ("import threading, weakref\n"
           "class Stage:\n"
           "  def __init__(self):\n"
           "    stop = threading.Event()\n"
           "    self._t = threading.Thread(target=print)\n"
           "    self._fin = weakref.finalize(self, stop.set)\n"
           "  def close(self):\n"
           "    self._t.join()\n")
    assert not self._findings(cm)
    assert not self._findings(fin)

  def test_functions_and_nested_classes_scoped(self):
    src = ("import threading\n"
           "def run_load():\n"
           "  t = threading.Thread(target=print)\n"
           "  t.start()\n"
           "  t.join()\n")
    assert not self._findings(src)

  def test_suppression(self):
    src = ("import threading\n"
           "class Stage:\n"
           "  def start(self):\n"
           "    self._t = threading.Thread(\n"
           "        target=print)"
           "  # graftlint: disable=thread-stage-missing-close\n")
    findings = thread_check.check_python_source("<test>", src)
    from tensor2robot_tpu.analysis.findings import (filter_findings,
                                                    load_suppressions)
    assert not filter_findings(findings, load_suppressions(src))

  def test_repo_stage_classes_are_clean(self):
    """The shipped loader/stage classes pass the rule (the mechanized
    discipline is the one they already follow)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rel in ("tensor2robot_tpu/data/overlap.py",
                "tensor2robot_tpu/parallel/mesh.py",
                "tensor2robot_tpu/serving/batcher.py",
                "tensor2robot_tpu/data/pipeline.py"):
      assert not thread_check.check_python_file(
          os.path.join(repo_root, rel)), rel


def test_overlap_plane_backend_free(corpus):
  """The whole overlapped chain (stager/python source -> parse pool ->
  preprocess worker -> byte-capped queue) runs without touching any
  JAX backend: poisoned JAX_PLATFORMS subprocess, the repo-standard
  trap — a backend init would also take the chip."""
  import subprocess
  import sys

  patterns, _ = corpus
  repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  code = """
import numpy as np
from tensor2robot_tpu.data import overlap, parsing, pipeline
from tensor2robot_tpu.specs import SpecStruct, TensorSpec

spec = SpecStruct({
    "idx": TensorSpec(shape=(), dtype=np.int64, name="idx"),
    "payload": TensorSpec(shape=(8,), dtype=np.float32, name="payload"),
})
pipe = pipeline.RecordBatchPipeline(
    %r, parsing.create_parse_fn(spec), batch_size=5, mode="train",
    seed=1, shuffle_buffer_size=8, repeat=False, prefetch_size=2,
    num_parallel_parses=2)
loader = iter(pipe)
assert isinstance(loader, overlap.OverlappedLoader), type(loader)
seen = sorted(int(i) for b in loader for i in b["features/idx"].tolist())
assert seen == list(range(%d)), seen
from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("NO_BACKEND_OK")
""" % (patterns, NUM_RECORDS)
  env = {**os.environ, "PYTHONPATH": repo_root,
         "JAX_PLATFORMS": "overlap_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600,
                          cwd=repo_root, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "NO_BACKEND_OK" in result.stdout
