"""Native data plane: stager/Python-chain parity, fuzz reader parity,
CRC fallback pinning, and the host-seed-offset regression tests.

Semantics contract under test (ISSUE 6 / data/stager.py):
  * eval mode is BYTE-IDENTICAL between the native staging plane and
    the pure-Python generator chain, end to end;
  * train mode yields the same record multiset with tf.data reservoir
    semantics, deterministic per seed (not the identical permutation —
    std::mt19937_64 vs Python's Random);
  * corruption surfaces as IOError on every path, and the toolchain-
    absent fallback produces identical batches;
  * the whole file is backend-free — no jax import anywhere on these
    paths (the data plane is host-only by design).
"""

import os

import numpy as np
import pytest

from tensor2robot_tpu import native
from tensor2robot_tpu import specs as specs_lib
from tensor2robot_tpu.data import codec, parsing, pipeline, tfrecord
from tensor2robot_tpu.data import stager as stager_lib
from tensor2robot_tpu.obs import metrics as obs_metrics
from tensor2robot_tpu.specs import SpecStruct, TensorSpec


@pytest.fixture(scope="module")
def lib():
  lib = native.load()
  if lib is None:
    pytest.skip("native toolchain unavailable")
  return lib


def _write_files(tmp_path, n_files=3, records_per_file=10, tag="d"):
  """Small corpus with distinctive per-record payloads."""
  paths = []
  idx = 0
  for i in range(n_files):
    path = str(tmp_path / f"{tag}-{i}.tfrecord")
    with tfrecord.RecordWriter(path) as w:
      for _ in range(records_per_file):
        w.write(f"{tag}-rec-{idx:04d}".encode() * (idx % 3 + 1))
        idx += 1
    paths.append(path)
  return paths


def _drain(batches):
  records = []
  for batch in batches:
    assert isinstance(batch, stager_lib.StagedBatch)
    records.append(batch.records())
  return records


class TestStageBatches:

  def test_eval_byte_identical_to_python_chain(self, lib, tmp_path):
    """shuffle 0: stager batches == interleave_records -> _batched."""
    paths = _write_files(tmp_path)
    expected_stream = pipeline.interleave_records(paths, cycle_length=2)
    expected = list(pipeline._batched(expected_stream, 4,
                                      drop_remainder=False))
    got = _drain(stager_lib.stage_batches(
        paths, batch_size=4, cycle_length=2, shuffle_buffer=0,
        drop_remainder=False))
    assert got == expected

  def test_iter_staged_records_matches_interleave(self, lib, tmp_path):
    paths = _write_files(tmp_path, n_files=4, records_per_file=7)
    assert (list(stager_lib.iter_staged_records(paths, cycle_length=3))
            == list(pipeline.interleave_records(paths, cycle_length=3)))

  def test_byte_cap_bounds_chunks_stream_invariant(self, lib, tmp_path):
    """max_chunk_bytes flushes chunks early and byte-bounds the reader
    queues, but the flattened record stream is invariant to chunk
    boundaries — the record-mode memory bound must not change what the
    weighted/zip consumers see."""
    paths = _write_files(tmp_path, n_files=3, records_per_file=10)
    ref = list(pipeline.interleave_records(paths, cycle_length=2))
    record_bytes = len(ref[0])
    capped = _drain(stager_lib.stage_batches(
        paths, batch_size=256, cycle_length=2, drop_remainder=False,
        max_chunk_bytes=3 * record_bytes, telemetry=False))
    assert [r for b in capped for r in b] == ref
    assert len(capped) > 5              # early flushes actually engaged
    assert all(len(b) <= 4 for b in capped)
    assert (list(stager_lib.iter_staged_records(
                paths, cycle_length=2, chunk_bytes=3 * record_bytes))
            == ref)

  def test_batch_mode_large_records_exact_batches(self, lib, tmp_path):
    """Exact-batch mode over records big enough that the reader-queue
    byte cap (16 MiB/file) gates admission well before the 64-record
    count cap: batches stay exact and the stream stays intact — the
    cap bounds RSS, never semantics."""
    big = str(tmp_path / "episodes.tfrecord")
    rng = np.random.RandomState(7)
    recs = [rng.bytes(2 << 20) for _ in range(24)]  # 48 MiB total
    with tfrecord.RecordWriter(big) as w:
      for r in recs:
        w.write(r)
    out = _drain(stager_lib.stage_batches([big], batch_size=4,
                                          drop_remainder=False,
                                          telemetry=False))
    assert [len(b) for b in out] == [4] * 6
    assert [r for b in out for r in b] == recs

  def test_byte_cap_admits_oversize_record(self, lib, tmp_path):
    """One record larger than the cap still flows (queues admit into
    empty; the flush-after-append puts it in its own chunk)."""
    big = str(tmp_path / "big.tfrecord")
    recs = [b"a" * 5, b"b" * (1 << 20), b"c" * 5]  # 1 MiB middle record
    with tfrecord.RecordWriter(big) as w:
      for r in recs:
        w.write(r)
    out = _drain(stager_lib.stage_batches(
        [big], batch_size=256, drop_remainder=False,
        max_chunk_bytes=1024, telemetry=False))
    assert [r for b in out for r in b] == recs

  def test_drop_remainder(self, lib, tmp_path):
    paths = _write_files(tmp_path)  # 30 records
    kept = _drain(stager_lib.stage_batches(paths, batch_size=8,
                                           drop_remainder=True))
    assert [len(b) for b in kept] == [8, 8, 8]
    full = _drain(stager_lib.stage_batches(paths, batch_size=8,
                                           drop_remainder=False))
    assert [len(b) for b in full] == [8, 8, 8, 6]

  def test_shuffle_permutation_deterministic_per_seed(self, lib, tmp_path):
    paths = _write_files(tmp_path)

    def run(seed):
      return [r for b in _drain(stager_lib.stage_batches(
          paths, batch_size=4, shuffle_buffer=8, seed=seed,
          drop_remainder=False)) for r in b]

    base = list(pipeline.interleave_records(paths, cycle_length=4))
    a, b, c = run(11), run(11), run(12)
    assert a == b  # deterministic per seed
    assert a != c  # seeds decorrelate
    assert sorted(a) == sorted(base)  # a permutation, nothing dropped
    assert a != base  # actually shuffled

  def test_shuffle_reservoir_semantics(self, lib, tmp_path):
    """tf.data reservoir contract (pipeline.shuffled parity): the k-th
    emitted record was read among the first buffer+k interleaved
    records, and the first emission varies across seeds."""
    paths = _write_files(tmp_path)
    base = list(pipeline.interleave_records(paths, cycle_length=4))
    buffer = 8
    firsts = set()
    for seed in range(40):
      out = [r for b in _drain(stager_lib.stage_batches(
          paths, batch_size=4, shuffle_buffer=buffer, seed=seed,
          drop_remainder=False)) for r in b]
      for k, rec in enumerate(out[:10]):
        assert rec in base[:buffer + k + 1]
      firsts.add(out[0])
    # Python's shuffled has the same property; both draw the evicted
    # slot uniformly, so many distinct firsts must appear over 40 seeds.
    assert len(firsts) >= 5

  def test_corrupt_file_raises_ioerror(self, lib, tmp_path):
    paths = _write_files(tmp_path, n_files=1)
    data = open(paths[0], "rb").read()
    bad = str(tmp_path / "bad.tfrecord")
    with open(bad, "wb") as f:
      f.write(data[:-2])
    with pytest.raises(IOError):
      _drain(stager_lib.stage_batches([bad], batch_size=4,
                                      drop_remainder=False))

  def test_missing_file_raises_ioerror(self, lib, tmp_path):
    with pytest.raises(IOError):
      _drain(stager_lib.stage_batches([str(tmp_path / "nope.tfrecord")],
                                      batch_size=4))

  def test_telemetry_recorded(self, lib, tmp_path):
    paths = _write_files(tmp_path)
    with obs_metrics.isolated():
      batches = _drain(stager_lib.stage_batches(
          paths, batch_size=4, drop_remainder=False))
      snap = obs_metrics.snapshot(prefix="data/")
    assert snap["counter/data/staged_batches"] == len(batches)
    # stage_ms counts the end-of-stream probe too (one extra wait).
    assert snap["hist/data/stage_ms/count"] == len(batches) + 1
    assert snap["hist/data/arena_bytes/mean"] > 0
    assert "gauge/data/stager_queue_depth" in snap

  def test_close_mid_stream_joins_threads(self, lib, tmp_path):
    """Abandoning the stream mid-epoch must stop + join the C++ threads
    (generator close -> RecordStager.__exit__), not leak readers."""
    paths = _write_files(tmp_path, records_per_file=50)
    stream = stager_lib.stage_batches(paths, batch_size=4, queue_depth=1)
    next(stream)
    stream.close()  # must not hang or crash


class TestPipelineIntegration:

  def _make_files(self, tmp_path, n_files=3, records_per_file=10):
    spec = SpecStruct({
        "image": TensorSpec(shape=(4, 3, 3), dtype=np.uint8,
                            name="state/image", data_format="jpeg",
                            is_extracted=True),
        "idx": TensorSpec(shape=(), dtype=np.int64, name="idx"),
    })
    label_spec = SpecStruct({"y": TensorSpec(shape=(1,), name="y")})
    merged = SpecStruct(dict(spec.items(), y=label_spec["y"]))
    rng = np.random.RandomState(0)
    idx = 0
    paths = []
    for i in range(n_files):
      path = tmp_path / f"data-{i}.tfrecord"
      with tfrecord.RecordWriter(str(path)) as w:
        for _ in range(records_per_file):
          w.write(codec.encode_example(
              {"image": rng.randint(0, 255, (4, 3, 3), np.uint8),
               "idx": np.array(idx, np.int64),
               "y": np.array([idx], np.float32)}, merged))
          idx += 1
      paths.append(str(path))
    return spec, label_spec, paths

  def _collect(self, pipe, n=None):
    out = []
    for i, batch in enumerate(pipe):
      if n is not None and i >= n:
        break
      out.append(batch)
    return out

  def test_eval_stager_identical_to_python_chain(self, lib, tmp_path):
    spec, label_spec, paths = self._make_files(tmp_path)
    parse_fn = parsing.create_parse_fn(spec, label_spec)
    kwargs = dict(batch_size=5, mode="eval", repeat=False,
                  prefetch_size=0, cycle_length=2)
    fast = self._collect(pipeline.RecordBatchPipeline(
        paths, parse_fn, use_native_stager=True, **kwargs))
    slow = self._collect(pipeline.RecordBatchPipeline(
        paths, parse_fn, use_native_stager=False, **kwargs))
    assert len(fast) == len(slow) == 6
    for a, b in zip(fast, slow):
      assert sorted(a.keys()) == sorted(b.keys())
      for key in a.keys():
        np.testing.assert_array_equal(np.asarray(a[key]),
                                      np.asarray(b[key]), err_msg=key)

  def test_stager_parses_under_pipeline_files_key(self, lib, tmp_path):
    # Specs may declare several dataset keys while a pipeline feeds just
    # ONE of them (not necessarily dataset_keys[0]). The native plane
    # must parse the staged arena under the pipeline's OWN files key —
    # keying by dataset_keys[0] silently parsed d2's records with d1's
    # plans while the Python chain parsed them correctly under d2.
    spec = SpecStruct({
        "a": TensorSpec(shape=(1,), name="a", dataset_key="d1"),
        "b": TensorSpec(shape=(1,), name="b", dataset_key="d2"),
    })
    parse_fn = parsing.create_parse_fn(spec)
    second_key = parse_fn.dataset_keys[1]
    path = tmp_path / "second.tfrecord"
    wire = "a" if second_key == "d1" else "b"
    with tfrecord.RecordWriter(str(path)) as w:
      for i in range(10):
        w.write(codec.encode_example(
            {wire: np.array([float(i)], np.float32)}, None))
    kwargs = dict(batch_size=5, mode="eval", repeat=False,
                  prefetch_size=0)
    fast = self._collect(pipeline.RecordBatchPipeline(
        {second_key: str(path)}, parse_fn, use_native_stager=True,
        **kwargs))
    slow = self._collect(pipeline.RecordBatchPipeline(
        {second_key: str(path)}, parse_fn, use_native_stager=False,
        **kwargs))
    assert len(fast) == len(slow) == 2
    for a, b in zip(fast, slow):
      assert sorted(a.keys()) == sorted(b.keys())
      for key in a.keys():
        np.testing.assert_array_equal(np.asarray(a[key]),
                                      np.asarray(b[key]), err_msg=key)
    np.testing.assert_allclose(
        np.concatenate([np.asarray(x[f"features/{wire}"]) for x in fast]),
        np.arange(10, dtype=np.float32).reshape(10, 1))

  def test_train_stager_same_multiset_and_deterministic(self, lib,
                                                        tmp_path):
    spec, label_spec, paths = self._make_files(tmp_path)
    parse_fn = parsing.create_parse_fn(spec, label_spec)
    kwargs = dict(batch_size=5, mode="train", seed=3, repeat=False,
                  shuffle_buffer_size=16, prefetch_size=0,
                  drop_remainder=False)

    def run(use_native):
      pipe = pipeline.RecordBatchPipeline(
          paths, parse_fn, use_native_stager=use_native, **kwargs)
      return [int(i) for b in self._collect(pipe)
              for i in b["features/idx"].tolist()]

    fast_a, fast_b, slow = run(True), run(True), run(False)
    assert fast_a == fast_b  # per-seed determinism on the stager path
    assert sorted(fast_a) == sorted(slow) == list(range(30))
    assert fast_a != sorted(fast_a)  # actually shuffled

  def test_multi_epoch_orders_differ(self, lib, tmp_path):
    spec, label_spec, paths = self._make_files(tmp_path)
    parse_fn = parsing.create_parse_fn(spec, label_spec)
    pipe = pipeline.RecordBatchPipeline(
        paths, parse_fn, batch_size=30, mode="train", seed=3,
        shuffle_buffer_size=30, prefetch_size=0, use_native_stager=True)
    it = iter(pipe)
    epoch1 = next(it)["features/idx"].tolist()
    epoch2 = next(it)["features/idx"].tolist()
    assert sorted(epoch1) == sorted(epoch2)
    assert epoch1 != epoch2  # per-epoch seeds decorrelate

  def test_toolchain_absent_fallback(self, lib, tmp_path, monkeypatch):
    """With the stager reported unavailable the pipeline silently runs
    the Python chain and produces the same eval batches."""
    spec, label_spec, paths = self._make_files(tmp_path)
    parse_fn = parsing.create_parse_fn(spec, label_spec)
    kwargs = dict(batch_size=5, mode="eval", repeat=False,
                  prefetch_size=0, cycle_length=2)
    native_out = self._collect(
        pipeline.RecordBatchPipeline(paths, parse_fn, **kwargs))
    monkeypatch.setattr(stager_lib, "stager_available", lambda: False)
    fallback_out = self._collect(
        pipeline.RecordBatchPipeline(paths, parse_fn, **kwargs))
    assert len(native_out) == len(fallback_out)
    for a, b in zip(native_out, fallback_out):
      for key in a.keys():
        np.testing.assert_array_equal(np.asarray(a[key]),
                                      np.asarray(b[key]), err_msg=key)

  def test_forced_stager_warns_when_unavailable(self, lib, tmp_path,
                                                monkeypatch, caplog):
    """An EXPLICIT use_native_stager=True that can't be honored logs a
    loud warning (once per pipeline); auto mode stays silent."""
    spec, label_spec, paths = self._make_files(tmp_path, n_files=1)
    parse_fn = parsing.create_parse_fn(spec, label_spec)
    monkeypatch.setattr(stager_lib, "stager_available", lambda: False)
    kwargs = dict(batch_size=5, mode="eval", repeat=False,
                  prefetch_size=0)
    with caplog.at_level("WARNING"):
      forced = pipeline.RecordBatchPipeline(
          paths, parse_fn, use_native_stager=True, **kwargs)
      batches = self._collect(forced)  # still works on the Python chain
    assert len(batches) == 2
    warnings = [r for r in caplog.records
                if "use_native_stager=True" in r.getMessage()]
    assert len(warnings) == 1  # loud, but once per pipeline
    caplog.clear()
    with caplog.at_level("WARNING"):
      self._collect(pipeline.RecordBatchPipeline(paths, parse_fn, **kwargs))
    assert not [r for r in caplog.records
                if "use_native_stager" in r.getMessage()]

  def test_corrupt_stream_surfaces_through_pipeline(self, lib, tmp_path):
    spec, label_spec, paths = self._make_files(tmp_path, n_files=1)
    data = open(paths[0], "rb").read()
    with open(paths[0], "wb") as f:
      f.write(data[:-3])
    parse_fn = parsing.create_parse_fn(spec, label_spec)
    pipe = pipeline.RecordBatchPipeline(
        paths, parse_fn, batch_size=5, mode="eval", repeat=False,
        prefetch_size=0, use_native_stager=True)
    with pytest.raises(IOError):
      self._collect(pipe)

  def test_weighted_pipeline_parity(self, lib, tmp_path):
    """The weighted sampler rides the native record mode: same batches
    as the pure-Python chain in deterministic (eval) mode."""
    spec, label_spec, paths = self._make_files(tmp_path, n_files=4)
    parse_fn = parsing.create_parse_fn(spec, label_spec)

    def run(use_native):
      pipe = pipeline.WeightedRecordPipeline(
          [paths[:2], paths[2:]], weights=[0.5, 0.5], parse_fn=parse_fn,
          batch_size=5, mode="eval", seed=5, prefetch_size=0,
          use_native_stager=use_native)
      return [int(i) for b in self._collect(pipe)
              for i in b["features/idx"].tolist()]

    assert run(True) == run(False)

  def test_parse_batch_accepts_staged_arena(self, lib, tmp_path):
    """ParseFn.parse_batch(StagedBatch) == parse_batch(list-of-bytes),
    including the mismatch fallback that must materialize records."""
    spec = SpecStruct({
        "image": TensorSpec(shape=(4, 3, 3), dtype=np.uint8,
                            name="state/image", data_format="jpeg",
                            is_extracted=True),
        "pose": TensorSpec(shape=(2,), dtype=np.float32, name="pose"),
    })
    rng = np.random.RandomState(1)
    records = [codec.encode_example(
        {"image": rng.randint(0, 255, (4, 3, 3), np.uint8),
         "pose": rng.randn(2).astype(np.float32)}, spec)
        for _ in range(6)]
    arena = np.frombuffer(b"".join(records), np.uint8).copy()
    lengths = np.asarray([len(r) for r in records], np.int64)
    offsets = np.concatenate([[0], np.cumsum(lengths)[:-1]]).astype(
        np.int64)
    staged = stager_lib.StagedBatch(arena, offsets, lengths)
    parse_fn = parsing.create_parse_fn(spec)
    from_list = parse_fn.parse_batch(records)
    from_arena = parse_fn.parse_batch(staged)
    for key in from_list.keys():
      np.testing.assert_array_equal(np.asarray(from_list[key]),
                                    np.asarray(from_arena[key]),
                                    err_msg=key)
    #

    # No native parser (forced): the Python path materializes records()
    # from the arena and must agree too.
    slow_fn = parsing.create_parse_fn(spec)
    slow_fn._native_parsers[""] = None
    from_arena_slow = slow_fn.parse_batch(staged)
    for key in from_list.keys():
      np.testing.assert_array_equal(np.asarray(from_list[key]),
                                    np.asarray(from_arena_slow[key]),
                                    err_msg=key)


def test_data_bench_ratio_diff_gated():
  """The load-invariant A/B ratio (`stager_vs_python_chain`) is part of
  the runlog diff vocabulary with 'down is bad' direction — a staging
  regression is flagged even when absolute ex/s moved WITH the host."""
  from tensor2robot_tpu.obs import runlog

  def rec(value, ratio):
    return runlog.make_record(
        "bench", platform="cpu",
        bench={"metric": "qtopt_parse_ex_per_sec_cpu_smoke",
               "value": value, "unit": "examples/sec",
               "stager_vs_python_chain": ratio})

  # Host got faster but the stager lost its edge: absolute ex/s is up
  # (not a regression), the ratio collapsed (flagged).
  deltas = {d["metric"]: d
            for d in runlog.diff_records(rec(50_000, 1.9),
                                         rec(80_000, 1.1))}
  assert not deltas["examples_per_sec"]["regressed"]
  assert deltas["stager_vs_python_chain"]["regressed"]
  # Stable ratio within the 15% band: no flag.
  deltas = {d["metric"]: d
            for d in runlog.diff_records(rec(50_000, 1.9),
                                         rec(48_000, 1.8))}
  assert not deltas["stager_vs_python_chain"]["regressed"]


def test_stager_path_backend_free(lib, tmp_path):
  """The whole records->parsed-batch plane (stager + parse_arena +
  pipeline) runs without touching any JAX backend: poisoned
  JAX_PLATFORMS subprocess, same trap as tests/test_static_analysis.py
  — a backend init would also take the chip."""
  import os as os_lib
  import subprocess
  import sys

  repo_root = os_lib.path.dirname(
      os_lib.path.dirname(os_lib.path.abspath(__file__)))
  code = """
import numpy as np
from tensor2robot_tpu.data import codec, parsing, pipeline, tfrecord
from tensor2robot_tpu.specs import SpecStruct, TensorSpec

spec = SpecStruct({
    "image": TensorSpec(shape=(4, 3, 3), dtype=np.uint8,
                        name="state/image", data_format="jpeg",
                        is_extracted=True),
    "idx": TensorSpec(shape=(), dtype=np.int64, name="idx"),
})
rng = np.random.RandomState(0)
path = %r
with tfrecord.RecordWriter(path) as w:
  for i in range(20):
    w.write(codec.encode_example(
        {"image": rng.randint(0, 255, (4, 3, 3), np.uint8),
         "idx": np.array(i, np.int64)}, spec))
pipe = pipeline.RecordBatchPipeline(
    [path], parsing.create_parse_fn(spec), batch_size=5, mode="train",
    seed=1, shuffle_buffer_size=8, repeat=False, prefetch_size=0,
    use_native_stager=True)
seen = sorted(int(i) for b in pipe for i in b["features/idx"].tolist())
assert seen == list(range(20)), seen
from jax._src import xla_bridge
live = getattr(xla_bridge, "_backends", None)
assert not live, f"jax backends were initialized: {sorted(live)}"
print("NO_BACKEND_OK")
""" % str(tmp_path / "trap.tfrecord")
  env = {**os_lib.environ, "PYTHONPATH": repo_root,
         "JAX_PLATFORMS": "stager_trap"}
  env.pop("XLA_FLAGS", None)
  result = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=600,
                          cwd=repo_root, env=env)
  assert result.returncode == 0, (result.stdout[-2000:],
                                  result.stderr[-2000:])
  assert "NO_BACKEND_OK" in result.stdout


class TestHostSeedOffset:
  """ISSUE 6 satellite: fewer files than hosts -> co-hosted processes
  must not read identical record orders."""

  def _pipe(self, paths, parse_fn, process_index, process_count,
            **overrides):
    kwargs = dict(batch_size=5, mode="train", seed=9, repeat=False,
                  shuffle_buffer_size=16, prefetch_size=0,
                  drop_remainder=False)
    kwargs.update(overrides)
    return pipeline.RecordBatchPipeline(
        paths, parse_fn, process_index=process_index,
        process_count=process_count, **kwargs)

  def _order(self, pipe):
    return [int(i) for b in pipe for i in b["features/idx"].tolist()]

  def test_shared_file_hosts_get_offset_orders(self, tmp_path):
    t = TestPipelineIntegration()
    spec, label_spec, paths = t._make_files(tmp_path, n_files=1,
                                            records_per_file=30)
    parse_fn = parsing.create_parse_fn(spec, label_spec)
    host0 = self._order(self._pipe(paths, parse_fn, 0, 2))
    host1 = self._order(self._pipe(paths, parse_fn, 1, 2))
    # Same full file list on both hosts (1 file, 2 hosts)...
    assert sorted(host0) == sorted(host1) == list(range(30))
    # ...but the seed offset decorrelates the record orders.
    assert host0 != host1
    # And host 0 matches a single-process pipeline bit for bit (the
    # offset is zero there — pre-round-6 determinism is preserved).
    single = self._order(self._pipe(paths, parse_fn, 0, 1))
    assert host0 == single

  def test_weighted_pipeline_threads_host_offset(self, tmp_path):
    # WeightedRecordPipeline drives its sources' _record_tuples directly
    # (bypassing their _epoch_seed), so _source_iter must add the
    # source's _host_seed_offset itself — without it, co-hosted
    # processes on the shared-files path read identical weighted
    # streams.
    t = TestPipelineIntegration()
    spec, label_spec, paths = t._make_files(tmp_path, n_files=1,
                                            records_per_file=30)
    parse_fn = parsing.create_parse_fn(spec, label_spec)

    def _weighted(process_index):
      return pipeline.WeightedRecordPipeline(
          [paths], [1.0], parse_fn, batch_size=5, mode="train", seed=9,
          repeat=False, shuffle_buffer_size=16, prefetch_size=0,
          drop_remainder=False, process_index=process_index,
          process_count=2)

    host0 = self._order(_weighted(0))
    host1 = self._order(_weighted(1))
    # Both hosts see the full record set (1 file shared by 2 hosts)...
    assert sorted(host0) == sorted(host1) == list(range(30))
    # ...in decorrelated orders, and host 0 matches single-process.
    assert host0 != host1
    assert host0 == self._order(_weighted(0))
    single = self._order(pipeline.WeightedRecordPipeline(
        [paths], [1.0], parse_fn, batch_size=5, mode="train", seed=9,
        repeat=False, shuffle_buffer_size=16, prefetch_size=0,
        drop_remainder=False))
    assert host0 == single

  def test_sharded_hosts_unaffected(self, tmp_path):
    t = TestPipelineIntegration()
    spec, label_spec, paths = t._make_files(tmp_path, n_files=2,
                                            records_per_file=10)
    parse_fn = parsing.create_parse_fn(spec, label_spec)
    host0 = self._pipe(paths, parse_fn, 0, 2)
    host1 = self._pipe(paths, parse_fn, 1, 2)
    assert host0._host_seed_offset == 0
    assert host1._host_seed_offset == 0
    seen0 = set(self._order(host0))
    seen1 = set(self._order(host1))
    assert not seen0 & seen1  # disjoint shards, as before

  def test_resolve_file_patterns_public_contract_unchanged(self,
                                                           tmp_path):
    paths = _write_files(tmp_path, n_files=1)
    assert pipeline.resolve_file_patterns(paths, 0, 2) == paths
    assert pipeline.resolve_file_patterns(paths, 1, 2) == paths
    files, shared = pipeline._resolve_file_patterns_sharded(paths, 1, 2)
    assert files == paths and shared


class TestShuffledGuard:
  """ISSUE 6 satellite: shuffled(stream, 0) is a pass-through."""

  def test_zero_buffer_passthrough(self):
    items = list(range(20))
    assert list(pipeline.shuffled(iter(items), 0)) == items

  def test_negative_buffer_passthrough(self):
    items = list(range(5))
    assert list(pipeline.shuffled(iter(items), -3)) == items

  def test_positive_buffer_still_shuffles(self):
    items = list(range(100))
    out = list(pipeline.shuffled(iter(items), 32, seed=0))
    assert sorted(out) == items and out != items


class TestCrcFallback:
  """ISSUE 6 satellite: chunked slicing-by-8 CRC32C fallback pins
  identical masked CRCs vs the native library."""

  def test_known_vectors(self):
    assert tfrecord._crc32c(b"123456789") == 0xE3069283  # RFC 3720
    assert tfrecord._crc32c(b"\x00" * 32) == 0x8A9136AA
    assert tfrecord._crc32c(b"") == 0

  def test_matches_native_on_random_payloads(self, lib):
    rng = np.random.RandomState(0)
    # Cover the word-loop/tail split: every length mod 8, empty, and
    # multi-KiB payloads.
    for n in [*range(0, 18), 64, 255, 4096, 65537]:
      payload = rng.randint(0, 256, n, np.uint8).tobytes()
      crc = tfrecord._crc32c(payload)
      masked = ((((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF)
      assert masked == native.masked_crc32c(payload), n

  def test_writer_reader_roundtrip_without_native(self, tmp_path,
                                                  monkeypatch):
    monkeypatch.setattr(native, "masked_crc32c", lambda data: None)
    monkeypatch.setattr(native, "available", lambda: False)
    path = str(tmp_path / "py.tfrecord")
    records = [b"x" * n for n in (0, 1, 7, 8, 9, 1000)]
    with tfrecord.RecordWriter(path) as w:
      for r in records:
        w.write(r)
    assert list(tfrecord.iter_records(path, verify_crc=True)) == records


class TestReaderFuzzParity:
  """ISSUE 6 satellite: fuzzed TFRecord files through BOTH iter_records
  paths -> identical records, identical error classes."""

  def _both_paths(self, path, monkeypatch, verify_crc=False):
    """Returns (native_outcome, python_outcome): ('ok', records) or
    ('error', exception type)."""

    def run():
      try:
        return "ok", list(tfrecord.iter_records(path,
                                                verify_crc=verify_crc))
      except Exception as e:  # noqa: BLE001 - class parity is the test
        return "error", type(e)

    native_out = run()
    with monkeypatch.context() as m:
      m.setattr(native, "available", lambda: False)
      python_out = run()
    return native_out, python_out

  def _write(self, tmp_path, records, name="f.tfrecord"):
    path = str(tmp_path / name)
    with tfrecord.RecordWriter(path) as w:
      for r in records:
        w.write(r)
    return path

  def test_empty_file(self, lib, tmp_path, monkeypatch):
    path = str(tmp_path / "empty.tfrecord")
    open(path, "wb").close()
    a, b = self._both_paths(path, monkeypatch)
    assert a == b == ("ok", [])

  def test_empty_and_large_records(self, lib, tmp_path, monkeypatch):
    rng = np.random.RandomState(0)
    records = [b"", rng.bytes(3 * 1024 * 1024), b"", b"tail"]
    path = self._write(tmp_path, records)
    for verify in (False, True):
      a, b = self._both_paths(path, monkeypatch, verify_crc=verify)
      assert a == b == ("ok", records)

  @pytest.mark.parametrize("cut", ["header", "body", "footer"])
  def test_truncated_tail(self, lib, tmp_path, monkeypatch, cut):
    records = [b"alpha" * 20, b"beta" * 50]
    path = self._write(tmp_path, records)
    size = os.path.getsize(path)
    last = 12 + len(records[1]) + 4  # header + body + footer
    keep = {"header": size - last + 5,
            "body": size - last + 12 + 37,
            "footer": size - 2}[cut]
    data = open(path, "rb").read()
    with open(path, "wb") as f:
      f.write(data[:keep])
    a, b = self._both_paths(path, monkeypatch)
    assert a == b
    assert a[0] == "error" and issubclass(a[1], IOError)

  @pytest.mark.parametrize("where", ["length", "data"])
  def test_corrupt_crc(self, lib, tmp_path, monkeypatch, where):
    records = [b"payload-one", b"payload-two"]
    path = self._write(tmp_path, records)
    data = bytearray(open(path, "rb").read())
    offset = 8 if where == "length" else 12 + len(records[0])
    data[offset] ^= 0xFF  # flip a CRC byte of record 0
    with open(path, "wb") as f:
      f.write(bytes(data))
    # verify_crc=True: both paths reject with IOError.
    a, b = self._both_paths(path, monkeypatch, verify_crc=True)
    assert a == b
    assert a[0] == "error" and issubclass(a[1], IOError)
    # verify_crc=False: both paths read straight through.
    a, b = self._both_paths(path, monkeypatch, verify_crc=False)
    assert a == b == ("ok", records)

  def test_garbage_length_prefix(self, lib, tmp_path, monkeypatch):
    path = str(tmp_path / "garbage.tfrecord")
    with open(path, "wb") as f:
      f.write(b"\xff" * 64)  # implausible 2^64-ish length
    a, b = self._both_paths(path, monkeypatch)
    assert a[0] == b[0] == "error"
    assert issubclass(a[1], IOError) and issubclass(b[1], IOError)
