"""Tests for the native C++ TFRecord reader / CRC32C path."""

import numpy as np
import pytest

from tensor2robot_tpu import native
from tensor2robot_tpu.data import tfrecord


@pytest.fixture(scope="module")
def lib():
  lib = native.load()
  if lib is None:
    pytest.skip("native toolchain unavailable")
  return lib


class TestNative:

  def test_crc32c_known_vectors(self, lib):
    # RFC 3720 test vector: crc32c of 32 zero bytes.
    assert lib.t2r_crc32c(b"\x00" * 32, 32) == 0x8A9136AA
    assert lib.t2r_crc32c(b"123456789", 9) == 0xE3069283

  def test_masked_crc_matches_python(self, lib):
    data = b"some record payload"
    native_crc = native.masked_crc32c(data)
    py_crc = ((((tfrecord._crc32c(data) >> 15)
                | (tfrecord._crc32c(data) << 17)) + 0xA282EAD8)
              & 0xFFFFFFFF)
    assert native_crc == py_crc

  def test_native_reader_roundtrip(self, lib, tmp_path):
    path = str(tmp_path / "d.tfrecord")
    records = [b"a" * n for n in (1, 1000, 0, 65536)]
    with tfrecord.RecordWriter(path) as w:
      for r in records:
        w.write(r)
    got = list(native.iter_records_native(path, verify_crc=True))
    assert got == records

  def test_native_reader_detects_corruption(self, lib, tmp_path):
    path = tmp_path / "bad.tfrecord"
    with tfrecord.RecordWriter(str(path)) as w:
      w.write(b"hello world")
    raw = bytearray(path.read_bytes())
    raw[14] ^= 0xFF  # flip a payload byte
    path.write_bytes(bytes(raw))
    with pytest.raises(IOError, match="crc"):
      list(native.iter_records_native(str(path), verify_crc=True))

  def test_tfrecord_module_uses_native(self, lib, tmp_path):
    path = str(tmp_path / "d.tfrecord")
    with tfrecord.RecordWriter(path) as w:
      w.write(b"via native")
    assert tfrecord.read_records(path, verify_crc=True) == [b"via native"]

  def test_throughput_sanity(self, lib, tmp_path):
    """Native reader should stream tens of MB/s at minimum."""
    import time

    path = str(tmp_path / "big.tfrecord")
    payload = b"x" * 4096
    with tfrecord.RecordWriter(path) as w:
      for _ in range(2000):
        w.write(payload)
    start = time.perf_counter()
    n = sum(1 for _ in native.iter_records_native(path, verify_crc=True))
    elapsed = time.perf_counter() - start
    assert n == 2000
    mb_per_s = 2000 * 4096 / elapsed / 1e6
    assert mb_per_s > 20, f"native reader too slow: {mb_per_s:.1f} MB/s"


class TestNativeExampleParser:

  def _records(self, n=4):
    from tensor2robot_tpu.data import codec
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "pose": TensorSpec(shape=(3,), dtype=np.float32, name="pose"),
        "step": TensorSpec(shape=(), dtype=np.int64, name="step"),
        "image": TensorSpec(shape=(6, 6, 3), dtype=np.uint8, name="img",
                            data_format="png"),
    })
    rng = np.random.RandomState(0)
    records, rows = [], []
    for i in range(n):
      img = rng.randint(0, 255, (6, 6, 3), np.uint8)
      rows.append((np.full(3, i, np.float32), i, img))
      records.append(codec.encode_example(
          {"pose": rows[-1][0], "step": np.array(i, np.int64),
           "image": img}, spec))
    return spec, records, rows

  def test_parse_fn_uses_native_and_matches(self, lib):
    from tensor2robot_tpu.data import parsing

    spec, records, rows = self._records()
    parse_fn = parsing.create_parse_fn(spec)
    assert parse_fn._native_parsers[""] is not None, "fast path not built"
    out = parse_fn.parse_batch(records)
    for i, (pose, step, img) in enumerate(rows):
      np.testing.assert_allclose(out["features/pose"][i], pose)
      assert int(out["features/step"][i]) == step
      np.testing.assert_array_equal(out["features/image"][i], img)

  def test_python_and_native_agree(self, lib):
    from tensor2robot_tpu.data import parsing

    spec, records, _ = self._records()
    fast = parsing.create_parse_fn(spec)
    slow = parsing.create_parse_fn(spec)
    slow._native_parsers[""] = None  # force the python path
    out_fast = fast.parse_batch(records)
    out_slow = slow.parse_batch(records)
    for key in out_slow.keys():
      np.testing.assert_array_equal(np.asarray(out_fast[key]),
                                    np.asarray(out_slow[key]),
                                    err_msg=key)

  def test_extracted_raw_planes_stay_native_and_match_python(self, lib):
    """is_extracted raw planes (the pod-scale no-decode feed) take the
    native columnar path and agree with the Python parser byte-for-byte."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "image": TensorSpec(shape=(8, 6, 3), dtype=np.uint8,
                            name="state/image", data_format="jpeg",
                            is_extracted=True),
        "pose": TensorSpec(shape=(4,), dtype=np.float32, name="pose"),
    })
    rng = np.random.RandomState(0)
    records, planes = [], []
    for _ in range(5):
      plane = rng.randint(0, 255, (8, 6, 3), np.uint8)
      planes.append(plane)
      records.append(codec.encode_example(
          {"image": plane.tobytes(),
           "pose": rng.randn(4).astype(np.float32)}, spec))
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None, \
        "extracted plane spec fell off the native path"
    slow = parsing.create_parse_fn(spec)
    slow._native_parsers[""] = None
    out_fast = fast.parse_batch(records)
    out_slow = slow.parse_batch(records)
    for key in out_slow.keys():
      np.testing.assert_array_equal(np.asarray(out_fast[key]),
                                    np.asarray(out_slow[key]),
                                    err_msg=key)
    for i, plane in enumerate(planes):
      np.testing.assert_array_equal(out_fast["features/image"][i], plane)

  def test_extracted_plane_split_across_values_matches_python(self, lib):
    """A plane split over several bytes values joins identically on both
    paths (the Python path has always joined)."""
    from tensor2robot_tpu.data import example_pb2, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "image": TensorSpec(shape=(4, 2, 3), dtype=np.uint8,
                            name="img", data_format="png",
                            is_extracted=True),
    })
    plane = np.arange(24, dtype=np.uint8).reshape(4, 2, 3)
    example = example_pb2.Example()
    raw = plane.tobytes()
    example.features.feature["img"].bytes_list.value.extend(
        [raw[:10], raw[10:]])
    records = [example.SerializeToString()]
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    slow = parsing.create_parse_fn(spec)
    slow._native_parsers[""] = None
    np.testing.assert_array_equal(
        fast.parse_batch(records)["features/image"][0], plane)
    np.testing.assert_array_equal(
        slow.parse_batch(records)["features/image"][0], plane)

  def test_extracted_plane_empty_bytes_list_raises_clearly(self, lib):
    """An empty bytes list re-parses on the Python path (the columnar
    parser cannot tell it from a non-bytes wire kind) and still fails
    loudly there — never a silent zero plane."""
    from tensor2robot_tpu.data import example_pb2, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "image": TensorSpec(shape=(2, 2, 3), dtype=np.uint8,
                            name="img", data_format="png",
                            is_extracted=True),
    })
    example = example_pb2.Example()
    example.features.feature["img"].bytes_list.SetInParent()
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    with pytest.raises(ValueError, match="0 values"):
      fast.parse_batch([example.SerializeToString()])

  def test_extracted_legacy_float_list_falls_back_to_python(self, lib):
    """Legacy writers stored numeric planes as float_list; the native
    path must detect the wire-kind mismatch and re-parse via Python
    instead of erroring (pre-native-path behavior preserved)."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "plane": TensorSpec(shape=(2, 3), dtype=np.float32, name="plane",
                            data_format="png", is_extracted=True),
        "pose": TensorSpec(shape=(2,), dtype=np.float32, name="pose"),
    })
    values = np.arange(6, dtype=np.float32).reshape(2, 3)
    pose = np.array([1.0, -1.0], np.float32)
    # encode WITHOUT specs: numeric arrays land as float_list wire kind.
    record = codec.encode_example({"plane": values, "pose": pose}, None)
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    out = fast.parse_batch([record])
    np.testing.assert_allclose(out["features/plane"][0], values)
    np.testing.assert_allclose(out["features/pose"][0], pose)
    # One mismatched batch falls back alone; only a run of
    # _NATIVE_DISABLE_STREAK consecutive mismatches means the stream
    # carries the legacy format throughout and disables the fast path.
    assert fast._native_parsers[""] is not None
    for _ in range(parsing._NATIVE_DISABLE_STREAK - 1):
      out2 = fast.parse_batch([record])
      np.testing.assert_allclose(out2["features/plane"][0], values)
    assert fast._native_parsers[""] is None
    out3 = fast.parse_batch([record])
    np.testing.assert_allclose(out3["features/plane"][0], values)

  def test_native_mismatch_streak_resets_on_good_batch(self, lib):
    """A single anomalous record must not march the stream toward
    disablement: a well-formed batch resets the consecutive-mismatch
    counter (ADVICE r3: per-batch fallback, not permanent disable)."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "plane": TensorSpec(shape=(2, 3), dtype=np.float32, name="plane",
                            data_format="png", is_extracted=True),
    })
    values = np.arange(6, dtype=np.float32).reshape(2, 3)
    legacy = codec.encode_example({"plane": values}, None)  # float_list
    good = codec.encode_example({"plane": values}, spec)    # bytes plane
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    for _ in range(2 * parsing._NATIVE_DISABLE_STREAK):
      for record in ((legacy,) * (parsing._NATIVE_DISABLE_STREAK - 1)
                     + (good,)):
        out = fast.parse_batch([record])
        np.testing.assert_allclose(out["features/plane"][0], values)
    assert fast._native_parsers[""] is not None, \
        "interleaved good batches must keep the native path enabled"
    # ...but not forever: a shuffle-merged legacy/new stream trips the
    # TOTAL mismatch budget even though good batches keep resetting the
    # streak, bounding the wasted native passes.
    while fast._native_mismatch_total[""] < parsing._NATIVE_DISABLE_TOTAL:
      fast.parse_batch([legacy])
      fast.parse_batch([good])
    assert fast._native_parsers[""] is None, \
        "total mismatch budget must disable the native path"

  def test_native_rare_mismatch_ratio_never_disables(self, lib):
    """A long-lived stream with RARE anomalous batches keeps the fast
    path indefinitely (ADVICE r4): the total budget only disables when
    mismatches are also >= _NATIVE_DISABLE_RATIO of attempted batches,
    so 1-in-10 anomalies never trip it even past the total count."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "plane": TensorSpec(shape=(2, 3), dtype=np.float32, name="plane",
                            data_format="png", is_extracted=True),
    })
    values = np.arange(6, dtype=np.float32).reshape(2, 3)
    legacy = codec.encode_example({"plane": values}, None)  # float_list
    good = codec.encode_example({"plane": values}, spec)    # bytes plane
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    # Mismatch ratio 10% (1 legacy per 10 batches), well under the 25%
    # ratio gate; run past the total budget to prove the count alone no
    # longer disables.
    for _ in range(parsing._NATIVE_DISABLE_TOTAL + 5):
      out = fast.parse_batch([legacy])
      np.testing.assert_allclose(out["features/plane"][0], values)
      for _ in range(9):
        fast.parse_batch([good])
    assert fast._native_mismatch_total[""] > parsing._NATIVE_DISABLE_TOTAL
    assert fast._native_parsers[""] is not None, \
        "rare anomalies must not permanently disable the native path"

  def test_extracted_plane_over_cap_split_falls_back(self, lib):
    """A plane split across more bytes values than the native cap joins
    correctly via the Python fallback (pre-native behavior preserved)."""
    from tensor2robot_tpu.data import example_pb2, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "image": TensorSpec(shape=(10, 3), dtype=np.uint8, name="img",
                            data_format="png", is_extracted=True),
    })
    plane = np.arange(30, dtype=np.uint8).reshape(10, 3)
    raw = plane.tobytes()
    example = example_pb2.Example()
    example.features.feature["img"].bytes_list.value.extend(
        [raw[i:i + 5] for i in range(0, 30, 5)])  # 6 values > cap of 4
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    record = example.SerializeToString()
    out = fast.parse_batch([record])
    np.testing.assert_array_equal(out["features/image"][0], plane)
    # Per-batch fallback: still enabled until the mismatch streak runs.
    assert fast._native_parsers[""] is not None
    for _ in range(parsing._NATIVE_DISABLE_STREAK - 1):
      fast.parse_batch([record])
    assert fast._native_parsers[""] is None  # disabled after the streak

  def test_extracted_plane_contiguous_single_copy_path(self, lib):
    """Well-formed batches take the wrapper's contiguous buffer (one
    memmove per record), not the per-record bytes-object path."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "image": TensorSpec(shape=(4, 4, 3), dtype=np.uint8, name="img",
                            data_format="png", is_extracted=True),
    })
    rng = np.random.RandomState(3)
    planes = [rng.randint(0, 255, (4, 4, 3), np.uint8) for _ in range(3)]
    records = [codec.encode_example({"image": p}, spec) for p in planes]
    fast = parsing.create_parse_fn(spec)
    parser = fast._native_parsers[""]
    assert parser is not None
    parsed = parser.parse(records)
    assert any(v is not None for v in parsed["bytes_planes"].values()), \
        "contiguous plane path did not engage"
    out = fast.parse_batch(records)
    for i, p in enumerate(planes):
      np.testing.assert_array_equal(out["features/image"][i], p)

  def test_string_extracted_spec_falls_back_to_python(self, lib):
    """frombuffer cannot read string dtypes: a string extracted spec
    must keep the Python path (and still parse) rather than build a
    native plan that crashes at parse time."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "blob": TensorSpec(shape=(1,), dtype=str, name="blob",
                           data_format="png", is_extracted=True),
    })
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is None, \
        "string extracted spec must not take the native path"
    def _parsed_strings(value):
      record = codec.encode_example({"blob": value}, spec)
      flat = np.asarray(fast.parse_batch([record])["features/blob"])
      return [e.decode() if isinstance(e, bytes) else str(e)
              for e in flat.reshape(-1)]

    # bytes, str, and ragged lists must all survive the wire unpadded
    # and un-transcoded (no UTF-32, no 'S'-array null padding).
    assert _parsed_strings([b"payload"]) == ["payload"]
    assert _parsed_strings("payload") == ["payload"]
    ragged_spec_out = _parsed_strings([b"ab", b"c"])
    assert ragged_spec_out[:1] == ["ab"]  # shape (1,) spec keeps value 0

  def test_optional_and_sequence_fall_back(self, lib):
    from tensor2robot_tpu.data import parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    optional = SpecStruct({
        "a": TensorSpec(shape=(1,), name="a", is_optional=True)})
    assert parsing.create_parse_fn(optional)._native_parsers[""] is None
    seq = SpecStruct({
        "s": TensorSpec(shape=(None, 2), name="s", is_sequence=True)})
    assert parsing.create_parse_fn(seq)._native_parsers[""] is None

  def test_missing_required_feature_raises(self, lib):
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({"a": TensorSpec(shape=(1,), name="a"),
                       "b": TensorSpec(shape=(1,), name="b")})
    record = codec.encode_example({"a": np.zeros(1, np.float32)}, None)
    parse_fn = parsing.create_parse_fn(spec)
    assert parse_fn._native_parsers[""] is not None
    with pytest.raises(ValueError, match="missing required feature 'b'"):
      parse_fn.parse_batch([record])

  def test_wrong_element_count_raises(self, lib):
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({"a": TensorSpec(shape=(3,), name="a")})
    record = codec.encode_example({"a": np.zeros(2, np.float32)}, None)
    parse_fn = parsing.create_parse_fn(spec)
    with pytest.raises(ValueError, match="malformed feature"):
      parse_fn.parse_batch([record])

  def _sequence_spec_and_records(self, n=4, t_data=5):
    from tensor2robot_tpu.data import codec
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "episode_id": TensorSpec(shape=(), dtype=np.int64,
                                 name="episode_id"),
        "poses": TensorSpec(shape=(4, 3), dtype=np.float32, name="poses",
                            is_sequence=True),
        "frames": TensorSpec(shape=(4, 6, 6, 3), dtype=np.uint8,
                             name="frames", data_format="png",
                             is_sequence=True),
    })
    rng = np.random.RandomState(0)
    records, rows = [], []
    for i in range(n):
      poses = rng.rand(t_data, 3).astype(np.float32)
      frames = rng.randint(0, 255, (t_data, 6, 6, 3), np.uint8)
      rows.append((i, poses, frames))
      records.append(codec.encode_sequence_example(
          context={"episode_id": np.array(i, np.int64)},
          sequences={"poses": poses, "frames": frames},
          spec_structure=spec))
    return spec, records, rows

  def test_sequence_example_uses_native(self, lib):
    """BC-Z/VRGripper-style episode records hit the native fast path."""
    from tensor2robot_tpu.data import parsing

    spec, records, rows = self._sequence_spec_and_records()
    parse_fn = parsing.create_parse_fn(spec)
    assert parse_fn._native_parsers[""] is not None, \
        "SequenceExample fast path not built"
    out = parse_fn.parse_batch(records)
    for i, (eid, poses, frames) in enumerate(rows):
      assert int(out["features/episode_id"][i]) == eid
      # data time dim 5 clips to the spec's 4
      np.testing.assert_allclose(out["features/poses"][i], poses[:4])
      np.testing.assert_array_equal(out["features/frames"][i], frames[:4])
      assert int(out["features/poses_length"][i]) == 5

  def test_sequence_native_matches_python(self, lib):
    from tensor2robot_tpu.data import parsing

    for t_data in (2, 4, 5):  # pad, exact, clip
      spec, records, _ = self._sequence_spec_and_records(t_data=t_data)
      fast = parsing.create_parse_fn(spec)
      assert fast._native_parsers[""] is not None
      slow = parsing.create_parse_fn(spec)
      slow._native_parsers[""] = None
      out_fast = fast.parse_batch(records)
      out_slow = slow.parse_batch(records)
      assert set(out_fast.keys()) == set(out_slow.keys())
      for key in out_slow.keys():
        np.testing.assert_array_equal(np.asarray(out_fast[key]),
                                      np.asarray(out_slow[key]),
                                      err_msg=f"{key} (t_data={t_data})")

  def test_multi_image_bytes_list(self, lib):
    """A context feature with N image values ([N, H, W, C] spec) parses
    natively — the multi-bytes path."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.data import example_pb2
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "cameras": TensorSpec(shape=(3, 6, 6, 3), dtype=np.uint8,
                              name="cameras", data_format="png"),
    })
    rng = np.random.RandomState(0)
    records, expected = [], []
    for _ in range(2):
      imgs = rng.randint(0, 255, (3, 6, 6, 3), np.uint8)
      expected.append(imgs)
      example = example_pb2.Example()
      for img in imgs:
        example.features.feature["cameras"].bytes_list.value.append(
            codec.encode_image(img, "png"))
      records.append(example.SerializeToString())
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    out = fast.parse_batch(records)
    for i in range(2):
      np.testing.assert_array_equal(out["features/cameras"][i],
                                    expected[i])
    slow = parsing.create_parse_fn(spec)
    slow._native_parsers[""] = None
    out_slow = slow.parse_batch(records)
    np.testing.assert_array_equal(np.asarray(out["features/cameras"]),
                                  np.asarray(out_slow["features/cameras"]))

  def test_missing_context_image_zero_fills_like_python(self, lib):
    """Reference empty-string -> zeros image fallback must hold on the
    native path too (review r2 finding)."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "pose": TensorSpec(shape=(3,), dtype=np.float32, name="pose"),
        "image": TensorSpec(shape=(6, 6, 3), dtype=np.uint8, name="img",
                            data_format="png"),
    })
    record = codec.encode_example({"pose": np.ones(3, np.float32)}, spec)
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    slow = parsing.create_parse_fn(spec)
    slow._native_parsers[""] = None
    out_fast = fast.parse_batch([record])
    out_slow = slow.parse_batch([record])
    np.testing.assert_array_equal(out_fast["features/image"],
                                  np.zeros((1, 6, 6, 3), np.uint8))
    np.testing.assert_array_equal(np.asarray(out_fast["features/image"]),
                                  np.asarray(out_slow["features/image"]))

  def test_too_many_multi_image_values_raises(self, lib):
    """More bytes values than the spec's leading dim must be a loud
    error, not a silent clip (review r2 finding)."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.data import example_pb2
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "cameras": TensorSpec(shape=(2, 6, 6, 3), dtype=np.uint8,
                              name="cameras", data_format="png"),
    })
    example = example_pb2.Example()
    rng = np.random.RandomState(0)
    for _ in range(4):  # 4 values, spec says 2
      example.features.feature["cameras"].bytes_list.value.append(
          codec.encode_image(rng.randint(0, 255, (6, 6, 3), np.uint8),
                             "png"))
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    with pytest.raises(ValueError, match="expects at most 2"):
      fast.parse_batch([example.SerializeToString()])

  def test_dynamic_hw_context_image_stays_native(self, lib):
    """Dynamic H/W single images keep the native fast path (review r2):
    only buffer-sizing dims (time, multi-image N) must be concrete."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "image": TensorSpec(shape=(None, None, 3), dtype=np.uint8,
                            name="img", data_format="png"),
    })
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    img = np.random.RandomState(0).randint(0, 255, (5, 7, 3), np.uint8)
    out = fast.parse_batch([codec.encode_example({"image": img}, spec)])
    np.testing.assert_array_equal(out["features/image"][0], img)

  def test_extra_single_image_values_raise(self, lib):
    """2 bytes values under a single-image spec must error loudly on the
    native path, matching the Python path's failure (review r2)."""
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.data import example_pb2
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "image": TensorSpec(shape=(6, 6, 3), dtype=np.uint8, name="img",
                            data_format="png"),
    })
    example = example_pb2.Example()
    rng = np.random.RandomState(0)
    for _ in range(2):
      example.features.feature["img"].bytes_list.value.append(
          codec.encode_image(rng.randint(0, 255, (6, 6, 3), np.uint8),
                             "png"))
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    with pytest.raises(ValueError, match="single image"):
      fast.parse_batch([example.SerializeToString()])

  def test_mixed_context_and_sequence_missing_raises(self, lib):
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "episode_id": TensorSpec(shape=(), dtype=np.int64,
                                 name="episode_id"),
        "poses": TensorSpec(shape=(4, 3), dtype=np.float32, name="poses",
                            is_sequence=True),
    })
    record = codec.encode_sequence_example(
        context={"episode_id": np.array(0, np.int64)}, sequences={},
        spec_structure=spec)
    parse_fn = parsing.create_parse_fn(spec)
    assert parse_fn._native_parsers[""] is not None
    with pytest.raises(ValueError, match="poses"):
      parse_fn.parse_batch([record])

  def test_sequence_parser_throughput(self, lib):
    """The native path must beat Python protobuf on episode records."""
    import time
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "obs": TensorSpec(shape=(40, 32), dtype=np.float32, name="obs",
                          is_sequence=True),
        "action": TensorSpec(shape=(40, 7), dtype=np.float32,
                             name="action", is_sequence=True),
    })
    rng = np.random.RandomState(0)
    records = [codec.encode_sequence_example(
        context={},
        sequences={"obs": rng.rand(40, 32).astype(np.float32),
                   "action": rng.rand(40, 7).astype(np.float32)},
        spec_structure=spec) for _ in range(128)]
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    slow = parsing.create_parse_fn(spec)
    slow._native_parsers[""] = None
    fast.parse_batch(records)  # warm
    t0 = time.perf_counter()
    for _ in range(3):
      fast.parse_batch(records)
    t_fast = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(3):
      slow.parse_batch(records)
    t_slow = time.perf_counter() - t0
    assert t_fast < t_slow, (t_fast, t_slow)

  def test_native_parser_throughput(self, lib):
    """Native columnar parse must beat the Python protobuf path."""
    import time
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    spec = SpecStruct({
        "obs": TensorSpec(shape=(128,), dtype=np.float32, name="obs"),
        "action": TensorSpec(shape=(8,), dtype=np.float32, name="action"),
        "step": TensorSpec(shape=(), dtype=np.int64, name="step"),
    })
    records = [codec.encode_example(
        {"obs": np.random.rand(128).astype(np.float32),
         "action": np.zeros(8, np.float32),
         "step": np.array(i, np.int64)}, None) for i in range(512)]

    fast = parsing.create_parse_fn(spec)
    slow = parsing.create_parse_fn(spec)
    slow._native_parsers[""] = None
    fast.parse_batch(records)  # warm

    t0 = time.perf_counter()
    for _ in range(5):
      fast.parse_batch(records)
    t_fast = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(5):
      slow.parse_batch(records)
    t_slow = time.perf_counter() - t0
    assert t_fast < t_slow, (t_fast, t_slow)


class TestNativeJpegDecode:

  def test_matches_pil_exactly(self, lib):
    if not hasattr(lib, "t2r_decode_jpeg_batch"):
      pytest.skip("built without libjpeg")
    from tensor2robot_tpu.data import codec

    rng = np.random.RandomState(0)
    imgs = [rng.randint(0, 255, (24, 16, 3), np.uint8) for _ in range(9)]
    datas = [codec.encode_image(im, "jpeg") for im in imgs]
    out = native.decode_jpeg_batch(datas, 24, 16, 3)
    assert out is not None and out.shape == (9, 24, 16, 3)
    for i, d in enumerate(datas):
      np.testing.assert_array_equal(out[i],
                                    codec.decode_image(d, channels=3))

  def test_grayscale(self, lib):
    if not hasattr(lib, "t2r_decode_jpeg_batch"):
      pytest.skip("built without libjpeg")
    from tensor2robot_tpu.data import codec

    img = np.random.RandomState(0).randint(0, 255, (8, 8, 1), np.uint8)
    data = codec.encode_image(img, "jpeg")
    out = native.decode_jpeg_batch([data], 8, 8, 1)
    assert out is not None and out.shape == (1, 8, 8, 1)
    np.testing.assert_array_equal(out[0],
                                  codec.decode_image(data, channels=1))

  def test_rejects_bad_inputs(self, lib):
    if not hasattr(lib, "t2r_decode_jpeg_batch"):
      pytest.skip("built without libjpeg")
    from tensor2robot_tpu.data import codec

    good = codec.encode_image(
        np.zeros((8, 8, 3), np.uint8), "jpeg")
    # corrupt payload -> whole batch falls back (None)
    assert native.decode_jpeg_batch([good, b"not a jpeg"], 8, 8, 3) is None
    # dimension mismatch -> None
    assert native.decode_jpeg_batch([good], 16, 16, 3) is None
    # empty payload -> None (caller's zeros fallback)
    assert native.decode_jpeg_batch([good, b""], 8, 8, 3) is None

  def test_parse_path_uses_native_and_matches_python(self, lib):
    if not hasattr(lib, "t2r_decode_jpeg_batch"):
      pytest.skip("built without libjpeg")
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    rng = np.random.RandomState(0)
    spec = SpecStruct({
        "image": TensorSpec(shape=(12, 12, 3), dtype=np.uint8,
                            name="img", data_format="jpeg"),
        "frames": TensorSpec(shape=(3, 12, 12, 3), dtype=np.uint8,
                             name="frames", data_format="jpeg",
                             is_sequence=True),
    })
    records = []
    for _ in range(4):
      frames = rng.randint(0, 255, (3, 12, 12, 3), np.uint8)
      records.append(codec.encode_sequence_example(
          context={"image": rng.randint(0, 255, (12, 12, 3), np.uint8)},
          sequences={"frames": frames}, spec_structure=spec))
    fast = parsing.create_parse_fn(spec)
    assert fast._native_parsers[""] is not None
    out_native = fast.parse_batch(records)
    # force the PIL path and compare
    import tensor2robot_tpu.data.parsing as parsing_mod
    original = parsing_mod._native_jpeg_batch
    parsing_mod._native_jpeg_batch = lambda *a, **k: None
    try:
      out_pil = fast.parse_batch(records)
    finally:
      parsing_mod._native_jpeg_batch = original
    for key in out_pil.keys():
      np.testing.assert_array_equal(np.asarray(out_native[key]),
                                    np.asarray(out_pil[key]),
                                    err_msg=key)

  def test_color_jpeg_with_grayscale_spec_falls_back_identically(self, lib):
    """A COLOR jpeg under a (H, W, 1) spec must not silently diverge
    from PIL's RGB->L conversion (review r2): the native path bails and
    the parse result equals the PIL path exactly."""
    if not hasattr(lib, "t2r_decode_jpeg_batch"):
      pytest.skip("built without libjpeg")
    from tensor2robot_tpu.data import codec, parsing
    from tensor2robot_tpu.specs import SpecStruct, TensorSpec

    rng = np.random.RandomState(0)
    color = codec.encode_image(rng.randint(0, 255, (16, 16, 3), np.uint8),
                               "jpeg")
    assert native.decode_jpeg_batch([color], 16, 16, 1) is None
    spec = SpecStruct({"image": TensorSpec(shape=(16, 16, 1),
                                           dtype=np.uint8, name="img",
                                           data_format="jpeg")})
    from tensor2robot_tpu.data import example_pb2
    example = example_pb2.Example()
    example.features.feature["img"].bytes_list.value.append(color)
    record = example.SerializeToString()
    out = parsing.create_parse_fn(spec).parse_batch([record])
    np.testing.assert_array_equal(
        out["features/image"][0], codec.decode_image(color, channels=1))


def test_library_is_rebuilt_when_a_source_changes(tmp_path, monkeypatch):
  """The library's name carries a content hash of its sources: an edit
  builds a new one (and drops the stale one), a touched mtime or a copy
  of the tree builds nothing it already has, and a source g++ refuses
  raises instead of falling back."""
  import os
  import shutil
  import subprocess

  if shutil.which("g++") is None:
    pytest.skip("no g++")

  def copied(path):
    return shutil.copy(path, tmp_path)

  monkeypatch.setattr(native, "_SOURCES",
                      [copied(p) for p in native._SOURCES])
  monkeypatch.setattr(native, "_JPEG_SOURCE", copied(native._JPEG_SOURCE))
  monkeypatch.setattr(native, "_HEADERS",
                      [copied(p) for p in native._HEADERS])
  monkeypatch.setattr(native, "_LIB", None)
  monkeypatch.setattr(native, "_LOAD_ERROR", None)
  builds = []
  real_run = subprocess.run

  def counting_run(cmd, *args, **kwargs):
    builds.append(cmd)
    return real_run(cmd, *args, **kwargs)

  monkeypatch.setattr(native.subprocess, "run", counting_run)
  libs = lambda: sorted(p.name for p in tmp_path.glob("*.so"))

  assert native.load() is not None
  (first,) = libs()
  assert len(builds) == 1
  # Same content, newer mtime: nothing to build.
  os.utime(native._SOURCES[0], None)
  native._LIB = None
  assert native.load() is not None
  assert libs() == [first] and len(builds) == 1
  # Changed content: a new library, the stale one gone.
  with open(native._SOURCES[0], "a") as f:
    f.write("\n// edited\n")
  native._LIB = None
  assert native.load() is not None
  (second,) = libs()
  assert second != first and len(builds) == 2
  assert native.masked_crc32c(b"123456789") is not None
  # A source the compiler refuses is an error, not a silent fallback.
  with open(native._SOURCES[0], "a") as f:
    f.write("\nthis is not C++\n")
  native._LIB = None
  with pytest.raises(native.NativeBuildError, match="g\\+\\+ could not"):
    native.load()
  with pytest.raises(native.NativeBuildError):
    native.require()
