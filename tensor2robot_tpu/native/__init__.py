"""Native (C++) runtime components, loaded via ctypes.

`tfrecord_io.cc` provides the fast host-side TFRecord reader and CRC32C
used by the data layer; `batch_stager.cc` the GIL-free batched record
staging plane (interleave + shuffle + batch assembly on worker threads);
`example_parser.cc` the columnar Example parser. The shared library is
built on first use with g++ from the committed sources into a gitignored
`libt2r_native-<hash>.so` beside them, where `<hash>` is a content hash
of those sources: a changed source builds a new library, and a copy of
the tree (which keeps no mtimes and no `.so`) builds its own. Where
there is no g++ every caller has a pure-Python fallback (`load()`
returns None, logged once); a g++ that REFUSES the committed sources is
a bug and raises. `require()` is for callers that must not fall back.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import shutil
import subprocess
import threading
from typing import Iterator, List, Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = [os.path.join(_DIR, "tfrecord_io.cc"),
            os.path.join(_DIR, "example_parser.cc"),
            os.path.join(_DIR, "batch_stager.cc")]
_JPEG_SOURCE = os.path.join(_DIR, "jpeg_decode.cc")
_HEADERS = [os.path.join(_DIR, "record_framing.h")]
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LOAD_ERROR: Optional[str] = None
_NO_TOOLCHAIN = "g++ not found"


class NativeBuildError(RuntimeError):
  """The native library could not be built or loaded."""


def _lib_path() -> str:
  """`<dir>/libt2r_native-<content hash of the sources>.so`."""
  digest = hashlib.sha256()
  for src in [*_SOURCES, _JPEG_SOURCE, *_HEADERS]:
    digest.update(os.path.basename(src).encode())
    with open(src, "rb") as f:
      digest.update(f.read())
  return os.path.join(os.path.dirname(_SOURCES[0]),
                      f"libt2r_native-{digest.hexdigest()[:16]}.so")


def _build(lib_path: str) -> Optional[str]:
  """Builds `lib_path`; returns None, or the compiler's complaint."""
  # Preferred build includes the libjpeg-backed batch decoder; if the
  # toolchain lacks jpeglib.h / -ljpeg, fall back to building without it
  # (the reader/parser/stager fast paths must not depend on libjpeg).
  # -lpthread in BOTH attempts: the stager spawns std::threads.
  # Built under a per-process name and renamed into place, so two
  # processes building at once (test workers) each publish a whole file.
  tmp = f"{lib_path}.tmp.{os.getpid()}"
  base = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17"]
  attempts = [
      base + [*_SOURCES, _JPEG_SOURCE, "-o", tmp, "-ljpeg", "-lpthread"],
      base + [*_SOURCES, "-o", tmp, "-lpthread"],
  ]
  error = None
  for cmd in attempts:
    try:
      subprocess.run(cmd, check=True, capture_output=True, timeout=180)
    except subprocess.CalledProcessError as e:
      error = e.stderr.decode(errors="replace")[-2000:]
      continue
    except (OSError, subprocess.TimeoutExpired) as e:
      error = f"{type(e).__name__}: {e}"
      continue
    os.replace(tmp, lib_path)
    for stale in glob.glob(os.path.join(os.path.dirname(lib_path),
                                        "libt2r_native*.so")):
      if stale != lib_path:
        try:
          os.unlink(stale)
        except OSError:
          pass
    return None
  return error


def load() -> Optional[ctypes.CDLL]:
  """Returns the native library, building it if its sources changed.
  None (logged once) where there is no g++; raises `NativeBuildError`
  when g++ refuses the committed sources or the result does not load."""
  global _LIB, _LOAD_ERROR
  with _LOCK:
    if _LIB is not None:
      return _LIB
    if _LOAD_ERROR == _NO_TOOLCHAIN:
      return None
    if _LOAD_ERROR is not None:
      raise NativeBuildError(_LOAD_ERROR)
    lib_path = _lib_path()
    if not os.path.isfile(lib_path):
      if shutil.which("g++") is None:
        from absl import logging

        _LOAD_ERROR = _NO_TOOLCHAIN
        logging.warning("native: no g++ on this machine; the data layer "
                        "runs its pure-Python paths.")
        return None
      error = _build(lib_path)
      if error is not None:
        _LOAD_ERROR = f"g++ could not build {lib_path}:\n{error}"
        raise NativeBuildError(_LOAD_ERROR)
    try:
      lib = ctypes.CDLL(lib_path)
    except OSError as e:
      _LOAD_ERROR = f"cannot load {lib_path}: {e}"
      raise NativeBuildError(_LOAD_ERROR) from e
    lib.t2r_crc32c.restype = ctypes.c_uint32
    lib.t2r_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.t2r_masked_crc32c.restype = ctypes.c_uint32
    lib.t2r_masked_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.t2r_reader_open.restype = ctypes.c_void_p
    lib.t2r_reader_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
    lib.t2r_reader_close.argtypes = [ctypes.c_void_p]
    lib.t2r_reader_next_batch.restype = ctypes.c_int64
    lib.t2r_reader_next_batch.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.t2r_reader_data.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.t2r_reader_data.argtypes = [ctypes.c_void_p]
    lib.t2r_reader_offsets.restype = ctypes.POINTER(ctypes.c_int64)
    lib.t2r_reader_offsets.argtypes = [ctypes.c_void_p]
    lib.t2r_reader_lengths.restype = ctypes.POINTER(ctypes.c_int64)
    lib.t2r_reader_lengths.argtypes = [ctypes.c_void_p]
    lib.t2r_reader_error.restype = ctypes.c_char_p
    lib.t2r_reader_error.argtypes = [ctypes.c_void_p]
    lib.t2r_parser_create.restype = ctypes.c_void_p
    lib.t2r_parser_create.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int]
    lib.t2r_parser_destroy.argtypes = [ctypes.c_void_p]
    lib.t2r_parser_error.restype = ctypes.c_char_p
    lib.t2r_parser_error.argtypes = [ctypes.c_void_p]
    lib.t2r_parser_bytes_ptrs.restype = ctypes.POINTER(ctypes.c_void_p)
    lib.t2r_parser_bytes_ptrs.argtypes = [ctypes.c_void_p]
    lib.t2r_parser_bytes_lens.restype = ctypes.POINTER(ctypes.c_int64)
    lib.t2r_parser_bytes_lens.argtypes = [ctypes.c_void_p]
    lib.t2r_parser_bytes_counts.restype = ctypes.POINTER(ctypes.c_int64)
    lib.t2r_parser_bytes_counts.argtypes = [ctypes.c_void_p]
    lib.t2r_parser_step_counts.restype = ctypes.POINTER(ctypes.c_int64)
    lib.t2r_parser_step_counts.argtypes = [ctypes.c_void_p]
    lib.t2r_parser_parse_batch.restype = ctypes.c_int
    lib.t2r_parser_parse_batch.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_uint8)]
    lib.t2r_parser_gather_plane.restype = ctypes.c_int
    lib.t2r_parser_gather_plane.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8)]
    lib.t2r_stager_open.restype = ctypes.c_void_p
    lib.t2r_stager_open.argtypes = [
        ctypes.POINTER(ctypes.c_char_p), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_uint64, ctypes.c_int64, ctypes.c_int,
        ctypes.c_int, ctypes.c_int64, ctypes.c_int64]
    lib.t2r_stager_next_batch.restype = ctypes.c_void_p
    lib.t2r_stager_next_batch.argtypes = [ctypes.c_void_p]
    lib.t2r_stager_error.restype = ctypes.c_char_p
    lib.t2r_stager_error.argtypes = [ctypes.c_void_p]
    lib.t2r_stager_queue_depth.restype = ctypes.c_int64
    lib.t2r_stager_queue_depth.argtypes = [ctypes.c_void_p]
    lib.t2r_stager_close.argtypes = [ctypes.c_void_p]
    lib.t2r_staged_count.restype = ctypes.c_int64
    lib.t2r_staged_count.argtypes = [ctypes.c_void_p]
    lib.t2r_staged_data.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.t2r_staged_data.argtypes = [ctypes.c_void_p]
    lib.t2r_staged_offsets.restype = ctypes.POINTER(ctypes.c_int64)
    lib.t2r_staged_offsets.argtypes = [ctypes.c_void_p]
    lib.t2r_staged_lengths.restype = ctypes.POINTER(ctypes.c_int64)
    lib.t2r_staged_lengths.argtypes = [ctypes.c_void_p]
    lib.t2r_staged_arena_bytes.restype = ctypes.c_int64
    lib.t2r_staged_arena_bytes.argtypes = [ctypes.c_void_p]
    lib.t2r_staged_free.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "t2r_decode_jpeg_batch"):  # libjpeg build variant
      lib.t2r_decode_jpeg_batch.restype = ctypes.c_int
      lib.t2r_decode_jpeg_batch.argtypes = [
          ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
          ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8),
          ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int]
    _LIB = lib
    return _LIB


def available() -> bool:
  return load() is not None


def require() -> ctypes.CDLL:
  """`load()` for callers with no fallback: raises where it gives None."""
  lib = load()
  if lib is None:
    raise NativeBuildError(_LOAD_ERROR or "native library unavailable")
  return lib


def masked_crc32c(data: bytes) -> Optional[int]:
  lib = load()
  if lib is None:
    return None
  return lib.t2r_masked_crc32c(data, len(data))


def iter_records_native(path: str, verify_crc: bool = False,
                        batch_records: int = 256) -> Iterator[bytes]:
  """Streams records via the native reader; raises IOError on corruption."""
  lib = load()
  if lib is None:
    raise RuntimeError("native library unavailable")
  handle = lib.t2r_reader_open(path.encode(), int(verify_crc))
  if not handle:
    raise IOError(f"Cannot open {path}")
  try:
    while True:
      n = lib.t2r_reader_next_batch(handle, batch_records)
      if n < 0:
        error = lib.t2r_reader_error(handle).decode()
        raise IOError(f"Corrupt TFRecord file {path}: {error}")
      if n == 0:
        return
      data = lib.t2r_reader_data(handle)
      offsets = lib.t2r_reader_offsets(handle)
      lengths = lib.t2r_reader_lengths(handle)
      for i in range(n):
        yield ctypes.string_at(
            ctypes.addressof(data.contents) + offsets[i], lengths[i])
  finally:
    lib.t2r_reader_close(handle)


def decode_jpeg_batch(datas, height: int, width: int, channels: int,
                      num_threads: int = 0):
  """GIL-free batched JPEG decode to a uint8 [N, H, W, C] array.

  Returns None when unavailable (no libjpeg build) or when ANY image in
  the batch fails to decode to exactly (height, width, channels) — the
  caller then takes the Python (PIL) path for the whole batch.
  """
  import numpy as np

  lib = load()
  if lib is None or not hasattr(lib, "t2r_decode_jpeg_batch"):
    return None
  datas = list(datas)
  n = len(datas)
  if n == 0:
    return np.zeros((0, height, width, channels), np.uint8)
  if any(not d for d in datas):
    return None  # empty payloads use the Python zeros fallback
  arr = (ctypes.c_char_p * n)(*datas)
  lens = (ctypes.c_int64 * n)(*[len(d) for d in datas])
  out = np.empty((n, height, width, channels), np.uint8)
  status = lib.t2r_decode_jpeg_batch(
      arr, lens, n, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
      height, width, channels, num_threads)
  return out if status == 0 else None


class RecordStager:
  """Low-level handle on the C++ batched record stager (one epoch).

  Staging (file interleave + reservoir shuffle + batch assembly) starts
  on background C++ threads at construction; `next_batch()` blocks until
  a batch is staged and returns `(arena, offsets, lengths)` numpy arrays
  (the arena is copied out of the native buffer in ONE memcpy and owned
  by Python), or None at end of stream. Corruption/IO failures raise
  IOError, matching both `iter_records` paths. `close()` (or `with`)
  stops and JOINS the worker threads — every thread owner joins what it
  started, and an abandoned stager would leak readers blocked on full
  queues.

  Telemetry (`data/stage_ms` etc.) lives one level up in
  `data/stager.py`; this class stays a thin ctypes seam.
  """

  def __init__(self, paths: List[str], batch_size: int,
               cycle_length: int = 4, shuffle_buffer: int = 0,
               seed: int = 0, drop_remainder: bool = True,
               verify_crc: bool = False, queue_depth: int = 2,
               max_chunk_bytes: int = 0):
    # max_chunk_bytes > 0 byte-bounds the C++ reader queues and flushes
    # batches early at that arena size — record-mode streaming only
    # (early flush breaks exact batch_size semantics); 0 = off.
    lib = load()
    if lib is None:
      raise RuntimeError("native library unavailable")
    if not paths:
      raise ValueError("RecordStager needs at least one file")
    self._lib = lib
    encoded = [p.encode() for p in paths]
    path_array = (ctypes.c_char_p * len(encoded))(*encoded)
    self._handle = lib.t2r_stager_open(
        path_array, len(encoded), cycle_length, shuffle_buffer,
        ctypes.c_uint64(seed & (2**64 - 1)), batch_size,
        int(drop_remainder), int(verify_crc), queue_depth,
        max_chunk_bytes)
    if not self._handle:
      raise ValueError("invalid stager configuration")

  def next_batch(self):
    """(arena uint8[bytes], offsets int64[n], lengths int64[n]) or None."""
    import numpy as np

    lib = self._lib
    if self._handle is None:
      return None
    batch = lib.t2r_stager_next_batch(self._handle)
    if not batch:
      error = lib.t2r_stager_error(self._handle).decode()
      if error:
        raise IOError(f"Corrupt TFRecord stream: {error}")
      return None
    try:
      n = lib.t2r_staged_count(batch)
      nbytes = lib.t2r_staged_arena_bytes(batch)
      arena = np.empty((nbytes,), np.uint8)
      if nbytes:
        ctypes.memmove(arena.ctypes.data, lib.t2r_staged_data(batch),
                       nbytes)
      offsets = np.ctypeslib.as_array(lib.t2r_staged_offsets(batch),
                                      (n,)).copy()
      lengths = np.ctypeslib.as_array(lib.t2r_staged_lengths(batch),
                                      (n,)).copy()
      return arena, offsets, lengths
    finally:
      lib.t2r_staged_free(batch)

  def queue_depth(self) -> int:
    """Staged batches waiting for the consumer (0 in steady state means
    Python consumes faster than the plane stages)."""
    if self._handle is None:
      return 0
    return int(self._lib.t2r_stager_queue_depth(self._handle))

  def close(self) -> None:
    if getattr(self, "_handle", None):
      self._lib.t2r_stager_close(self._handle)
      self._handle = None

  def __enter__(self) -> "RecordStager":
    return self

  def __exit__(self, *exc) -> None:
    self.close()

  def __del__(self):
    self.close()


KIND_FLOAT, KIND_INT64, KIND_BYTES = 0, 1, 2


class BatchExampleParser:
  """Columnar batched Example/SequenceExample parsing (native library).

  Plan: a list of (name, kind, size, missing_ok, seq_len, cap) tuples —
  `seq_len` 0 for context features or the fixed time dim for
  SequenceExample feature lists (short sequences zero-pad, long ones
  clip); `cap` is the stored value capacity for bytes features (1 for a
  single image, N for multi-image lists, == seq_len for image sequences).
  For context bytes, `size` > 0 declares a fixed-size raw plane: when
  every record carries exactly one value of that byte length, the batch
  is returned as ONE contiguous [batch, size] uint8 buffer filled by a
  single `t2r_parser_gather_plane` call straight from the parser's
  slices (the per-record bytes-object path would copy twice); otherwise
  the entry falls back to the per-record value lists.

  `parse` returns a dict:
    float/int: {plan index: np array [batch, size] or [batch, T, size]},
    bytes:     {plan index: per-record lists of bytes values, or None
                when bytes_planes took the entry},
    bytes_planes: {plan index: contiguous uint8 [batch, size] or None},
    bytes_counts / step_counts: {plan index: np.int64 [batch]}.
  """

  def __init__(self, plan):
    import numpy as np

    lib = load()
    if lib is None:
      raise RuntimeError("native library unavailable")
    self._lib = lib
    # The C++ Plan handle stores per-call results (bytes ptr/len
    # vectors), so concurrent parse() calls on one parser must serialize.
    self._parse_lock = threading.Lock()
    def _norm(entry):
      entry = tuple(entry)
      if len(entry) == 4:  # legacy (name, kind, size, missing_ok)
        entry += (0, 1)
      elif len(entry) == 5:
        entry += (1,)
      return entry

    self._plan = [_norm(entry) for entry in plan]
    n = len(self._plan)
    names = (ctypes.c_char_p * n)(
        *[e[0].encode() for e in self._plan])
    kinds = (ctypes.c_int * n)(*[e[1] for e in self._plan])
    sizes = (ctypes.c_int64 * n)(*[e[2] for e in self._plan])
    seq_lens = (ctypes.c_int64 * n)(*[e[4] for e in self._plan])
    caps = (ctypes.c_int64 * n)(
        *[max(1, e[5]) if e[1] == KIND_BYTES else 0 for e in self._plan])
    self._missing_ok = (ctypes.c_uint8 * n)(
        *[1 if e[3] else 0 for e in self._plan])
    self._caps = [max(1, e[5]) if e[1] == KIND_BYTES else 0
                  for e in self._plan]
    self._caps_offset = []
    total = 0
    for c in self._caps:
      self._caps_offset.append(total if c else -1)
      total += c
    self._total_caps = total
    self._num_bytes = sum(1 for c in self._caps if c)
    self._num_seq = sum(1 for e in self._plan if e[4] > 0)
    self._handle = lib.t2r_parser_create(names, kinds, sizes, seq_lens,
                                         caps, n)
    self._np = np

  def __del__(self):
    if getattr(self, "_handle", None) and self._lib is not None:
      self._lib.t2r_parser_destroy(self._handle)
      self._handle = None

  def parse(self, records):
    batch = len(records)
    rec_array = (ctypes.c_char_p * batch)(*records)
    len_array = (ctypes.c_int64 * batch)(*[len(r) for r in records])
    with self._parse_lock:
      return self._parse_ptrs(rec_array, len_array, batch)

  def parse_arena(self, arena, offsets, lengths):
    """Parses records living in one contiguous arena buffer.

    `arena` is a C-contiguous uint8 numpy array; `offsets`/`lengths` are
    per-record int64 arrays indexing into it (the `t2r_stager_*` batch
    layout, see `data/stager.py`). No per-record bytes objects are
    materialized — the parser reads straight out of the arena, so the
    whole records->parsed-batch path costs a handful of ctypes calls
    per BATCH. The arena must stay alive for the duration of the call
    (the returned per-record bytes values are copied out before
    return).
    """
    base = arena.ctypes.data
    batch = len(offsets)
    ptr_array = (ctypes.c_void_p * batch)(
        *[base + o for o in offsets.tolist()])
    rec_array = ctypes.cast(ptr_array, ctypes.POINTER(ctypes.c_char_p))
    len_array = (ctypes.c_int64 * batch)(*lengths.tolist())
    with self._parse_lock:
      return self._parse_ptrs(rec_array, len_array, batch)

  def _parse_ptrs(self, rec_array, len_array, batch):
    np = self._np
    n = len(self._plan)
    float_outs = (ctypes.c_void_p * n)()
    int_outs = (ctypes.c_void_p * n)()
    out = {"float": {}, "int": {}, "bytes": {}, "bytes_planes": {},
           "bytes_counts": {}, "step_counts": {}}
    for i, (name, kind, size, _, seq_len, _) in enumerate(self._plan):
      shape = (batch, seq_len, size) if seq_len > 0 else (batch, size)
      if kind == KIND_FLOAT:
        buf = np.zeros(shape, np.float32)
        out["float"][i] = buf
        float_outs[i] = buf.ctypes.data_as(ctypes.c_void_p)
      elif kind == KIND_INT64:
        buf = np.zeros(shape, np.int64)
        out["int"][i] = buf
        int_outs[i] = buf.ctypes.data_as(ctypes.c_void_p)
    status = self._lib.t2r_parser_parse_batch(
        self._handle, rec_array, len_array, batch, float_outs, int_outs,
        self._missing_ok)
    if status != 0:
      raise ValueError(
          "native example parse failed: "
          + self._lib.t2r_parser_error(self._handle).decode())
    if self._num_bytes:
      ptrs = self._lib.t2r_parser_bytes_ptrs(self._handle)
      lens = self._lib.t2r_parser_bytes_lens(self._handle)
      counts = self._lib.t2r_parser_bytes_counts(self._handle)
      slot = 0
      for i, (name, kind, size, _, seq_len, _) in enumerate(self._plan):
        if kind != KIND_BYTES:
          continue
        cap, offset = self._caps[i], self._caps_offset[i]
        if size > 0 and seq_len == 0:
          # Raw-plane single-copy path: when every record has exactly
          # one value of the declared byte length, t2r_parser_gather_
          # plane memcpys all planes into one contiguous buffer — the
          # pre-round-6 wrapper paid a Python frame + ctypes.memmove
          # per record here. A null-dest probe first, so a stream that
          # never qualifies (status 0 -> per-value path below) does
          # not allocate a dest per batch. Still under the lock,
          # before the next parse invalidates the slices.
          status = self._lib.t2r_parser_gather_plane(
              self._handle, i, batch, None)
          if status == 1:
            dest = np.empty((batch, size), np.uint8)
            status = self._lib.t2r_parser_gather_plane(
                self._handle, i, batch,
                dest.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
          if status == 1:
            out["bytes_planes"][i] = dest
            out["bytes"][i] = None
            out["bytes_counts"][i] = np.ones((batch,), np.int64)
            slot += 1
            continue
        per_record = []
        count_arr = np.zeros((batch,), np.int64)
        for r in range(batch):
          count = counts[r * self._num_bytes + slot]
          count_arr[r] = count
          # Sequence bytes expose all `cap` step slots (missing steps as
          # b"" -> zero images downstream); context bytes expose the
          # actual values present.
          num_values = cap if seq_len > 0 else min(count, cap)
          values = []
          for c in range(num_values):
            ptr = ptrs[r * self._total_caps + offset + c]
            length = lens[r * self._total_caps + offset + c]
            values.append(ctypes.string_at(ptr, length) if ptr else b"")
          per_record.append(values)
        out["bytes"][i] = per_record
        out["bytes_counts"][i] = count_arr
        slot += 1
    if self._num_seq:
      steps = self._lib.t2r_parser_step_counts(self._handle)
      seq_slot = 0
      for i, entry in enumerate(self._plan):
        if entry[4] <= 0:
          continue
        out["step_counts"][i] = np.asarray(
            [steps[r * self._num_seq + seq_slot] for r in range(batch)],
            np.int64)
        seq_slot += 1
    return out
