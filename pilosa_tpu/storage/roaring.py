"""64-bit roaring bitmap, numpy-backed, Pilosa file-format compatible.

Clean-room implementation of the storage-side bitmap. The reference keeps
three container encodings and 45 hand-specialized pairwise op kernels
(roaring/roaring.go:1273, 2162-3353) because containers are also its *compute*
representation. Here compute happens on TPU over dense bitvectors
(pilosa_tpu.ops), so the host bitmap only needs: mutation, bulk build,
dense-range materialization (the OffsetRange analog, roaring/roaring.go:320,
used by fragment row reads, fragment.go:361), set algebra for merges, and
serialization.

In-memory model: three container kinds, matching the reference's
(roaring/roaring.go:56-62) — a sorted uint16 numpy array (cardinality ≤ 4096,
ARRAY_MAX_SIZE as roaring/roaring.go:1258), a 1024-word uint64 little-endian
bitmap, or an [nruns, 2] (start, last) run-interval array. Encoding is
re-picked cheaply after mutation (array↔bitmap) and fully by `optimize()`
(the countRuns heuristic, roaring/roaring.go:1261, 1594), which is what
introduces runs; serialization writes whichever of the three is smallest,
which the format permits because container types are explicit in the
descriptive header (docs/architecture.md: "Container types are NOT
inferred").

File format (docs/architecture.md, roaring/roaring.go:812-985):
  bytes 0-1  magic 12348        (u16 LE)
  bytes 2-3  storage version 0  (u16 LE)
  bytes 4-7  container count    (u32 LE)
  per container: key u64 | container type u16 | cardinality-1 u16   (12 B)
  per container: absolute file offset u32                            (4 B)
  container payloads: array = n×u16; bitmap = 1024×u64;
                      run = count u16 then count×(start u16, last u16)
  trailing: op-log — 13-byte records [type u8 | value u64 | fnv1a32 u32]
  (roaring/roaring.go:3354-3420), replayed on open.
"""

from __future__ import annotations

import hashlib
import io
import os
import struct
import zlib
from typing import Iterator, Optional

import numpy as np

from pilosa_tpu.constants import (
    ARRAY_MAX_SIZE,
    CONTAINER_BITS,
    MAGIC_NUMBER,
    STORAGE_VERSION,
)
from pilosa_tpu.storage.containers import (
    make_container_store,
    resolve_store_kind,
)

BITMAP_WORDS = CONTAINER_BITS // 64  # 1024 x uint64
HEADER_BASE_SIZE = 8

TYPE_ARRAY = 1
TYPE_BITMAP = 2
TYPE_RUN = 3

OP_ADD = 0
OP_REMOVE = 1
OP_SIZE = 13

# CRC-framed WAL records (v1): legacy 13-byte records begin with the op
# type (0 or 1) and carry an fnv1a32 of the body; framed records carry a
# magic + version prefix and a zlib CRC32 over the whole body, so recovery
# can distinguish "torn tail" from "valid record" byte-exactly. Both forms
# parse; new appends are always framed.
OP_MAGIC = 0xFA  # never a legacy op type, never the snapshot-trailer magic
OP_VERSION = 1
FRAMED_OP_SIZE = 15  # magic u8 | version u8 | type u8 | value u64 | crc32 u32

# Snapshot integrity trailer, appended by write_snapshot() after the
# container section: magic | snapshot-section length u64 | blake2b-16
# digest of the section. The WAL appends AFTER the trailer; parse skips it
# once verified. Files without one (legacy, or network payloads written by
# write_to/to_bytes) parse unverified.
SNAP_TRAILER_MAGIC = b"PTS1"
SNAP_TRAILER_SIZE = 4 + 8 + 16


class CorruptionError(ValueError):
    """Snapshot-section integrity failure (trailer digest mismatch): the
    file's container data cannot be trusted. Distinct from a torn WAL tail,
    which recovery truncates — this is the quarantine signal."""


def frame_op(typ: int, value: int) -> bytes:
    """One CRC32-framed WAL record."""
    body = struct.pack("<BBBQ", OP_MAGIC, OP_VERSION, typ, value)
    return body + struct.pack("<I", zlib.crc32(body))


class _HashingWriter:
    """Pass-through writer computing a running blake2b-16 + byte count —
    how write_snapshot digests the stream without buffering it."""

    __slots__ = ("w", "h", "n")

    def __init__(self, w):
        self.w = w
        self.h = hashlib.blake2b(digest_size=16)
        self.n = 0

    def write(self, data) -> int:
        self.w.write(data)
        self.h.update(data)
        # nbytes, not len(): the frozen store streams memoryviews of
        # structured/uint16 arrays, where len() counts elements
        n = memoryview(data).nbytes
        self.n += n
        return n


def _valid_record_after(data, pos: int, n: int) -> bool:
    """True if any offset past `pos` parses as a checksum-valid op record
    — the discriminator between a torn TAIL (garbage to EOF; safe to
    truncate, nothing after it was acked) and mid-log bit-rot (intact
    acked records follow the damage; truncation would silently discard
    them). False-positive odds are one checksum collision in random
    garbage (~2^-32 per candidate byte), and the failure mode of a false
    positive is the conservative one (quarantine + replica rebuild)."""
    for off in range(pos + 1, n - FRAMED_OP_SIZE + 1):
        lead = data[off]
        if lead == OP_MAGIC:
            _m, ver, typ, _value, chk = struct.unpack_from("<BBBQI", data,
                                                           off)
            if ver == OP_VERSION and typ in (OP_ADD, OP_REMOVE) \
                    and chk == zlib.crc32(bytes(data[off:off + 11])):
                return True
        elif lead in (OP_ADD, OP_REMOVE) and off + OP_SIZE <= n:
            (chk,) = struct.unpack_from("<I", data, off + 9)
            if chk == fnv1a32(bytes(data[off:off + 9])):
                return True
    return False


def fnv1a32(data: bytes) -> int:
    h = 2166136261
    for b in data:
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


def _array_to_words(arr: np.ndarray) -> np.ndarray:
    from pilosa_tpu import native
    return native.array_to_bits(arr)  # numpy fallback lives in the wrapper


def _words_to_array(words: np.ndarray) -> np.ndarray:
    from pilosa_tpu import native
    return native.bits_to_array(words)


def _runs_to_words(iv: np.ndarray) -> np.ndarray:
    """[nruns, 2] (start, last) -> uint64[1024] dense words (native masked
    range-set kernel; numpy packbits fallback lives in native.run_to_bits)."""
    from pilosa_tpu import native
    return native.run_to_bits(iv)


def _runs_to_values(iv: np.ndarray) -> np.ndarray:
    """[nruns, 2] (start, last) -> sorted uint16 members."""
    if iv.shape[0] == 0:
        return np.empty(0, dtype=np.uint16)
    return np.concatenate([
        np.arange(s, last + 1, dtype=np.uint16)
        for s, last in iv.astype(np.int64)
    ])


def container_contains_many(c, lows: np.ndarray) -> np.ndarray:
    """Vectorized membership of uint16 `lows` in one container, by kind."""
    if c.kind == "array":
        idx = np.searchsorted(c.data, lows)
        idx_c = np.minimum(idx, c.data.size - 1)
        return (idx < c.data.size) & (c.data[idx_c] == lows)
    if c.kind == "run":
        i = np.searchsorted(c.data[:, 0], lows, side="right") - 1
        i_c = np.maximum(i, 0)
        return (i >= 0) & (lows <= c.data[i_c, 1])
    li = lows.astype(np.int64)
    w = c.data[li >> 6]
    return ((w >> (li.astype(np.uint64) & np.uint64(63)))
            & np.uint64(1)).astype(bool)


class Container:
    """One 2^16-bit container: sorted uint16 array, uint64[1024] bitmap, or
    [nruns, 2] (start, last) run intervals — all three in-memory, matching
    the reference (roaring/roaring.go:56-62): a fully-set time-view
    container costs 4 bytes as one run, not 8 KiB as a bitmap."""

    __slots__ = ("kind", "data")

    def __init__(self, kind: str, data: np.ndarray):
        self.kind = kind  # "array" | "bitmap" | "run"
        self.data = data

    # -- constructors -------------------------------------------------------

    @classmethod
    def empty(cls) -> "Container":
        return cls("array", np.empty(0, dtype=np.uint16))

    @classmethod
    def from_values(cls, values: np.ndarray) -> "Container":
        """values: sorted unique uint16."""
        values = np.asarray(values, dtype=np.uint16)
        if values.size > ARRAY_MAX_SIZE:
            return cls("bitmap", _array_to_words(values))
        return cls("array", values)

    # -- basics -------------------------------------------------------------

    @property
    def n(self) -> int:
        if self.kind == "array":
            return int(self.data.size)
        if self.kind == "run":
            iv = self.data.astype(np.int64)
            return int(np.sum(iv[:, 1] - iv[:, 0] + 1)) if iv.size else 0
        return int(np.sum(np.bitwise_count(self.data)))

    def values(self) -> np.ndarray:
        """Sorted uint16 members."""
        if self.kind == "array":
            return self.data
        if self.kind == "run":
            return _runs_to_values(self.data)
        return _words_to_array(self.data)

    def words(self) -> np.ndarray:
        """uint64[1024] little-endian dense form."""
        if self.kind == "bitmap":
            return self.data
        if self.kind == "run":
            return _runs_to_words(self.data)
        return _array_to_words(self.data)

    def contains(self, v: int) -> bool:
        if self.kind == "array":
            i = np.searchsorted(self.data, v)
            return bool(i < self.data.size and self.data[i] == v)
        if self.kind == "run":
            starts = self.data[:, 0]
            i = int(np.searchsorted(starts, v, side="right")) - 1
            return bool(i >= 0 and v <= int(self.data[i, 1]))
        return bool((int(self.data[v >> 6]) >> (v & 63)) & 1)

    def _normalize(self) -> "Container":
        """Re-pick array-vs-bitmap after mutation. Run selection is NOT done
        here (it needs a full interval scan): optimize() handles it at
        snapshot time, like the reference (roaring/roaring.go:1594)."""
        if self.kind == "run":
            return self
        if self.kind == "bitmap" and self.n <= ARRAY_MAX_SIZE:
            return Container("array", _words_to_array(self.data))
        if self.kind == "array" and self.data.size > ARRAY_MAX_SIZE:
            return Container("bitmap", _array_to_words(self.data))
        return self

    def optimize(self) -> "Container":
        """Pick the smallest of the three encodings (optimize()/countRuns
        heuristic, roaring/roaring.go:1594,1776-1950); called on snapshot."""
        runs = self._runs()
        n = self.n
        sizes = {
            "array": 2 * n,
            "bitmap": 8 * BITMAP_WORDS,
            "run": 2 + 4 * runs.shape[0],
        }
        best = min(sizes, key=lambda k: (sizes[k], k))
        if best == self.kind:
            return self
        if best == "run":
            return Container("run", runs)
        if best == "array":
            return Container("array", self.values())  # fresh: kind != array
        return Container("bitmap", self.words())

    # -- mutation (returns possibly re-encoded container) -------------------

    def add_many(self, vals: np.ndarray) -> "Container":
        vals = np.asarray(vals, dtype=np.uint16)
        if self.kind == "array":
            merged = np.union1d(self.data, vals)
            return Container.from_values(merged)
        # run: words() is already a fresh buffer; bitmap: copy before mutate
        words = self.data.copy() if self.kind == "bitmap" else self.words()
        idx = vals.astype(np.int64)
        np.bitwise_or.at(words, idx >> 6, np.uint64(1) << (idx & 63).astype(np.uint64))
        return Container("bitmap", words)._normalize()

    def remove_many(self, vals: np.ndarray) -> "Container":
        vals = np.asarray(vals, dtype=np.uint16)
        if self.kind == "array":
            keep = self.data[~np.isin(self.data, vals)]
            return Container("array", keep)
        words = self.data.copy() if self.kind == "bitmap" else self.words()
        idx = np.unique(vals).astype(np.int64)
        np.bitwise_and.at(words, idx >> 6, ~(np.uint64(1) << (idx & 63).astype(np.uint64)))
        return Container("bitmap", words)._normalize()

    # -- set algebra --------------------------------------------------------

    def op(self, other: "Container", kind: str) -> "Container":
        from pilosa_tpu import native
        if self.kind == "array" and other.kind == "array":
            out = native.array_op(self.data, other.data, kind)
            return Container.from_values(out)
        # run fast paths (intersect/union/difference/xor *Run kernels,
        # roaring.go:3549-3771): interval algebra instead of an 8 KiB
        # dense inflation; None = native lib unavailable -> dense fallback
        if self.kind == "run" and other.kind == "run":
            iv = native.run_op(self.data, other.data, kind)
            if iv is not None:
                if iv.shape[0] == 0:
                    return Container.empty()
                return Container("run", iv)
        if self.kind == "array" and other.kind == "run" \
                and kind in ("and", "andnot"):
            out = native.run_filter_array(other.data, self.data,
                                          keep_inside=(kind == "and"))
            if out is not None:
                return Container.from_values(out)
        if self.kind == "run" and other.kind == "array" and kind == "and":
            out = native.run_filter_array(self.data, other.data,
                                          keep_inside=True)
            if out is not None:
                return Container.from_values(out)
        aw, bw = self.words(), other.words()
        if kind == "and":
            out = aw & bw
        elif kind == "or":
            out = aw | bw
        elif kind == "andnot":
            out = aw & ~bw
        else:
            out = aw ^ bw
        return Container("bitmap", out)._normalize()

    def op_count(self, other: "Container", kind: str) -> int:
        from pilosa_tpu import native
        if self.kind == "array" and other.kind == "array" and kind == "and":
            return int(native.array_op(self.data, other.data, "and").size)
        # run fast paths (intersectionCount*Run kernels,
        # roaring.go:2162-2291): count without dense inflation
        if self.kind == "run" and other.kind == "run":
            n = native.run_op_count(self.data, other.data, kind)
            if n is not None:
                return n
        if kind == "and" and {self.kind, other.kind} == {"run", "bitmap"}:
            runs, words = ((self.data, other.data)
                           if self.kind == "run" else (other.data, self.data))
            n = native.run_and_count_bits(runs, words)
            if n is not None:
                return n
        if kind == "and" and {self.kind, other.kind} == {"run", "array"}:
            runs, vals = ((self.data, other.data)
                          if self.kind == "run" else (other.data, self.data))
            out = native.run_filter_array(runs, vals, keep_inside=True)
            if out is not None:
                return int(out.size)
        aw, bw = self.words(), other.words()
        if kind == "and":
            return native.and_count(aw, bw)
        if kind == "or":
            out = aw | bw
        elif kind == "andnot":
            out = aw & ~bw
        else:
            out = aw ^ bw
        return native.popcount64(out)

    # -- serialization ------------------------------------------------------

    def _runs(self) -> np.ndarray:
        """[nruns, 2] (start, last) intervals of the sorted member array."""
        if self.kind == "run":
            return self.data
        vals = self.values().astype(np.int64)
        if vals.size == 0:
            return np.empty((0, 2), dtype=np.uint16)
        breaks = np.flatnonzero(np.diff(vals) != 1)
        starts = np.concatenate(([0], breaks + 1))
        ends = np.concatenate((breaks, [vals.size - 1]))
        return np.stack([vals[starts], vals[ends]], axis=1).astype(np.uint16)

    def encode_current(self):
        """(type_code, payload_bytes) in the container's CURRENT encoding —
        no selection scan; callers that just ran optimize() use this."""
        if self.kind == "array":
            return TYPE_ARRAY, self.values().astype("<u2").tobytes()
        if self.kind == "run":
            runs = self.data
            return TYPE_RUN, struct.pack("<H", runs.shape[0]) + \
                runs.astype("<u2").tobytes()
        return TYPE_BITMAP, self.words().astype("<u8").tobytes()

    def best_encoding(self):
        """(type_code, payload_bytes) — smallest of array/bitmap/run. One
        selection scan shared with optimize()."""
        return self.optimize().encode_current()

    @classmethod
    def from_payload(cls, type_code: int, n: int, buf: memoryview) -> tuple["Container", int]:
        """Parse one container payload; returns (container, bytes consumed)."""
        def need(nbytes: int) -> None:
            if len(buf) < nbytes:
                raise ValueError(
                    f"container payload truncated: need {nbytes} bytes, have {len(buf)}"
                )

        if type_code == TYPE_ARRAY:
            need(2 * n)
            arr = np.frombuffer(buf[: 2 * n], dtype="<u2").astype(np.uint16)
            return cls("array", arr), 2 * n
        if type_code == TYPE_BITMAP:
            need(8 * BITMAP_WORDS)
            words = np.frombuffer(buf[: 8 * BITMAP_WORDS], dtype="<u8").copy()
            return cls("bitmap", words)._normalize(), 8 * BITMAP_WORDS
        if type_code == TYPE_RUN:
            need(2)
            (nruns,) = struct.unpack_from("<H", buf, 0)
            need(2 + 4 * nruns)
            # runs stay runs in memory (roaring/roaring.go:56-62) — a dense
            # time-view container is 4 bytes here, not 8 KiB inflated
            iv = np.frombuffer(buf[2 : 2 + 4 * nruns], dtype="<u2") \
                .reshape(nruns, 2).copy()
            return cls("run", iv), 2 + 4 * nruns
        raise ValueError(f"unknown container type {type_code}")


class LazyContainer:
    """A container whose payload still lives in the mmapped snapshot.

    The mmap storage lifecycle (fragment.go:190-247: mmap + MADV_RANDOM +
    zero-copy UnmarshalBinary) means holder open must be O(#containers
    metadata), not O(payload bytes): this handle records (type, cardinality,
    buffer window) from the descriptive header and parses the payload only
    on first data access. Cardinality reads (`n`) never materialize — full
    container-aligned row counts (rank-cache build, count_range) stay lazy.

    Mutation paths replace the entry with a real Container via the normal
    _store() flow; `best_encoding` passes the raw payload through untouched
    so snapshots of unread containers never parse them either.
    """

    __slots__ = ("code", "card", "buf", "offset", "size", "_real")

    def __init__(self, code: int, card: int, buf, offset: int, size: int):
        self.code = code
        self.card = card
        self.buf = buf
        self.offset = offset
        self.size = size
        self._real: Optional[Container] = None

    def _ensure(self) -> Container:
        if self._real is None:
            mv = memoryview(self.buf)[self.offset : self.offset + self.size]
            self._real, _ = Container.from_payload(self.code, self.card, mv)
        return self._real

    @property
    def materialized(self) -> bool:
        return self._real is not None

    @property
    def n(self) -> int:
        return self.card if self._real is None else self._real.n

    @property
    def kind(self) -> str:
        return self._ensure().kind

    @property
    def data(self) -> np.ndarray:
        return self._ensure().data

    def values(self) -> np.ndarray:
        return self._ensure().values()

    def words(self) -> np.ndarray:
        return self._ensure().words()

    def contains(self, v: int) -> bool:
        return self._ensure().contains(v)

    def _normalize(self):
        # snapshot encodings were normalized at write time; don't parse
        return self

    def _runs(self) -> np.ndarray:
        return self._ensure()._runs()

    def add_many(self, vals: np.ndarray) -> Container:
        return self._ensure().add_many(vals)

    def remove_many(self, vals: np.ndarray) -> Container:
        return self._ensure().remove_many(vals)

    def op(self, other, kind: str) -> Container:
        return self._ensure().op(other, kind)

    def op_count(self, other, kind: str) -> int:
        return self._ensure().op_count(other, kind)

    def best_encoding(self):
        if self._real is not None:
            return self._real.best_encoding()
        return self.code, bytes(
            memoryview(self.buf)[self.offset : self.offset + self.size])

    def encode_current(self):
        if self._real is not None:
            return self._real.encode_current()
        return self.code, bytes(
            memoryview(self.buf)[self.offset : self.offset + self.size])


def _payload_size(code: int, card: int, buf, offset: int) -> int:
    """Byte length of a container payload without parsing it."""
    if code == TYPE_ARRAY:
        return 2 * card
    if code == TYPE_BITMAP:
        return 8 * BITMAP_WORDS
    if code == TYPE_RUN:
        if offset + 2 > len(buf):
            raise ValueError("run container header out of bounds")
        (nruns,) = struct.unpack_from("<H", buf, offset)
        return 2 + 4 * nruns
    raise ValueError(f"unknown container type {code}")


class Bitmap:
    """64-bit roaring bitmap: {key = position >> 16} -> Container.

    Mirrors the reference Bitmap's public behavior (roaring/roaring.go:115)
    minus compute kernels. `op_writer` is the WAL hook: when set, single-value
    add/remove append 13-byte op records (the OpWriter field,
    roaring/roaring.go:119-122).
    """

    def __init__(self, values=None, store: Optional[str] = None):
        # pluggable container collection (the `Containers` abstraction,
        # roaring/roaring.go:67): "dict" (default, sliceContainers analog)
        # or "btree" (the enterprise/b B+Tree analog) — see
        # storage/containers.py. `store=None` defers to the
        # PILOSA_TPU_CONTAINER_STORE env (the build-tag selection analog).
        # The resolved kind is recorded so derived bitmaps (intersect/union/
        # slice results) inherit it.
        self.store_kind = resolve_store_kind(store)
        self.containers = make_container_store(self.store_kind)
        self.op_writer: Optional[io.RawIOBase] = None
        self.op_sync = False  # fsync after each op (fragment plumbs config)
        self.op_n = 0
        # WAL recovery report, set by from_bytes(recover_wal=True): the
        # absolute offset where valid op records end, and the parse error
        # (None = clean) — Fragment.open truncates the torn tail there
        self.wal_valid_end: Optional[int] = None
        self.wal_error: Optional[str] = None
        # set when a failed append could not be rewound off the log: the
        # file ends in garbage that recovery would truncate ALONG WITH any
        # record appended after it, so further appends must refuse rather
        # than ack doomed writes (cleared by snapshot, which rewrites)
        self.wal_poisoned = False
        if values is not None:
            self.add_many(np.asarray(values, dtype=np.uint64))

    # -- mutation -----------------------------------------------------------

    def _with_key(self, key: int) -> Container:
        c = self.containers.get(key)
        if c is None:
            c = Container.empty()
        return c

    def _store(self, key: int, c: Container) -> None:
        if c.n == 0:
            self.containers.pop(key, None)
        else:
            self.containers[key] = c

    def add_many(self, values: np.ndarray) -> None:
        """Bulk insert (no op-log; callers snapshot, as reference bulk paths)."""
        values = np.unique(np.asarray(values, dtype=np.uint64))
        if values.size == 0:
            return
        keys = (values >> np.uint64(16)).astype(np.int64)
        lows = (values & np.uint64(0xFFFF)).astype(np.uint16)
        boundaries = np.flatnonzero(np.diff(keys)) + 1
        for chunk_keys, chunk_lows in zip(
            np.split(keys, boundaries), np.split(lows, boundaries)
        ):
            key = int(chunk_keys[0])
            self._store(key, self._with_key(key).add_many(chunk_lows))

    def remove_many(self, values: np.ndarray) -> None:
        values = np.unique(np.asarray(values, dtype=np.uint64))
        keys = (values >> np.uint64(16)).astype(np.int64)
        lows = (values & np.uint64(0xFFFF)).astype(np.uint16)
        if values.size == 0:
            return
        boundaries = np.flatnonzero(np.diff(keys)) + 1
        for chunk_keys, chunk_lows in zip(
            np.split(keys, boundaries), np.split(lows, boundaries)
        ):
            key = int(chunk_keys[0])
            if key in self.containers:
                self._store(key, self.containers[key].remove_many(chunk_lows))

    def add(self, value: int) -> bool:
        """Single add; appends to the op-log when attached (DirectAdd +
        writeOp, roaring/roaring.go:154,977). Returns True if changed."""
        changed = not self.contains(value)
        if changed:
            # canonical int keys: numpy scalars hash like ints in the dict
            # store but would interleave as a distinct type in ordered stores
            key, low = int(value) >> 16, int(value) & 0xFFFF
            self._store(key, self._with_key(key).add_many(np.array([low], dtype=np.uint16)))
        self._write_op(OP_ADD, value)
        return changed

    def remove(self, value: int) -> bool:
        changed = self.contains(value)
        if changed:
            key, low = int(value) >> 16, int(value) & 0xFFFF
            self._store(key, self.containers[key].remove_many(np.array([low], dtype=np.uint16)))
        self._write_op(OP_REMOVE, value)
        return changed

    def _check_wal_clean(self) -> None:
        if self.wal_poisoned:
            raise OSError(
                "WAL poisoned by an earlier failed append (un-rewindable "
                "torn record); snapshot the fragment to restore durability")

    def _rewind_torn_write(self, n_written: int, torn: Exception) -> None:
        """A surviving process must not leave torn bytes mid-log: recovery
        truncates at the FIRST bad record, so any record acked after the
        garbage would be silently discarded at the next open. Rewind the
        file to the pre-write boundary (a crash between write and rewind
        leaves the torn tail — exactly what recovery truncates, with
        nothing acked after it). If even the rewind fails (dying disk),
        poison the WAL so no future append can be acked-but-doomed."""
        try:
            end = os.fstat(self.op_writer.fileno()).st_size
            os.ftruncate(self.op_writer.fileno(), end - n_written)
        except (OSError, ValueError):
            self.wal_poisoned = True
        raise torn

    def _write_op(self, typ: int, value: int) -> None:
        # poisoned check FIRST: a poisoned WAL may have op_writer=None
        # (failed re-attach after snapshot) and must refuse, not silently
        # ack writes that would never be logged
        self._check_wal_clean()
        if self.op_writer is None:
            return
        from pilosa_tpu.utils import failpoints
        rec, torn = failpoints.corrupt_write("storage.wal.append",
                                             frame_op(typ, value))
        self.op_writer.write(rec)
        if torn is not None:
            # the op was NOT acked: rewind the partial record off the log
            self._rewind_torn_write(len(rec), torn)
        if self.op_sync:
            os.fsync(self.op_writer.fileno())
        self.op_n += 1

    def append_ops(self, adds: np.ndarray, removes: np.ndarray) -> None:
        """WAL-append bulk deltas as individual op records in ONE write
        (writeOp, roaring/roaring.go:977) — the durability path for small
        anti-entropy adoptions, where the alternative is a full snapshot
        rewriting the whole fragment. Caller has already applied the
        mutations; these are redo records for replay."""
        self._check_wal_clean()  # before the None check — see _write_op
        if self.op_writer is None:
            return
        from pilosa_tpu.utils import failpoints
        parts = []
        for typ, vals in ((OP_ADD, adds), (OP_REMOVE, removes)):
            for v in np.asarray(vals, dtype=np.uint64).tolist():
                parts.append(frame_op(typ, int(v)))
        if not parts:
            return
        buf, torn = failpoints.corrupt_write("storage.wal.append",
                                             b"".join(parts))
        self.op_writer.write(buf)
        if torn is not None:
            # all-or-nothing: the whole delta is unacked, rewind it all
            self._rewind_torn_write(len(buf), torn)
        if self.op_sync:
            os.fsync(self.op_writer.fileno())
        self.op_n += len(parts)

    # -- queries ------------------------------------------------------------

    def contains_many(self, values: np.ndarray) -> np.ndarray:
        """Vectorized membership: bool mask per value, grouped by container
        (the batch analog of the per-container probe in contains())."""
        values = np.asarray(values, dtype=np.uint64)
        if getattr(self.containers, "VECTORIZED_STORE", False):
            # frozen store: segmented searchsorted over the flat arrays —
            # no per-key Python loop, no Container materialization
            return self.containers.contains_positions(values)
        out = np.zeros(values.size, dtype=bool)
        keys = (values >> np.uint64(16)).astype(np.int64)
        lows = (values & np.uint64(0xFFFF)).astype(np.uint16)
        for key in np.unique(keys):
            c = self.containers.get(int(key))
            if c is None or c.n == 0:
                continue
            m = keys == key
            out[m] = container_contains_many(c, lows[m])
        return out

    def positions(self) -> np.ndarray:
        """ALL set positions as one sorted uint64 array. Frozen stores
        answer from their flat arrays; dict/btree stores concatenate per
        container (slice with no bounds)."""
        if getattr(self.containers, "VECTORIZED_STORE", False):
            return self.containers.all_positions()
        return self.slice(0)

    def contains(self, value: int) -> bool:
        c = self.containers.get(value >> 16)
        return c is not None and c.contains(value & 0xFFFF)

    @classmethod
    def frozen(cls, positions: np.ndarray,
               presorted: bool = False) -> "Bitmap":
        """Bulk-load constructor for BASELINE-scale imports: the whole
        position set becomes a flat array-backed store (storage/frozen.py)
        in O(N log N) numpy — no per-container Python loop, no per-row
        object allocation. Mutations after the freeze go to a COW overlay.
        `presorted=True` skips the dedup sort for callers that construct
        sorted-unique positions themselves (the BSI plane import builds
        them from disjoint plane ranges — re-sorting a billion positions
        costs more than the store build)."""
        from pilosa_tpu.storage.frozen import FrozenContainers

        b = cls()  # store_kind stays the resolved default: DERIVED bitmaps
        # (intersect/union results) are ordinary mutable stores
        positions = np.asarray(positions, dtype=np.uint64)
        if not presorted:
            positions = np.unique(positions)
        b.containers = FrozenContainers.from_positions(positions)
        return b

    def count(self) -> int:
        if getattr(self.containers, "VECTORIZED_STORE", False):
            return self.containers.total_count()
        return sum(c.n for c in self.containers.values())

    def count_range(self, start: int, stop: int) -> int:
        total = 0
        for key in self._keys_in(start, stop):
            c = self.containers[key]
            base = key << 16
            lo, hi = max(start - base, 0), min(stop - base, CONTAINER_BITS)
            if lo <= 0 and hi >= CONTAINER_BITS:
                total += c.n
            else:
                v = c.values().astype(np.int64)
                total += int(np.count_nonzero((v >= lo) & (v < hi)))
        return total

    def slice(self, start: int = 0, stop: Optional[int] = None) -> np.ndarray:
        """All set positions in [start, stop) as uint64."""
        out = []
        stop = stop if stop is not None else (1 << 64)
        if stop <= start:
            return np.empty(0, dtype=np.uint64)
        # inclusive upper bound so stop == 2^64 doesn't overflow uint64 compare
        last = np.uint64(stop - 1)
        for key in self._keys_in(start, stop):
            c = self.containers[key]
            base = np.uint64(key << 16)
            vals = c.values().astype(np.uint64) + base
            out.append(vals[(vals >= np.uint64(start)) & (vals <= last)])
        if not out:
            return np.empty(0, dtype=np.uint64)
        return np.concatenate(out)

    def _keys_in(self, start: int, stop: int) -> list[int]:
        if stop <= start:
            return []
        lo, hi = start >> 16, (stop - 1) >> 16
        if hasattr(self.containers, "irange"):
            # ordered store: O(log n + k) range walk instead of full scan
            return list(self.containers.irange(lo, hi))
        if hi - lo < len(self.containers):
            # a narrow range (one row = 16 keys) in a wide store: probe
            # the range instead of scanning every key — per-row reads of a
            # 10k-row fragment were otherwise quadratic in its key count
            return [k for k in range(lo, hi + 1) if k in self.containers]
        return sorted(k for k in self.containers if lo <= k <= hi)

    def min(self) -> Optional[int]:
        if not self.containers:
            return None
        key = (self.containers.first_key()
               if hasattr(self.containers, "first_key")
               else min(self.containers))
        return (key << 16) | int(self.containers[key].values()[0])

    def max(self) -> Optional[int]:
        if not self.containers:
            return None
        key = (self.containers.last_key()
               if hasattr(self.containers, "last_key")
               else max(self.containers))
        return (key << 16) | int(self.containers[key].values()[-1])

    def any(self) -> bool:
        return bool(self.containers)

    def __iter__(self) -> Iterator[int]:
        for key in sorted(self.containers):
            base = key << 16
            for v in self.containers[key].values():
                yield base | int(v)

    # -- dense materialization (OffsetRange analog) -------------------------

    def to_dense_words(self, start: int, stop: int) -> np.ndarray:
        """Dense little-endian uint32 bitvector of positions [start, stop).

        start/stop must be container-aligned (multiples of 2^16) — true for
        row materialization since SHARD_WIDTH is container-aligned
        (fragment.go:361 OffsetRange usage).
        """
        if start % CONTAINER_BITS or stop % CONTAINER_BITS:
            raise ValueError("range must be container-aligned")
        n_words = (stop - start) // 32
        out = np.zeros(n_words, dtype=np.uint32)
        for key in range(start >> 16, stop >> 16):
            c = self.containers.get(key)
            if c is None:
                continue
            woff = ((key << 16) - start) // 32
            out[woff : woff + CONTAINER_BITS // 32] = c.words().view("<u4")
        return out

    @classmethod
    def from_dense_words(cls, words: np.ndarray, base: int = 0) -> "Bitmap":
        """Inverse of to_dense_words: build from a dense uint32 bitvector
        whose bit 0 is absolute position `base` (container-aligned)."""
        if base % CONTAINER_BITS:
            raise ValueError("base must be container-aligned")
        b = cls()
        words = np.ascontiguousarray(np.asarray(words, dtype=np.uint32))
        wpc = CONTAINER_BITS // 32
        for i in range(0, words.size, wpc):
            chunk = words[i : i + wpc]
            if not chunk.any():
                continue
            w64 = chunk.astype("<u4").tobytes()
            w64 = np.frombuffer(w64.ljust(8 * BITMAP_WORDS, b"\0"), dtype="<u8").copy()
            c = Container("bitmap", w64)._normalize()
            b._store((base >> 16) + i // wpc, c)
        return b

    # -- set algebra --------------------------------------------------------

    def _binary(self, other: "Bitmap", kind: str) -> "Bitmap":
        out = Bitmap(store=self.store_kind)
        if kind in ("and",):
            keys = set(self.containers) & set(other.containers)
        elif kind == "andnot":
            keys = set(self.containers)
        else:
            keys = set(self.containers) | set(other.containers)
        for key in keys:
            a = self.containers.get(key)
            b = other.containers.get(key)
            if a is None and b is None:
                continue
            if a is None:
                # aliases the other bitmap's container: copy
                res = Container(b.kind, b.data.copy()) if kind in ("or", "xor") else None
            elif b is None:
                res = Container(a.kind, a.data.copy()) if kind in ("or", "xor", "andnot") else None
            else:
                res = a.op(b, kind)  # freshly allocated
            if res is not None and res.n:
                out.containers[key] = res
        return out

    def intersect(self, other: "Bitmap") -> "Bitmap":
        return self._binary(other, "and")

    def union(self, other: "Bitmap") -> "Bitmap":
        return self._binary(other, "or")

    def difference(self, other: "Bitmap") -> "Bitmap":
        return self._binary(other, "andnot")

    def xor(self, other: "Bitmap") -> "Bitmap":
        return self._binary(other, "xor")

    def intersection_count(self, other: "Bitmap") -> int:
        total = 0
        for key in set(self.containers) & set(other.containers):
            total += self.containers[key].op_count(other.containers[key], "and")
        return total

    def union_in_place(self, *others: "Bitmap") -> None:
        """K-way bulk union into self (UnionInPlace, roaring/roaring.go:417-690).

        The reference walks all operands' container iterators key-by-key and
        picks a merge strategy from summary stats; here each key's operand
        containers are merged in one pass — word-wise OR when any operand is
        bitmap-encoded, sorted-value union otherwise — without materializing
        intermediate per-pair results (the import hot path)."""
        keys: set[int] = set()
        for o in others:
            keys.update(k for k, c in o.containers.items() if c.n)
        for key in keys:
            ops = [o.containers[key] for o in others
                   if key in o.containers and o.containers[key].n]
            mine = self.containers.get(key)
            if mine is not None and mine.n:
                ops.append(mine)
            if not ops:
                continue
            if len(ops) == 1:
                c = ops[0]
                self._store(key, Container(c.kind, c.data.copy()))
                continue
            if any(c.kind == "bitmap" for c in ops) or \
                    sum(c.n for c in ops) > ARRAY_MAX_SIZE:
                words = ops[0].words().copy()
                for c in ops[1:]:
                    np.bitwise_or(words, c.words(), out=words)
                self._store(key, Container("bitmap", words)._normalize())
            else:
                vals = np.unique(np.concatenate([c.values() for c in ops]))
                self._store(key, Container.from_values(vals))

    def repair(self) -> int:
        """Drop empty containers and re-pick stale encodings (Container.Repair
        + Containers.Repair, roaring/roaring.go:2093-2113,106; cardinality is
        derived here, so popcount drift cannot occur). Returns containers
        changed. Stores that own their serialization (frozen) skip the
        walk: their parse bounds-checked every container, base entries
        cannot be empty (cardinality = desc nm1 + 1 >= 1), and encodings
        re-pick lazily."""
        if getattr(self.containers, "VECTORIZED_STORE", False):
            return 0
        changed = 0
        for key in list(self.containers):
            c = self.containers[key]
            if c.n == 0:
                del self.containers[key]
                changed += 1
                continue
            fixed = c._normalize()
            if fixed is not c:
                self.containers[key] = fixed
                changed += 1
        return changed

    # -- serialization ------------------------------------------------------

    def write_to(self, w, optimized: bool = False) -> int:
        """Serialize in Pilosa roaring format (no op-log section — a fresh
        snapshot has an empty WAL, fragment.go:1737).

        optimized=True skips per-container encoding selection (serialize
        each container's current kind) — for callers that just ran
        optimize(), avoiding a second selection scan per snapshot."""
        if getattr(self.containers, "VECTORIZED_STORE", False):
            # vectorized store-owned path: metadata as structured arrays,
            # array payloads streamed as contiguous buffer views (a
            # billion-container store must never marshal per container)
            return self.containers.write_pilosa(w)
        keys = sorted(k for k, c in self.containers.items() if c.n > 0)
        encs = []
        for k in keys:
            c = self.containers[k]
            code, payload = c.encode_current() if optimized \
                else c.best_encoding()
            encs.append((k, code, c.n, payload))
        header = struct.pack("<HHI", MAGIC_NUMBER, STORAGE_VERSION, len(keys))
        desc = b"".join(struct.pack("<QHH", k, code, n - 1) for k, code, n, _ in encs)
        offset = HEADER_BASE_SIZE + len(keys) * 12 + len(keys) * 4
        offsets = []
        for _, _, _, payload in encs:
            offsets.append(struct.pack("<I", offset))
            offset += len(payload)
        data = header + desc + b"".join(offsets) + b"".join(p for *_, p in encs)
        w.write(data)
        return len(data)

    def to_bytes(self) -> bytes:
        buf = io.BytesIO()
        self.write_to(buf)
        return buf.getvalue()

    def write_snapshot(self, w, optimized: bool = False) -> int:
        """write_to + the blake2b integrity trailer — the durable-file
        variant (Fragment snapshots and fresh-file seeds). Network payloads
        and non-authoritative writes keep using write_to: the trailer is a
        property of files that a crash or bit-rot can damage in place."""
        hw = _HashingWriter(w)
        self.write_to(hw, optimized=optimized)
        w.write(SNAP_TRAILER_MAGIC + struct.pack("<Q", hw.n)
                + hw.h.digest())
        return hw.n + SNAP_TRAILER_SIZE

    @classmethod
    def from_bytes(cls, data, lazy: bool = False,
                   recover_wal: bool = False,
                   verify: bool = True) -> "Bitmap":
        """Parse either Pilosa format (magic 12348, + trailing op-log replay,
        roaring/roaring.go:886-975) or the official RoaringFormatSpec
        (cookies 12346/12347, roaring/roaring.go:3825-3985).

        lazy=True (Pilosa format only — `data` should be an mmap) defers
        container payload parsing to first access via LazyContainer: the
        zero-copy UnmarshalBinary analog (fragment.go:224).

        recover_wal=True (fragment open path): a torn/corrupt op-log TAIL
        stops replay at the last valid record instead of raising — the
        caller truncates the file there (wal_error / wal_valid_end record
        what happened). Snapshot-section damage (a failed trailer digest)
        still raises CorruptionError: that file needs quarantine, not a
        trim. verify=False skips the trailer digest computation (callers
        that just wrote the file themselves); structural trailer checks
        still apply."""
        if len(data) < HEADER_BASE_SIZE:
            raise ValueError("data too small")
        (magic,) = struct.unpack_from("<H", data, 0)
        if magic != MAGIC_NUMBER:
            return cls._from_official_bytes(
                data if isinstance(data, bytes) else bytes(data))
        _, version, key_n = struct.unpack_from("<HHI", data, 0)
        if version != STORAGE_VERSION:
            raise ValueError(f"wrong roaring version, file is v{version}")
        b = cls()
        mv = memoryview(data)
        desc_off = HEADER_BASE_SIZE
        off_off = desc_off + key_n * 12
        ops_offset = off_off + key_n * 4
        if ops_offset > len(data):
            raise ValueError(
                f"header overruns buffer: {key_n} containers need {ops_offset} bytes, have {len(data)}"
            )
        from pilosa_tpu.storage.frozen import (
            FROZEN_PARSE_MIN,
            parse_pilosa_frozen,
        )

        if lazy and key_n >= FROZEN_PARSE_MIN:
            # billion-container files: vectorized parse into the frozen
            # store (zero-copy array payload views over the mmap) — the
            # per-container loop below is interpreter-bound at this scale
            b.containers, ops_offset = parse_pilosa_frozen(
                data, key_n, desc_off, off_off)
            return cls._replay_ops(b, data, ops_offset, recover=recover_wal,
                                   verify=verify)
        for i in range(key_n):
            key, code, n_minus_1 = struct.unpack_from("<QHH", data, desc_off + i * 12)
            (offset,) = struct.unpack_from("<I", data, off_off + i * 4)
            if offset >= len(data):
                raise ValueError(f"offset out of bounds: off={offset}, len={len(data)}")
            if lazy:
                size = _payload_size(code, n_minus_1 + 1, data, offset)
                if offset + size > len(data):
                    raise ValueError(
                        f"container payload out of bounds: off={offset}, "
                        f"size={size}, len={len(data)}")
                b._store(int(key),
                         LazyContainer(code, n_minus_1 + 1, data, offset, size))
                consumed = size
            else:
                c, consumed = Container.from_payload(code, n_minus_1 + 1, mv[offset:])
                b._store(int(key), c)
            ops_offset = offset + consumed
        return cls._replay_ops(b, data, ops_offset, recover=recover_wal,
                               verify=verify)

    @staticmethod
    def flat_store_from_bytes(data):
        """A Pilosa-format payload as the flat array-backed store
        (storage/frozen.py), parsed with numpy: header and array payloads
        as views of `data`, bitmap and run containers one by one into the
        store's overlay. None where this parser is not the one to use —
        another format, an empty bitmap, bytes after the containers (an op
        log to replay), anything malformed: the caller then takes the
        container path, which also says what is wrong. What a bulk import
        into an empty fragment is parsed by (Fragment.import_roaring): a
        field of 10,000 rows is 150,000 array containers of six or seven
        values a shard, and one Python object each was 9.6 s a shard."""
        from pilosa_tpu.storage.frozen import parse_pilosa_frozen

        if len(data) < HEADER_BASE_SIZE:
            return None
        magic, version, key_n = struct.unpack_from("<HHI", data, 0)
        off_off = HEADER_BASE_SIZE + key_n * 12
        if (magic != MAGIC_NUMBER or version != STORAGE_VERSION
                or key_n == 0 or off_off + key_n * 4 > len(data)):
            return None
        try:
            store, end = parse_pilosa_frozen(data, key_n, HEADER_BASE_SIZE,
                                             off_off)
        except ValueError:
            return None
        return store if end == len(data) else None

    @classmethod
    def _verify_trailer(cls, data, ops_offset: int,
                        verify: bool = True) -> int:
        """Detect + verify the snapshot trailer at ops_offset; returns the
        offset where op records actually start (past the trailer, or
        ops_offset unchanged for trailer-less data). Raises CorruptionError
        on a digest/length mismatch — the quarantine signal. verify=False
        skips the digest (still parses + length-checks the trailer)."""
        n = len(data)
        if n - ops_offset < SNAP_TRAILER_SIZE \
                or bytes(data[ops_offset:ops_offset + 4]) != SNAP_TRAILER_MAGIC:
            return ops_offset
        (body_len,) = struct.unpack_from("<Q", data, ops_offset + 4)
        digest = bytes(data[ops_offset + 12:ops_offset + 28])
        if body_len != ops_offset:
            raise CorruptionError(
                f"snapshot trailer length mismatch: trailer says {body_len} "
                f"bytes, container section is {ops_offset}")
        if verify:
            actual = hashlib.blake2b(memoryview(data)[:ops_offset],
                                     digest_size=16).digest()
            if actual != digest:
                raise CorruptionError(
                    "snapshot integrity check failed: blake2b digest "
                    f"mismatch over {ops_offset} bytes")
        return ops_offset + SNAP_TRAILER_SIZE

    @classmethod
    def _replay_ops(cls, b: "Bitmap", data, ops_offset: int,
                    recover: bool = False, verify: bool = True) -> "Bitmap":
        """Trailing op-log replay: skip/verify the snapshot trailer, then
        parse framed (CRC32) and legacy (fnv1a32) records in sequence —
        mixed logs happen when an old file gains framed appends after an
        upgrade. Batched native parse still serves fully-legacy logs.

        recover=True: a torn/corrupt record STOPS replay — b.wal_error and
        b.wal_valid_end record the damage for the caller to truncate.
        Truncation is only safe for a genuine TAIL tear (nothing acked
        follows a crash's partial write); if intact, checksum-valid
        records exist AFTER the damage, the corruption is mid-log bit-rot
        and those records are acked data — that raises CorruptionError so
        the caller quarantines and rebuilds from a replica instead of
        silently discarding them. recover=False (network payloads): raise,
        as before."""
        ops_offset = cls._verify_trailer(data, ops_offset, verify=verify)
        n = len(data)
        pos = ops_offset
        if pos < n and data[pos] in (OP_ADD, OP_REMOVE):
            from pilosa_tpu import native
            parsed = native.oplog_parse(bytes(data[pos:]))
            if parsed is not None:
                types, values = parsed
                cls._apply_op_runs(b, types, values)
                b.op_n += int(types.size)
                b.wal_valid_end = n
                return b
        ops_t: list[int] = []
        ops_v: list[int] = []
        err = None
        while pos < n:
            lead = data[pos]
            if lead == OP_MAGIC:
                if pos + FRAMED_OP_SIZE > n:
                    err = f"op data out of bounds: len={n - pos}"
                    break
                _magic, ver, typ, value, chk = struct.unpack_from(
                    "<BBBQI", data, pos)
                if ver != OP_VERSION:
                    err = f"unknown op record version: {ver}"
                    break
                if chk != zlib.crc32(bytes(data[pos:pos + 11])):
                    err = "checksum mismatch"
                    break
                if typ not in (OP_ADD, OP_REMOVE):
                    err = f"invalid op type: {typ}"
                    break
                size = FRAMED_OP_SIZE
            elif lead in (OP_ADD, OP_REMOVE):
                if pos + OP_SIZE > n:
                    err = f"op data out of bounds: len={n - pos}"
                    break
                body = data[pos:pos + 9]
                (chk,) = struct.unpack_from("<I", data, pos + 9)
                if chk != fnv1a32(body):
                    err = "checksum mismatch"
                    break
                typ, value = struct.unpack("<BQ", body)
                size = OP_SIZE
            else:
                err = f"invalid op type: {lead}"
                break
            ops_t.append(typ)
            ops_v.append(value)
            pos += size
        if err is not None and not recover:
            raise ValueError(err)
        if err is not None and _valid_record_after(data, pos, n):
            raise CorruptionError(
                f"op log corrupt mid-stream at offset {pos} ({err}) with "
                "valid records after the damage — acked data would be "
                "lost by truncation; quarantining for replica rebuild")
        if ops_t:
            cls._apply_op_runs(b, np.asarray(ops_t, dtype=np.uint8),
                               np.asarray(ops_v, dtype=np.uint64))
            b.op_n += len(ops_t)
        b.wal_valid_end = pos
        b.wal_error = err
        return b

    @staticmethod
    def _apply_op_runs(b: "Bitmap", types: np.ndarray,
                       values: np.ndarray) -> None:
        """Apply an op sequence via the bulk paths, preserving order
        (consecutive same-type runs collapse into one add_many/remove_many)."""
        if types.size == 0:
            return
        bounds = np.flatnonzero(np.diff(types)) + 1
        for t_run, v_run in zip(np.split(types, bounds),
                                np.split(values, bounds)):
            if t_run[0] == OP_ADD:
                b.add_many(v_run)
            else:
                b.remove_many(v_run)

    # Official RoaringFormatSpec cookies (readOfficialHeader,
    # roaring/roaring.go:3825): 12347 = with runs, 12346 = without.
    _SERIAL_COOKIE = 12347
    _SERIAL_COOKIE_NO_RUN = 12346

    @classmethod
    def _from_official_bytes(cls, data: bytes) -> "Bitmap":
        """Official 32-bit RoaringFormatSpec reader. Note the official run
        encoding is (start, length), unlike Pilosa's (start, last)."""
        if len(data) < 8:
            raise ValueError("buffer too small")
        (cookie32,) = struct.unpack_from("<I", data, 0)
        pos = 4
        run_flags = None
        if cookie32 == cls._SERIAL_COOKIE_NO_RUN:
            (size,) = struct.unpack_from("<I", data, pos)
            pos += 4
        elif cookie32 & 0xFFFF == cls._SERIAL_COOKIE:
            size = (cookie32 >> 16) + 1
            nbytes = (size + 7) // 8
            run_flags = data[pos : pos + nbytes]
            pos += nbytes
        else:
            raise ValueError("did not find expected serialCookie in header")
        if size > (1 << 16):
            raise ValueError("more than 2^16 containers is impossible")
        keys, cards, kinds = [], [], []
        for i in range(size):
            key, card_m1 = struct.unpack_from("<HH", data, pos + 4 * i)
            keys.append(key)
            cards.append(card_m1 + 1)
            is_run = run_flags is not None and (run_flags[i // 8] >> (i % 8)) & 1
            kinds.append(TYPE_RUN if is_run else (TYPE_ARRAY if card_m1 + 1 <= ARRAY_MAX_SIZE else TYPE_BITMAP))
        pos += 4 * size
        b = cls()
        mv = memoryview(data)
        if run_flags is None:
            # offset section always present
            offsets = [struct.unpack_from("<I", data, pos + 4 * i)[0] for i in range(size)]
            for key, card, kind, off in zip(keys, cards, kinds, offsets):
                if off >= len(data):
                    raise ValueError(f"offset out of bounds: off={off}")
                c, _ = Container.from_payload(kind, card, mv[off:])
                b._store(key, c)
        else:
            # Spec: with the run cookie, an offset header is still present when
            # size >= NO_OFFSET_THRESHOLD (4). (The reference's readWithRuns
            # omits this and would misparse such files; we follow the spec.)
            if size >= 4:
                pos += 4 * size
            # sequential payloads, runs as (start, length)
            for i, (key, card, kind) in enumerate(zip(keys, cards, kinds)):
                if kind == TYPE_RUN:
                    (nruns,) = struct.unpack_from("<H", data, pos)
                    iv = np.frombuffer(mv[pos + 2 : pos + 2 + 4 * nruns], dtype="<u2").reshape(nruns, 2).astype(np.int64)
                    # official runs are (start, length); ours are (start, last)
                    runs = np.stack([iv[:, 0], iv[:, 0] + iv[:, 1]],
                                    axis=1).astype(np.uint16)
                    b._store(key, Container("run", runs))
                    pos += 2 + 4 * nruns
                else:
                    c, consumed = Container.from_payload(kind, card, mv[pos:])
                    b._store(key, c)
                    pos += consumed
        return b

    def optimize(self) -> int:
        """Re-pick every container's encoding, introducing run containers
        where smallest (Bitmap.Optimize, roaring/roaring.go:1594); called at
        snapshot time. Returns containers re-encoded. Unmaterialized lazy
        containers keep their on-disk encoding (already optimized at write).
        Stores that own their serialization (frozen) skip: the serializer
        picks encodings itself, and a per-container walk defeats the
        billion-container design."""
        if getattr(self.containers, "VECTORIZED_STORE", False):
            return 0
        changed = 0
        for key in list(self.containers):
            c = self.containers[key]
            if isinstance(c, LazyContainer):
                if not c.materialized:
                    continue
                c = c._real
            best = c.optimize()
            if best is not c:
                self.containers[key] = best
                changed += 1
        return changed

    def check(self) -> None:
        """Consistency check (Bitmap.Check, roaring/roaring.go:1015)."""
        for key, c in self.containers.items():
            if c.n == 0:
                raise ValueError(f"empty container at key {key}")
            if c.kind == "array":
                if c.data.size and not np.all(np.diff(c.data.astype(np.int64)) > 0):
                    raise ValueError(f"unsorted/duplicate array container at key {key}")
            elif c.kind == "run":
                iv = c.data.astype(np.int64)
                if iv.size:
                    if not np.all(iv[:, 1] >= iv[:, 0]):
                        raise ValueError(f"inverted run in container at key {key}")
                    if not np.all(iv[1:, 0] > iv[:-1, 1] + 1):
                        raise ValueError(
                            f"unsorted/overlapping/adjacent runs at key {key}")
