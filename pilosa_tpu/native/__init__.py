"""Native runtime: C++ storage kernels loaded via ctypes.

Builds the shared library from roaring_native.cc on first import (g++ -O3
-march=native), with a pure-numpy fallback when no compiler is present —
a failed build or load is logged once, loudly, never swallowed. The
library's file name carries a key over the source, the compile command and
this host's CPU flags, so a checkout copied from another machine (the
library is git-ignored but rides along in a directory copy) never loads
that machine's -march=native code: a different CPU means a different name
and a fresh build. Use `available()` to check, `lib()` for the raw handle;
the typed wrappers below are what storage code calls.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import tempfile
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(__file__)
_SRC = os.path.join(_HERE, "roaring_native.cc")
_CXX = ["g++", "-O3", "-march=native", "-shared", "-fPIC", "-std=c++17"]

_log = logging.getLogger(__name__)
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _cpu_flags() -> str:
    """What -march=native keys on: the machine type plus the first CPU's
    feature flags (/proc/cpuinfo; empty where the file is absent)."""
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    return f"{platform.machine()} {flags}"


def _so_path() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_CXX).encode())
    h.update(_cpu_flags().encode())
    return os.path.join(_HERE,
                        f"libroaring_native-{h.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    """Compile to a temporary name and rename into place: two processes
    importing at once (a subprocess cluster's nodes) each build their own
    file, and whichever renames last wins with a complete library."""
    fd, tmp = tempfile.mkstemp(prefix=".build-", suffix=".so", dir=_HERE)
    os.close(fd)
    try:
        subprocess.run(_CXX + ["-o", tmp, _SRC], check=True,
                       capture_output=True, timeout=120)
        os.chmod(tmp, 0o755)  # mkstemp's 0600 would survive the rename
        os.replace(tmp, so)
        return True
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", b"") or b""
        _log.warning(
            "native storage library NOT built (%s: %s) — storage runs on "
            "the slower numpy path\n%s", type(e).__name__, e,
            detail.decode(errors="replace")[-2000:])
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def lib() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            so = _so_path()
        except OSError as e:
            _log.warning("native storage library source unreadable (%s) — "
                         "storage runs on the slower numpy path", e)
            _build_failed = True
            return None
        if not os.path.exists(so) and not _build(so):
            _build_failed = True
            return None
        try:
            handle = ctypes.CDLL(so)
        except OSError as e:
            _log.warning("native storage library %s failed to load (%s) — "
                         "storage runs on the slower numpy path", so, e)
            _build_failed = True
            return None
        _configure(handle)
        _lib = handle
    return _lib


def _configure(h: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u16p = ctypes.POINTER(ctypes.c_uint16)
    u32p = ctypes.POINTER(ctypes.c_uint32)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    szp = ctypes.POINTER(ctypes.c_size_t)
    h.pt_fnv1a32.restype = ctypes.c_uint32
    h.pt_fnv1a32.argtypes = [u8p, ctypes.c_size_t]
    h.pt_fnv64a.restype = ctypes.c_uint64
    h.pt_fnv64a.argtypes = [u8p, ctypes.c_size_t]
    h.pt_popcount64.restype = ctypes.c_uint64
    h.pt_popcount64.argtypes = [u64p, ctypes.c_size_t]
    h.pt_and_count.restype = ctypes.c_uint64
    h.pt_and_count.argtypes = [u64p, u64p, ctypes.c_size_t]
    for name in ("pt_array_intersect", "pt_array_union",
                 "pt_array_difference", "pt_array_xor"):
        fn = getattr(h, name)
        fn.restype = ctypes.c_size_t
        fn.argtypes = [u16p, ctypes.c_size_t, u16p, ctypes.c_size_t, u16p]
    h.pt_bitmap_op.restype = None
    h.pt_bitmap_op.argtypes = [u64p, u64p, u64p, ctypes.c_size_t, ctypes.c_int]
    h.pt_array_to_bits.restype = None
    h.pt_array_to_bits.argtypes = [u16p, ctypes.c_size_t, u64p]
    h.pt_bits_to_array.restype = ctypes.c_size_t
    h.pt_bits_to_array.argtypes = [u64p, u16p]
    h.pt_positions_to_dense.restype = None
    h.pt_positions_to_dense.argtypes = [u64p, ctypes.c_size_t, ctypes.c_uint64,
                                        ctypes.c_uint64, u32p]
    h.pt_oplog_parse.restype = ctypes.c_size_t
    h.pt_oplog_parse.argtypes = [u8p, ctypes.c_size_t, u8p, u64p]
    h.pt_run_op.restype = ctypes.c_size_t
    h.pt_run_op.argtypes = [u16p, ctypes.c_size_t, u16p, ctypes.c_size_t,
                            u16p, ctypes.c_int]
    h.pt_run_op_count.restype = ctypes.c_uint64
    h.pt_run_op_count.argtypes = [u16p, ctypes.c_size_t, u16p,
                                  ctypes.c_size_t, ctypes.c_int]
    h.pt_run_filter_array.restype = ctypes.c_size_t
    h.pt_run_filter_array.argtypes = [u16p, ctypes.c_size_t, u16p,
                                      ctypes.c_size_t, u16p, ctypes.c_int]
    h.pt_run_and_count_bits.restype = ctypes.c_uint64
    h.pt_run_and_count_bits.argtypes = [u16p, ctypes.c_size_t, u64p]
    h.pt_run_to_bits.restype = None
    h.pt_run_to_bits.argtypes = [u16p, ctypes.c_size_t, u64p]


def available() -> bool:
    return lib() is not None


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


# ------------------------------------------------------------- wrappers


def fnv1a32(data: bytes) -> int:
    h = lib()
    if h is None:
        from pilosa_tpu.storage.roaring import fnv1a32 as py
        return py(data)
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data) if data else (ctypes.c_uint8 * 1)()
    return int(h.pt_fnv1a32(buf, len(data)))


def fnv64a(data: bytes) -> int:
    h = lib()
    if h is None:
        from pilosa_tpu.parallel.placement import fnv64a as py
        return py(data)
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data) if data else (ctypes.c_uint8 * 1)()
    return int(h.pt_fnv64a(buf, len(data)))


def popcount64(words: np.ndarray) -> int:
    h = lib()
    words = np.ascontiguousarray(words, dtype=np.uint64)
    if h is None:
        return int(np.sum(np.bitwise_count(words)))
    return int(h.pt_popcount64(_ptr(words, ctypes.c_uint64), words.size))


def and_count(a: np.ndarray, b: np.ndarray) -> int:
    h = lib()
    a = np.ascontiguousarray(a, dtype=np.uint64)
    b = np.ascontiguousarray(b, dtype=np.uint64)
    if h is None:
        return int(np.sum(np.bitwise_count(a & b)))
    return int(h.pt_and_count(_ptr(a, ctypes.c_uint64), _ptr(b, ctypes.c_uint64), a.size))


_ARRAY_OPS = {"and": "pt_array_intersect", "or": "pt_array_union",
              "andnot": "pt_array_difference", "xor": "pt_array_xor"}


def array_op(a: np.ndarray, b: np.ndarray, kind: str) -> np.ndarray:
    """Set algebra on sorted uint16 arrays."""
    h = lib()
    a = np.ascontiguousarray(a, dtype=np.uint16)
    b = np.ascontiguousarray(b, dtype=np.uint16)
    if h is None:
        if kind == "and":
            return np.intersect1d(a, b, assume_unique=True)
        if kind == "or":
            return np.union1d(a, b)
        if kind == "andnot":
            return np.setdiff1d(a, b, assume_unique=True)
        return np.setxor1d(a, b, assume_unique=True)
    out = np.empty(a.size + b.size, dtype=np.uint16)
    fn = getattr(h, _ARRAY_OPS[kind])
    k = fn(_ptr(a, ctypes.c_uint16), a.size, _ptr(b, ctypes.c_uint16), b.size,
           _ptr(out, ctypes.c_uint16))
    return out[:k].copy()


def array_to_bits(vals: np.ndarray) -> np.ndarray:
    """Sorted uint16 members -> uint64[1024] little-endian bitmap."""
    h = lib()
    vals = np.ascontiguousarray(vals, dtype=np.uint16)
    if h is None:
        bits = np.zeros(1 << 16, dtype=np.uint8)
        bits[vals] = 1
        return np.packbits(bits, bitorder="little").view("<u8").copy()
    out = np.zeros(1024, dtype=np.uint64)
    h.pt_array_to_bits(_ptr(vals, ctypes.c_uint16), vals.size,
                       _ptr(out, ctypes.c_uint64))
    return out


def bits_to_array(words: np.ndarray) -> np.ndarray:
    """uint64[1024] bitmap -> sorted uint16 members."""
    h = lib()
    words = np.ascontiguousarray(words, dtype=np.uint64)
    if h is None:
        bits = np.unpackbits(words.view(np.uint8), bitorder="little")
        return np.flatnonzero(bits).astype(np.uint16)
    out = np.empty(1 << 16, dtype=np.uint16)
    k = h.pt_bits_to_array(_ptr(words, ctypes.c_uint64), _ptr(out, ctypes.c_uint16))
    return out[:k].copy()


def positions_to_dense(positions: np.ndarray, start: int, width: int) -> np.ndarray:
    """Absolute uint64 positions -> dense uint32-lane bitvector of `width`
    bits with bit 0 = `start` (row materialization for HBM upload)."""
    h = lib()
    positions = np.ascontiguousarray(positions, dtype=np.uint64)
    out = np.zeros(width // 32, dtype=np.uint32)
    if h is None:
        off = positions[(positions >= start) & (positions < start + width)] - np.uint64(start)
        np.bitwise_or.at(out, (off >> np.uint64(5)).astype(np.int64),
                         np.uint32(1) << (off & np.uint64(31)).astype(np.uint32))
        return out
    h.pt_positions_to_dense(_ptr(positions, ctypes.c_uint64), positions.size,
                            start, width, _ptr(out, ctypes.c_uint32))
    return out


_RUN_KINDS = {"and": 0, "or": 1, "andnot": 2, "xor": 3}


def run_op(a: np.ndarray, b: np.ndarray, kind: str):
    """Interval algebra on two [n, 2] uint16 run lists; returns the result
    intervals [k, 2], or None when the native lib is unavailable (callers
    fall back to their dense path)."""
    h = lib()
    if h is None:
        return None
    a = np.ascontiguousarray(a, dtype=np.uint16)
    b = np.ascontiguousarray(b, dtype=np.uint16)
    na, nb = a.shape[0], b.shape[0]
    out = np.empty((na + nb + 1, 2), dtype=np.uint16)
    k = h.pt_run_op(_ptr(a, ctypes.c_uint16), na, _ptr(b, ctypes.c_uint16),
                    nb, _ptr(out, ctypes.c_uint16), _RUN_KINDS[kind])
    return out[:k].copy()


def run_op_count(a: np.ndarray, b: np.ndarray, kind: str):
    """Member count of op(a, b) over run lists; None without the lib."""
    h = lib()
    if h is None:
        return None
    a = np.ascontiguousarray(a, dtype=np.uint16)
    b = np.ascontiguousarray(b, dtype=np.uint16)
    return int(h.pt_run_op_count(_ptr(a, ctypes.c_uint16), a.shape[0],
                                 _ptr(b, ctypes.c_uint16), b.shape[0],
                                 _RUN_KINDS[kind]))


def run_filter_array(runs: np.ndarray, vals: np.ndarray, keep_inside: bool):
    """Sorted uint16 values inside (or outside) the intervals — array∧run /
    array∖run in one pass; None without the lib."""
    h = lib()
    if h is None:
        return None
    runs = np.ascontiguousarray(runs, dtype=np.uint16)
    vals = np.ascontiguousarray(vals, dtype=np.uint16)
    out = np.empty(vals.size, dtype=np.uint16)
    k = h.pt_run_filter_array(_ptr(runs, ctypes.c_uint16), runs.shape[0],
                              _ptr(vals, ctypes.c_uint16), vals.size,
                              _ptr(out, ctypes.c_uint16),
                              1 if keep_inside else 0)
    return out[:k].copy()


def run_and_count_bits(runs: np.ndarray, words: np.ndarray):
    """popcount of the uint64[1024] bitmap restricted to the intervals;
    None without the lib."""
    h = lib()
    if h is None:
        return None
    runs = np.ascontiguousarray(runs, dtype=np.uint16)
    words = np.ascontiguousarray(words, dtype=np.uint64)
    return int(h.pt_run_and_count_bits(_ptr(runs, ctypes.c_uint16),
                                       runs.shape[0],
                                       _ptr(words, ctypes.c_uint64)))


def run_to_bits(runs: np.ndarray) -> np.ndarray:
    """[n, 2] intervals -> uint64[1024] bitmap (numpy fallback included:
    this one backs the storage layer's dense materialization)."""
    h = lib()
    runs = np.ascontiguousarray(runs, dtype=np.uint16)
    out = np.zeros(1024, dtype=np.uint64)
    if h is None:
        bits = np.zeros(1 << 16, dtype=np.uint8)
        for s, e in runs.astype(np.int32):
            bits[s:e + 1] = 1
        return np.packbits(bits, bitorder="little").view("<u8").copy()
    h.pt_run_to_bits(_ptr(runs, ctypes.c_uint16), runs.shape[0],
                     _ptr(out, ctypes.c_uint64))
    return out


def oplog_parse(data: bytes):
    """Parse + checksum-validate an op-log chunk natively.
    Returns order-preserving (types uint8[], values uint64[]) or None on
    corruption / when the native lib is unavailable."""
    h = lib()
    if h is None or not data:
        return None
    n_ops_max = len(data) // 13
    types = np.empty(n_ops_max, dtype=np.uint8)
    values = np.empty(n_ops_max, dtype=np.uint64)
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    count = h.pt_oplog_parse(buf, len(data), _ptr(types, ctypes.c_uint8),
                             _ptr(values, ctypes.c_uint64))
    if count == ctypes.c_size_t(-1).value:
        return None
    return types[:count].copy(), values[:count].copy()
