"""The roaring wire writer against a parser written here from the format's
description (not the program's)."""

import struct

import numpy as np

from lib import roaring_wire


def parse(payload: bytes) -> np.ndarray:
    magic, version, n = struct.unpack_from("<HHI", payload, 0)
    assert (magic, version) == (12348, 0)
    out = []
    for i in range(n):
        key, kind, n1 = struct.unpack_from("<QHH", payload, 8 + 12 * i)
        (off,) = struct.unpack_from("<I", payload, 8 + 12 * n + 4 * i)
        if kind == 1:
            low = np.frombuffer(payload, "<u2", n1 + 1, off).astype(np.uint64)
            assert n1 + 1 <= 4096 and np.all(np.diff(low.astype(np.int64)) > 0)
        else:
            assert kind == 2 and n1 + 1 > 4096
            words = np.frombuffer(payload, "<u8", 1024, off)
            low = np.flatnonzero(np.unpackbits(
                words.view(np.uint8), bitorder="little")).astype(np.uint64)
            assert low.size == n1 + 1
        out.append((np.uint64(key) << np.uint64(16)) + low)
    got = np.concatenate(out) if out else np.empty(0, np.uint64)
    assert np.all(np.diff(got.astype(np.int64)) > 0)
    return got


def test_columns_round_trip():
    rng = np.random.default_rng(1)
    sparse = np.unique(rng.integers(0, 1 << 20, size=900, dtype=np.uint32))
    mid = np.unique(rng.integers(0, 1 << 20, size=90_000, dtype=np.uint32))
    lumpy = np.unique(np.concatenate([      # one full container, one thin
        np.arange(65536, 65536 + 30_000, dtype=np.uint32),
        rng.integers(5 << 16, 6 << 16, size=50, dtype=np.uint32)]))
    dense = np.flatnonzero(rng.random(1 << 20) < 0.3).astype(np.uint32)
    rows = [(3, sparse), (4, mid), (9, lumpy), (10, dense),
            (20, np.empty(0, dtype=np.uint32))]
    want = np.concatenate([
        sparse.astype(np.uint64) + (3 << 20), mid.astype(np.uint64) + (4 << 20),
        lumpy.astype(np.uint64) + (9 << 20),
        dense.astype(np.uint64) + (10 << 20)])
    assert np.array_equal(parse(roaring_wire.fragment_payload(rows)), want)


def test_empty_fragment():
    assert parse(roaring_wire.fragment_payload([])).size == 0
