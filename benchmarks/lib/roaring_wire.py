"""A numpy writer of Pilosa's roaring wire format, for the import-roaring
route. The benchmark's own code: it shares nothing with pilosa_tpu, so a
later PR can change the program's serializer and not the yardstick's load.

Format (upstream roaring.go, docs/architecture.md): u16 magic 12348, u16
version 0, u32 container count; per container key u64 | type u16 | n-1 u16;
per container absolute offset u32; then the payloads — array (type 1) is
n x u16 sorted low bits, bitmap (type 2) is 1024 x u64 little-endian. A
container holds 2^16 positions; a row of a fragment is 16 of them, row r's
local column c at position r * 2^20 + c. A container takes the smaller
encoding: array up to 4096 values, bitmap above. Run containers are never
written (the server finds runs in array containers itself).
"""

from __future__ import annotations

import numpy as np

MAGIC = 12348
ARRAY_MAX = 4096
CONTAINER_BITS = 1 << 16
CONTAINERS_PER_ROW = 16
WORDS_PER_CONTAINER = CONTAINER_BITS // 64
TYPE_ARRAY, TYPE_BITMAP = 1, 2

_DESC = np.dtype([("key", "<u8"), ("type", "<u2"), ("n1", "<u2")])


def _array_to_words(low: np.ndarray) -> bytes:
    bits = np.zeros(CONTAINER_BITS, dtype=np.uint8)
    bits[low] = 1
    return np.packbits(bits, bitorder="little").tobytes()


def fragment_payload(rows: list) -> bytes:
    """One shard of one field as a roaring payload. `rows` is a list of
    (row_id, data) in ascending row_id, where data is the row's sorted
    unique local columns (any integer dtype, values < 2^20)."""
    keys, types, counts, chunks = [], [], [], []
    for row_id, data in rows:
        base = row_id * CONTAINERS_PER_ROW
        if data.size == 0:
            continue
        per = np.bincount(data >> 16, minlength=CONTAINERS_PER_ROW)
        live = np.flatnonzero(per)
        low = (data & 0xFFFF).astype("<u2")
        keys.append(base + live)
        counts.append(per[live])
        if per.max() <= ARRAY_MAX:
            types.append(np.full(live.size, TYPE_ARRAY))
            chunks.append(low.tobytes())
            continue
        ends = np.cumsum(per)
        kinds = np.where(per[live] > ARRAY_MAX, TYPE_BITMAP, TYPE_ARRAY)
        types.append(kinds)
        for i, kind in zip(live, kinds):
            part = low[ends[i] - per[i]:ends[i]]
            chunks.append(_array_to_words(part) if kind == TYPE_BITMAP
                          else part.tobytes())
    if not keys:
        return np.array([MAGIC, 0, 0, 0], dtype="<u2").tobytes()
    n_per = np.concatenate(counts).astype(np.int64)
    desc = np.empty(n_per.size, dtype=_DESC)
    desc["key"] = np.concatenate(keys)
    desc["type"] = np.concatenate(types)
    desc["n1"] = n_per - 1
    sizes = np.where(desc["type"] == TYPE_BITMAP, WORDS_PER_CONTAINER * 8,
                     2 * n_per)
    head = 8 + 16 * n_per.size
    offsets = head + np.concatenate([[0], np.cumsum(sizes)[:-1]])
    if head + int(sizes.sum()) >= 1 << 32:
        raise ValueError("payload over 4 GiB: offsets are u32")
    header = (np.array([MAGIC, 0], dtype="<u2").tobytes()
              + np.array([n_per.size], dtype="<u4").tobytes())
    return b"".join([header, desc.tobytes(),
                     offsets.astype("<u4").tobytes()] + chunks)
