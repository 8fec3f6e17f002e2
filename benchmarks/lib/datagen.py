"""The data of a configuration, from the seed. A configuration's `fields`
(its config.json) say what each field holds; each field's `kind` names the
law it is drawn by, a file of its own under lib/data_kinds/ (absent means
`zipf`, what upstream's `pi bench zipf` leaves behind), and this module
hands every row out in the two forms the rest of the benchmark needs:
per-shard pieces for the roaring wire writer, and packed uint64 words over
all shards for the plain reference.

A data kind is one function,

    make_field(seed, fi, spec, n_shards, pool) -> {row_id: Row}

`fi` the field's place in the configuration, `spec` its entry of `fields`,
`pool` a thread pool to spread the numpy work over. It draws from
generators keyed by the seed, the field and a row or a shard, never by
what a thread happened to do first, so the bytes do not depend on how the
work is spread. `zipf_offset` and `rank_weights` below are the
Zipf-Mandelbrot law that more than one kind draws from: P(k) ~ (v + k) **
-exponent over ranks k = 0 .. n-1, the offset v not given but the `ratio`
of the least likely rank's probability to the most likely one's: v = z (n
- 1) / (1 - z) with z = ratio ** (1 / exponent), as the tool's
getZipfOffset has it.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

from . import byfile

SHARD_WIDTH = 1 << 20
WORDS_PER_SHARD = SHARD_WIDTH // 64


def pack_columns(cols: np.ndarray, n_shards: int) -> np.ndarray:
    """Sorted unique global columns -> uint64 words over all shards (bit c
    at word c >> 6, bit c & 63)."""
    words = np.zeros(n_shards * WORDS_PER_SHARD, dtype=np.uint64)
    if cols.size:
        idx = cols >> 6
        starts = np.flatnonzero(np.concatenate(
            [[True], idx[1:] != idx[:-1]]))
        vals = np.uint64(1) << (cols & 63).astype(np.uint64)
        words[idx[starts]] = np.bitwise_or.reduceat(vals, starts)
    return words


class Row:
    """One row of a field: its sorted unique uint32 global columns."""

    __slots__ = ("n_shards", "cols", "_cuts")

    def __init__(self, n_shards: int, cols: np.ndarray):
        self.n_shards = n_shards
        self.cols = cols
        self._cuts = None

    def packed(self) -> np.ndarray:
        return pack_columns(self.cols, self.n_shards)

    def shard_piece(self, shard: int) -> np.ndarray:
        """What roaring_wire.fragment_payload takes for this shard."""
        if self._cuts is None:
            edges = np.arange(self.n_shards, dtype=np.uint32) * SHARD_WIDTH
            self._cuts = np.searchsorted(self.cols, edges).tolist() \
                + [self.cols.size]
        a, b = self._cuts[shard], self._cuts[shard + 1]
        return self.cols[a:b] - np.uint32(shard * SHARD_WIDTH)

    def bits_per_shard(self) -> np.ndarray:
        return np.bincount(self.cols >> 20,
                           minlength=self.n_shards).astype(np.int64)

    def count(self) -> int:
        return int(self.cols.size)


class Data:
    """fields[name][row_id] -> Row, plus what the config said of each."""

    def __init__(self, n_shards: int):
        self.n_shards = n_shards
        self.fields: dict = {}
        self.options: dict = {}

    def row_ids(self, field: str) -> list:
        return sorted(self.fields[field])


def zipf_offset(n: int, exponent: float, ratio: float) -> float:
    z = ratio ** (1.0 / exponent)
    return z * (n - 1) / (1.0 - z)


def rank_weights(n: int, exponent: float, ratio: float) -> np.ndarray:
    """P(rank k), k = 0 .. n-1, exactly."""
    w = (zipf_offset(n, exponent, ratio) + np.arange(n)) ** -exponent
    return w / w.sum()


def make(config: dict, seed: int, shards: int | None = None) -> Data:
    """The configuration's data from the seed. `shards` overrides the
    configuration's scale (the CPU rehearsal and the tests use 2)."""
    n_shards = int(shards or config["shards"])
    data = Data(n_shards)
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1)) as pool:
        for fi, spec in enumerate(config["fields"]):
            kind = byfile.load("lib/data_kinds", spec.get("kind"))
            data.options[spec["name"]] = spec.get("options", {})
            data.fields[spec["name"]] = kind.make_field(
                seed, fi, spec, n_shards, pool)
    return data
