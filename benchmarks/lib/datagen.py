"""One general data generator. A configuration's `fields` (its config.json)
say what each field holds; this module makes it from the seed with numpy,
in bulk, and hands every row out in the two forms the rest of the
benchmark needs: per-shard pieces for the roaring wire writer, and packed
uint64 words over all shards for the plain reference.

A field is what upstream's own benchmark tool leaves behind
(`pi bench zipf` of github.com/pilosa/tools, written down from memory in
configs/segmentation/config.json): `set_bits_per_shard` x shards times it
sets one bit, at a row and a column each drawn from a Zipf-Mandelbrot law
P(k) ~ (v + k) ** -exponent over ranks k = 0 .. n-1. The offset v is not
given but the `ratio` of the least likely rank's probability to the most
likely one's: v = z (n - 1) / (1 - z) with z = ratio ** (1 / exponent),
as the tool's getZipfOffset has it. Ranks are scattered over the ids by a
permutation (the tool's PermutationGenerator): rows by a shuffle from the
seed, columns by the bijection rank -> (rank * A + b) mod n_columns. A bit
set twice is one bit.

Every row draws from a generator of its own, keyed by (seed, field, row),
so the bytes do not depend on how the work is spread over threads.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

SHARD_WIDTH = 1 << 20
WORDS_PER_SHARD = SHARD_WIDTH // 64
_PRIME = 2654435761   # prime, so coprime to any column count below it


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


def draw_ranks(rng, size: int, n: int, exponent: float,
               ratio: float) -> np.ndarray:
    """`size` ranks in [0, n) from the same law, by inverting the
    continuous distribution function (n is tens of millions here, and the
    offset a third of it, so a rank's probability differs from the discrete
    law's by parts in 10**8)."""
    v = zipf_offset(n, exponent, ratio)
    a = 1.0 - exponent
    lo, hi = v ** a, (v + n) ** a
    x = (lo - rng.random(size) * (lo - hi)) ** (1.0 / a) - v
    return np.minimum(x.astype(np.int64), n - 1)


def _row(seed, fi, rank, n_draws, n_shards, spec, shift) -> Row:
    rng = np.random.default_rng([seed, 0xDA7A, fi, rank])
    n_cols = n_shards * SHARD_WIDTH
    ranks = draw_ranks(rng, n_draws, n_cols, spec["column_exponent"],
                       spec["column_ratio"]).astype(np.uint64)
    cols = (ranks * np.uint64(_PRIME) + np.uint64(shift)) % np.uint64(n_cols)
    return Row(n_shards, np.unique(cols.astype(np.uint32)))


def make(config: dict, seed: int, shards: int | None = None) -> Data:
    """The configuration's data from the seed. `shards` overrides the
    configuration's scale (the CPU rehearsal and the tests use 2)."""
    n_shards = int(shards or config["shards"])
    data = Data(n_shards)
    jobs = []
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, os.cpu_count() or 1)) as pool:
        for fi, spec in enumerate(config["fields"]):
            name, n_rows = spec["name"], spec["rows"]
            data.fields[name] = {}
            data.options[name] = spec.get("options", {})
            rng = np.random.default_rng([seed, 0xDA7A, fi])
            per_row = rng.multinomial(
                spec["set_bits_per_shard"] * n_shards,
                rank_weights(n_rows, spec["row_exponent"],
                             spec["row_ratio"]))
            ids = rng.permutation(n_rows) + spec.get("first_id", 0)
            shift = int(rng.integers(0, n_shards * SHARD_WIDTH))
            for rank in range(n_rows):
                jobs.append((name, int(ids[rank]), pool.submit(
                    _row, seed, fi, rank, int(per_row[rank]), n_shards,
                    spec, shift)))
        for name, row_id, fut in jobs:
            data.fields[name][row_id] = fut.result()
    return data
