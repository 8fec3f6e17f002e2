"""The data kind `categorical`: every column of the index holds exactly
one value of the field, as a record's attribute does (a ride has one cab
type, one passenger count, one pick-up cell) - upstream's Transportation
example is twenty such fields. A row is the sorted columns that drew its
id; the rows of a field are disjoint and, where `present` is 1, cover
every column.

Keys of a field's spec:
  rows, first_id   the field's values are the ids first_id ..
                   first_id + rows - 1
  weights          the share of each id, first_id first (any positive
                   numbers; they are normalised). Or, without it,
  value_exponent, value_ratio
                   the Zipf-Mandelbrot law of lib/datagen.py over `rows`
                   ranks, the ranks scattered over the ids by a shuffle
                   from the seed
  present          the share of columns that hold a value at all (default
                   1); the others hold none

A column's value is drawn independently of every other's. One (field,
shard) is one draw of 2^20 32-bit numbers, looked up against the
cumulative weights (a table over the top 16 bits answers all but the
numbers that share a table cell with a boundary, and a binary search
those), and one stable argsort by the value drawn, from a generator keyed
by (seed, field, shard): no Python loop over columns, and the bytes do not
depend on how the work is spread over threads. A weight is honoured to
2^-32 of the columns.
"""

from __future__ import annotations

import numpy as np

from lib.datagen import SHARD_WIDTH, Row, rank_weights


class _Law:
    """Cumulative weights as 32-bit thresholds: a number v drew rank k
    where edges[k-1] <= v < edges[k], and none where v >= edges[-1]."""

    def __init__(self, weights: np.ndarray, present: float):
        n = weights.size
        self.n_rows = n
        self.dtype = (np.uint8 if n < 255 else
                      np.uint16 if n < 65535 else np.uint32)
        edges = np.floor(np.cumsum(weights) * present * 2.0 ** 32)
        self.edges = np.minimum(edges, 2.0 ** 32).astype(np.uint64)
        if present >= 1.0:
            self.edges[-1] = 1 << 32    # rounding may not leave a column out
        cell = np.arange(1 << 16, dtype=np.uint64) << np.uint64(16)
        self.lo = self._search(cell)
        self.hi = self._search(cell | np.uint64(0xFFFF))

    def _search(self, v: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.edges, v, side="right").astype(self.dtype)

    def rank(self, v: np.ndarray) -> np.ndarray:
        cell = v >> 16
        rank = self.lo[cell]
        split = np.flatnonzero(rank != self.hi[cell])
        rank[split] = self._search(v[split].astype(np.uint64))
        return rank


def _shard(seed, fi, shard, law) -> tuple:
    """(the shard's columns ordered by the rank drawn, then by column;
    how many drew each rank). Columns that drew no value are left out."""
    rng = np.random.default_rng([seed, 0xCA7E, fi, shard])
    rank = law.rank(rng.integers(0, 1 << 32, size=SHARD_WIDTH,
                                 dtype=np.uint32))
    counts = np.bincount(rank, minlength=law.n_rows + 1)[:law.n_rows]
    order = np.argsort(rank, kind="stable")[:int(counts.sum())]
    return (order.astype(np.uint32) + np.uint32(shard * SHARD_WIDTH),
            counts.astype(np.int64))


def make_field(seed: int, fi: int, spec: dict, n_shards: int, pool) -> dict:
    n_rows = spec["rows"]
    rng = np.random.default_rng([seed, 0xCA7E, fi])
    if "weights" in spec:
        w = np.asarray(spec["weights"], dtype=np.float64)
        if w.shape != (n_rows,) or np.any(w <= 0):
            raise ValueError(f"field {spec['name']!r}: `weights` has to be "
                             f"{n_rows} positive numbers")
        w = w / w.sum()
        ids = np.arange(n_rows)
    else:
        w = rank_weights(n_rows, spec["value_exponent"], spec["value_ratio"])
        ids = rng.permutation(n_rows)
    ids = ids + spec.get("first_id", 0)
    law = _Law(w, float(spec.get("present", 1.0)))
    shards = list(pool.map(lambda s: _shard(seed, fi, s, law),
                           range(n_shards)))
    # lay the pieces out row by row, shard by shard within a row: a row is
    # then one slice, already sorted
    counts = np.stack([c for _, c in shards], axis=1)      # [rank, shard]
    dest = np.concatenate([[0], np.cumsum(counts.ravel())])
    cols = np.empty(int(dest[-1]), dtype=np.uint32)
    dest = dest[:-1].reshape(counts.shape)

    def place(s: int) -> None:
        order, c = shards[s]
        src = np.concatenate([[0], np.cumsum(c)[:-1]])
        cols[np.repeat(dest[:, s] - src, c) + np.arange(order.size)] = order

    list(pool.map(place, range(n_shards)))
    starts = np.concatenate([dest[:, 0], [cols.size]]).tolist()
    return {int(ids[rank]): Row(n_shards, cols[starts[rank]:starts[rank + 1]])
            for rank in range(n_rows)}
