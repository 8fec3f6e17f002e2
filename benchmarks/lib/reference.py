"""The plain reference: set algebra on packed uint64 words with numpy.
It shares no code with pilosa_tpu and takes nothing the server made: the
rows come from lib.datagen, straight from the seed. A row over 64 shards is
8 MiB of words; an operator is one np.bitwise_* pass and a count is
np.bitwise_count summed. What a call makes of trees and counts (a Count's
int, a TopN's ranking, a GroupBy's table) is the call's own file under
lib/calls/.

Every count goes through one hook, `count_fn(words, cols)`, so that the
control (lib/control.py) breaks every call by breaking it:
  count_fn(words, None)   the bits set in packed words
  count_fn(None, cols)    how many sorted columns there are
  count_fn(words, cols)   how many of the columns have their bit set in
                          the words: (words[c >> 6] >> (c & 63)) & 1,
                          summed
The last form counts a row under a filter from the row's sorted columns,
without packing the row: a field of 10,000 rows is 80 GB packed at 64
shards. `row_count` reads a row in the cheaper of its two forms, as
lib/work.py reckons it: packed (and kept) where its columns would take
more bytes than its words, from its columns where not.
"""

from __future__ import annotations

import numpy as np

from .datagen import WORDS_PER_SHARD


def popcount(words: np.ndarray) -> int:
    return int(np.bitwise_count(words).sum(dtype=np.int64))


def members(words: np.ndarray, cols: np.ndarray) -> np.ndarray:
    """0 or 1 for each column: is its bit set in the words."""
    return (words[cols >> 6] >> (cols & 63).astype(np.uint64)) & np.uint64(1)


def exact_count(words, cols=None) -> int:
    if cols is None:
        return popcount(words)
    if words is None:
        return int(cols.size)
    return int(members(words, cols).sum(dtype=np.int64))


class Reference:
    def __init__(self, data, count_fn=exact_count):
        """`count_fn` is the control's hook (lib.control): how words and
        columns become a count. The reference itself counts every bit."""
        self.data = data
        self._packed: dict = {}
        self.count = count_fn

    def row(self, field: str, row_id: int, keep: bool = True) -> np.ndarray:
        """The row's packed words; kept for the next request unless
        `keep` is False."""
        key = (field, row_id)
        if key in self._packed:
            return self._packed[key]
        words = self.data.fields[field][row_id].packed()
        if keep:
            self._packed[key] = words
        return words

    def cheaper_packed(self, field: str, row_id: int) -> bool:
        """Do the row's columns take more bytes than its words."""
        return (4 * self.data.fields[field][row_id].cols.size
                > 8 * self.data.n_shards * WORDS_PER_SHARD)

    def eval(self, tree) -> np.ndarray:
        kind = tree[0]
        if kind == "row":
            return self.row(tree[1], tree[2])
        parts = [self.eval(c) for c in tree[1]]
        acc = parts[0].copy()
        for p in parts[1:]:
            if kind == "intersect":
                np.bitwise_and(acc, p, out=acc)
            elif kind == "union":
                np.bitwise_or(acc, p, out=acc)
            elif kind == "difference":
                np.bitwise_and(acc, ~p, out=acc)
            else:
                raise ValueError(f"unknown operator {kind!r}")
        return acc

    def row_count(self, field: str, row_id: int, under=None,
                  packed: bool | None = None) -> int:
        """The bits of one row, under packed filter words if given.
        `packed` forces one of the row's two forms (the tests hold them
        against each other)."""
        row = self.data.fields[field][row_id]
        if under is None:
            return self.count(None, row.cols)
        if packed is None:
            packed = self.cheaper_packed(field, row_id)
        if packed:
            return self.count(self.row(field, row_id) & under, None)
        return self.count(under, row.cols)
