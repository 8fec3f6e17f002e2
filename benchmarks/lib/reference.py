"""The plain reference: set algebra on packed uint64 words with numpy.
It shares no code with pilosa_tpu and takes nothing the server made: the
rows come from lib.datagen, straight from the seed. A row over 64 shards is
8 MiB of words; an operator is one np.bitwise_* pass and a count is
np.bitwise_count summed. A Count's answer is an int, as in the server's
JSON, so the two compare with `==`.
"""

from __future__ import annotations

import numpy as np


def popcount(words: np.ndarray) -> int:
    return int(np.bitwise_count(words).sum(dtype=np.int64))


class Reference:
    def __init__(self, data, count_fn=popcount):
        """`count_fn` is the control's hook (lib.control): how a result's
        words become its count. The reference itself counts every bit."""
        self.data = data
        self._packed: dict = {}
        self._count = count_fn

    def row(self, field: str, row_id: int) -> np.ndarray:
        key = (field, row_id)
        if key not in self._packed:
            self._packed[key] = self.data.fields[field][row_id].packed()
        return self._packed[key]

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

    def answer(self, q: tuple) -> int:
        """q is ("count", tree)."""
        return self._count(self.eval(q[1]))
