"""The bytes a request has to read, from the request and the data alone.

For each leaf row and shard it is min(131072, 4 x bits set there): the
cheaper of a dense plane (2^20 bits) and a sorted list of 32-bit columns.
It does not look at what the program chose to upload or dispatch, so the
yardstick reads the same work whatever implements it. Bitmap algebra does
next to no arithmetic per byte, so the roofline built on this is the
memory one.
"""

from __future__ import annotations

import numpy as np

from . import query

PLANE_BYTES = (1 << 20) // 8


class Work:
    def __init__(self, data):
        self.data = data
        self._row: dict = {}

    def row_bytes(self, field: str, row_id: int) -> int:
        key = (field, row_id)
        if key not in self._row:
            per = self.data.fields[field][row_id].bits_per_shard()
            self._row[key] = int(np.minimum(PLANE_BYTES, 4 * per).sum())
        return self._row[key]

    def bytes_needed(self, q: tuple) -> int:
        """q is ("count", tree)."""
        return sum(self.row_bytes(f, r) for f, r in query.leaves(q[1]))
