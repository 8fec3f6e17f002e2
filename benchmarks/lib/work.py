"""The bytes a request has to read, from the request and the data alone.

For each row and shard it is min(131072, 4 x bits set there): the cheaper
of a dense plane (2^20 bits) and a sorted list of 32-bit columns. It does
not look at what the program chose to upload or dispatch, so the yardstick
(`kernels_roofline`) reads the same work whatever implements it. Bitmap
algebra does next to no arithmetic per byte, so the roofline built on this
is the memory one.

What a call needs is the call's own (`bytes_needed` in lib/calls/<call>.py),
reckoned from these two:
  Count(<tree>)                  every leaf row of the tree, once
  TopN(field[, <tree>], n)       every row of the field once, plus the
                                 filter's leaves: which rows rank first is
                                 not known before each has been counted
  GroupBy(Rows(f1), ...[, filter=<tree>])
                                 every row of every named field once, plus
                                 the filter's leaves - not once a
                                 combination: an algorithm that holds what
                                 it has read need read no row twice, so
                                 that is the least one must read
A rank cache or a stored count is the program's way of reading less than
this; the yardstick does not know of it, and a share of the roofline over
100 % would say so.
"""

from __future__ import annotations

import numpy as np

PLANE_BYTES = (1 << 20) // 8


class Work:
    def __init__(self, data):
        self.data = data
        self._row: dict = {}
        self._field: dict = {}

    def row_bytes(self, field: str, row_id: int) -> int:
        key = (field, row_id)
        if key not in self._row:
            per = self.data.fields[field][row_id].bits_per_shard()
            self._row[key] = int(np.minimum(PLANE_BYTES, 4 * per).sum())
        return self._row[key]

    def field_bytes(self, field: str) -> int:
        """Every row of the field, once."""
        if field not in self._field:
            self._field[field] = sum(self.row_bytes(field, r)
                                     for r in self.data.fields[field])
        return self._field[field]
