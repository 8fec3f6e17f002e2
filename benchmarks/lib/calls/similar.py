"""The call `similar`: upstream's chemical-similarity search,
TopN(field, Row(field=q), n=N, tanimotoThreshold=T); node ("similar",
field, q, N, T). The rows are molecules, their columns the positions of
their fingerprint bits, and the answer is the N rows of the field most
like row q by Tanimoto similarity above T %.

The reference (this configuration's copy of the plain reference, nothing
of pilosa_tpu): every row's intersection with q, vectorised over all the
field's sorted columns at once (one lookup table of q's columns,
np.add.reduceat over the rows' runs of columns; no Python loop over the
rows); a row stays where 100 |r ∩ q| > T (|r| + |q| - |r ∩ q|), strictly
(upstream's ceil(100 tanimoto) > T, for a whole T), and counts its
intersection with q. |q| is counted through the reference's count hook,
so the control (lib/control.py), whose |q| is off, moves every band and
drops q itself from its own answer.

The server's result is a list of {"id", "count"}, most first; it equals
the reference by lib/calls/topn.py's rule: its counts, in order, are the
reference's N largest, every id is there once with exactly the count the
reference gives it, and ties at the N-th place are free.

In a mix: {"call": "similar", "field": f, "n": N, "threshold": T,
"row": {"row": {"field": f, "draw": "uniform"}}}.
"""

from __future__ import annotations

import threading
import weakref

import numpy as np

from lib import query

_lock = threading.Lock()
_flat = weakref.WeakKeyDictionary()   # data -> {field: its rows end to end}


def build(spec: dict, tree) -> tuple:
    row = tree(spec["row"])
    if row[0] != "row" or row[1] != spec["field"]:
        raise ValueError("similar: `row` has to be one row of `field`")
    return ("similar", spec["field"], row[2], int(spec["n"]),
            int(spec["threshold"]))


def to_pql(node: tuple) -> str:
    _, field, q, n, t = node
    return f"TopN({field}, Row({field}={q}), n={n}, tanimotoThreshold={t})"


def flat(data, field: str) -> tuple:
    """(ids, sizes, the start of each row in cols, cols, the widest
    column + 1) of the field's rows that hold a bit, every row's sorted
    columns one after another; made once a field and kept."""
    with _lock:
        mine = _flat.setdefault(data, {})
        if field not in mine:
            rows = data.fields[field]
            ids = np.array([r for r in sorted(rows) if rows[r].cols.size])
            sizes = np.array([rows[r].cols.size for r in ids.tolist()],
                             dtype=np.int64)
            cols = (np.concatenate([rows[r].cols for r in ids.tolist()])
                    if ids.size else np.empty(0, np.uint32))
            starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
            mine[field] = (ids, sizes, starts.astype(np.int64), cols,
                           int(cols.max()) + 1 if cols.size else 0)
        return mine[field]


def answer(ref, node: tuple) -> dict:
    _, field, q, n, t = node
    ids, sizes, starts, cols, width = flat(ref.data, field)
    q_cols = ref.data.fields[field][q].cols
    counts: dict = {}
    if ids.size:
        table = np.zeros(width, dtype=bool)
        table[q_cols] = True
        inter = np.add.reduceat(table[cols], starts, dtype=np.int64)
        q_count = ref.row_count(field, q)
        keep = (inter > 0) & (100 * inter > t * (sizes + q_count - inter))
        counts = dict(zip(ids[keep].tolist(), inter[keep].tolist()))
    return {"n": n, "counts": counts}


def same(got, want: dict) -> bool:
    return query.call_of("topn").same(got, want)


def bytes_needed(work, node: tuple) -> int:
    _, field, q, _, _ = node
    return work.field_bytes(field) + work.row_bytes(field, q)
