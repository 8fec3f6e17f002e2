"""The query trees the traffic generator makes, the reference evaluates and
the work counter reads. A tree is nested tuples:

  ("row", field, row_id)
  ("intersect" | "union" | "difference", (child, ...))
and a request is one call over a tree: ("count", tree).
"""

from __future__ import annotations

_OPS = {"intersect": "Intersect", "union": "Union",
        "difference": "Difference"}


def to_pql(q: tuple) -> str:
    kind = q[0]
    if kind == "row":
        return f"Row({q[1]}={q[2]})"
    if kind in _OPS:
        return f"{_OPS[kind]}({', '.join(to_pql(c) for c in q[1])})"
    if kind == "count":
        return f"Count({to_pql(q[1])})"
    raise ValueError(f"unknown query node {kind!r}")


def leaves(tree) -> list:
    """(field, row_id) of every Row under a tree."""
    if tree[0] == "row":
        return [(tree[1], tree[2])]
    return [leaf for c in tree[1] for leaf in leaves(c)]
