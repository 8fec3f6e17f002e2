"""The requests the generators make, the reference evaluates and the work
counter reads. A tree is nested tuples over rows of any field:

  ("row", field, row_id)
  ("intersect" | "union" | "difference", (child, ...))

and a request is one call over trees and fields, a tuple whose first
element names the call: ("count", tree), ("topn", field, n, tree or None),
("groupby", (field, ...), tree or None). A call is a file of its own,
lib/calls/<name>.py, found by that first element; this module knows the
tree and hands an outer node to its call. What a call's file holds:

  build(spec, tree)          a mix's query entry -> node; `tree(t)` turns
                             the entry's tree (below) into a tree above,
                             drawing its slots
  to_pql(node)               the text, as pql/parser.py reads it
  answer(ref, node)          the plain reference's answer
                             (lib/reference.py's Reference)
  same(got, want)            does the server's JSON result equal it
  bytes_needed(work, node)   the least bytes the call has to read
                             (lib/work.py's Work)

A tree in a mix's file is JSON: {"row": {"field": f, "id": 3}} or, a slot
the generator draws, {"row": {"field": f, "draw": "uniform" | "by_size"}};
{"intersect": [tree, ...]}, {"union": [...]}, {"difference": [...]}.
"""

from __future__ import annotations

from . import byfile

_OPS = {"intersect": "Intersect", "union": "Union",
        "difference": "Difference"}


def tree_pql(tree: tuple) -> str:
    kind = tree[0]
    if kind == "row":
        return f"Row({tree[1]}={tree[2]})"
    if kind in _OPS:
        return f"{_OPS[kind]}({', '.join(tree_pql(c) for c in tree[1])})"
    raise ValueError(f"unknown query node {kind!r}")


def leaves(tree) -> list:
    """(field, row_id) of every Row under a tree."""
    if tree[0] == "row":
        return [(tree[1], tree[2])]
    return [leaf for c in tree[1] for leaf in leaves(c)]


def tree_from_json(doc: dict, draw) -> tuple:
    """A mix's tree -> a tree above; `draw(field, how)` fills a slot."""
    (kind, body), = doc.items()
    if kind == "row":
        row_id = body["id"] if "id" in body else draw(body["field"],
                                                      body["draw"])
        return ("row", body["field"], int(row_id))
    if kind in _OPS:
        return (kind, tuple(tree_from_json(c, draw) for c in body))
    raise ValueError(f"unknown tree node {kind!r}")


def call_of(name: str):
    """The call's module, by the name a node or a mix gives it."""
    return byfile.load("lib/calls", name.lower())


def to_pql(node: tuple) -> str:
    if node[0] == "row" or node[0] in _OPS:
        return tree_pql(node)
    return call_of(node[0]).to_pql(node)


def answer(ref, node: tuple):
    return call_of(node[0]).answer(ref, node)


def same(node: tuple, got, want) -> bool:
    """`got` is the `results` of the server's reply to one request: one
    call, so one result."""
    return (isinstance(got, list) and len(got) == 1
            and call_of(node[0]).same(got[0], want))


def bytes_needed(work, node: tuple) -> int:
    return call_of(node[0]).bytes_needed(work, node)
