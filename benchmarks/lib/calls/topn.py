"""The call TopN(field, n=N) and TopN(field, <tree>, n=N): node
("topn", field, n, tree or None). The reference counts every row of the
field, under the tree's packed words where there is one, and keeps the
rows that count more than nought.

The server's result is a list of {"id", "count"}, most first. It equals
the reference where its counts, in order, are the reference's N largest
(all of them where N is 0 or fewer rows count), every id is there once, and
every id has exactly the count the reference gives it. The order among
equal counts is free, and so is which of several rows tied at the N-th
place is returned.

In a mix: {"call": "TopN", "field": f, "n": N[, "filter": <tree>]}.
"""

from lib import query


def build(spec: dict, tree) -> tuple:
    under = tree(spec["filter"]) if "filter" in spec else None
    return ("topn", spec["field"], int(spec["n"]), under)


def to_pql(node: tuple) -> str:
    _, field, n, under = node
    mid = f"{query.tree_pql(under)}, " if under is not None else ""
    return f"TopN({field}, {mid}n={n})"


def answer(ref, node: tuple) -> dict:
    _, field, n, under = node
    words = ref.eval(under) if under is not None else None
    counts = {r: c for r in ref.data.fields[field]
              if (c := ref.row_count(field, r, words)) > 0}
    return {"n": n, "counts": counts}


def same(got, want: dict) -> bool:
    if not isinstance(got, list) or not all(
            isinstance(p, dict) and set(p) >= {"id", "count"} for p in got):
        return False
    counts = want["counts"]
    best = sorted(counts.values(), reverse=True)
    if want["n"] > 0:
        best = best[:want["n"]]
    ids = [p["id"] for p in got]
    return ([p["count"] for p in got] == best
            and len(set(ids)) == len(ids)
            and all(counts.get(p["id"]) == p["count"] for p in got))


def bytes_needed(work, node: tuple) -> int:
    _, field, _, under = node
    leaves = query.leaves(under) if under is not None else []
    return work.field_bytes(field) + sum(
        work.row_bytes(f, r) for f, r in leaves)
