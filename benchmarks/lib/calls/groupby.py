"""The call GroupBy(Rows(field=f1), Rows(field=f2)[, Rows(field=f3)]
[, filter=<tree>]): node ("groupby", (f1, f2, ...), tree or None). The
reference tabulates it level by level: the filter's packed words, and-ed
with each row of a field that counts anything under them, go down to the
next field; the last field's rows are counted under what has come down.
Every count is the reference's one hook.

The server's result is a list of {"group": [{"field", "rowID"}, ...],
"count"}. It equals the reference where, as a map from the tuple of
(field, rowID) to the count, it is the reference's map of the groups that
are not empty, and no group is there twice.

In a mix: {"call": "GroupBy", "fields": [f1, f2, ...][, "filter": <tree>]}.
"""

from lib import query


def build(spec: dict, tree) -> tuple:
    under = tree(spec["filter"]) if "filter" in spec else None
    return ("groupby", tuple(spec["fields"]), under)


def to_pql(node: tuple) -> str:
    _, fields, under = node
    args = [f"Rows(field={f})" for f in fields]
    if under is not None:
        args.append(f"filter={query.tree_pql(under)}")
    return f"GroupBy({', '.join(args)})"


def _tabulate(ref, fields: tuple, words, prefix: tuple, out: dict) -> None:
    field = fields[0]
    for r in sorted(ref.data.fields[field]):
        n = ref.row_count(field, r, words)
        if n <= 0:
            continue
        if len(fields) == 1:
            out[prefix + ((field, r),)] = n
            continue
        # a thin row's words are made for this pass and dropped: a field of
        # many thin rows, all kept packed, would not fit
        below = ref.row(field, r, keep=ref.cheaper_packed(field, r))
        _tabulate(ref, fields[1:], below if words is None else below & words,
                  prefix + ((field, r),), out)


def answer(ref, node: tuple) -> dict:
    _, fields, under = node
    out: dict = {}
    _tabulate(ref, fields, ref.eval(under) if under is not None else None,
              (), out)
    return out


def same(got, want: dict) -> bool:
    if not isinstance(got, list):
        return False
    table: dict = {}
    for g in got:
        try:
            key = tuple((m["field"], m["rowID"]) for m in g["group"])
            n = g["count"]
        except (KeyError, TypeError):
            return False
        if key in table:
            return False
        table[key] = n
    return table == want


def bytes_needed(work, node: tuple) -> int:
    _, fields, under = node
    leaves = query.leaves(under) if under is not None else []
    return (sum(work.field_bytes(f) for f in fields)
            + sum(work.row_bytes(f, r) for f, r in leaves))
