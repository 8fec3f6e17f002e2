"""The plain reference against Python sets, the control against the
reference, and the work counter's bytes."""

import numpy as np
import pytest

from lib import byfile, control, datagen, query, reference, work

CFG = {"shards": 2, "fields": [
    {"name": "a", "rows": 6, "first_id": 1, "set_bits_per_shard": 300_000,
     "row_exponent": 1.5, "row_ratio": 0.05,
     "column_exponent": 1.01, "column_ratio": 0.25},
    {"name": "b", "rows": 3, "set_bits_per_shard": 900_000,
     "row_exponent": 1.01, "row_ratio": 0.5,
     "column_exponent": 1.2, "column_ratio": 0.1}]}


@pytest.fixture(scope="module")
def data():
    return datagen.make(CFG, 17)


def as_set(data, field, row):
    return set(data.fields[field][row].cols.tolist())


def test_counts_match_python_sets(data):
    ref = reference.Reference(data)
    a1, a2, a5 = (as_set(data, "a", r) for r in (1, 2, 5))
    b0 = as_set(data, "b", 0)
    tree = ("difference", (("union", (("row", "a", 1), ("row", "a", 5))),
                           ("intersect", (("row", "a", 2), ("row", "b", 0))),
                           ("row", "a", 5)))
    assert query.answer(ref, ("count", tree)) == len(
        ((a1 | a5) - (a2 & b0)) - a5)
    assert query.answer(ref, ("count", ("union", (
        ("row", "a", 1), ("row", "a", 2))))) == len(a1 | a2)
    assert query.to_pql(("count", tree)) == (
        "Count(Difference(Union(Row(a=1), Row(a=5)), "
        "Intersect(Row(a=2), Row(b=0)), Row(a=5)))")
    with pytest.raises(ValueError):
        query.answer(ref, ("count", ("xor", (("row", "a", 1),
                                             ("row", "a", 2)))))


def test_control_breaks_exactness(data):
    exact = reference.Reference(data)
    ctrl = reference.Reference(data, control.sampled_count(data.n_shards))
    q = ("count", ("intersect", (("row", "a", 1), ("row", "b", 0))))
    got, want = query.answer(ctrl, q), query.answer(exact, q)
    assert got != want and abs(got - want) < 0.05 * want


def test_bytes_needed(data):
    need = work.Work(data)
    per = data.fields["a"][6].bits_per_shard()
    assert need.row_bytes("a", 6) == int(np.minimum(131072, 4 * per).sum())
    dense = max(data.fields["b"], key=lambda r: data.fields["b"][r].count())
    assert need.row_bytes("b", dense) == 2 * 131072  # a plane a shard
    q = ("count", ("union", (("row", "b", dense), ("row", "a", 6))))
    assert query.bytes_needed(need, q) == (need.row_bytes("b", dense)
                                           + need.row_bytes("a", 6))


# -- TopN and GroupBy -----------------------------------------------------

CAT = {"shards": 2, "fields": [
    {"name": "cab", "kind": "categorical", "rows": 3, "first_id": 1,
     "weights": [6, 3, 1]},
    {"name": "pc", "kind": "categorical", "rows": 5,
     "value_exponent": 1.5, "value_ratio": 0.05},
    {"name": "cell", "kind": "categorical", "rows": 40, "present": 0.5,
     "value_exponent": 1.1, "value_ratio": 0.1},
    # rows that overlap: a column may hold several values of `seg`
    {"name": "seg", "rows": 4, "set_bits_per_shard": 700_000,
     "row_exponent": 1.01, "row_ratio": 0.3,
     "column_exponent": 1.01, "column_ratio": 0.25}]}
FILTER = ("difference", (("union", (("row", "pc", 0), ("row", "pc", 3))),
                         ("row", "seg", 1)))


@pytest.fixture(scope="module")
def cat():
    return datagen.make(CAT, 29)


def sets_of(data, field):
    return {r: as_set(data, field, r) for r in data.fields[field]}


def filter_set(data):
    return (as_set(data, "pc", 0) | as_set(data, "pc", 3)) \
        - as_set(data, "seg", 1)


def test_topn_matches_a_pass_over_python_sets(cat):
    ref = reference.Reference(cat)
    for field in ("cab", "cell", "seg"):
        rows = sets_of(cat, field)
        got = query.answer(ref, ("topn", field, 3, None))
        assert got == {"n": 3, "counts": {r: len(s) for r, s in rows.items()
                                          if s}}
        under = filter_set(cat)
        got = query.answer(ref, ("topn", field, 2, FILTER))
        assert got["counts"] == {r: len(s & under) for r, s in rows.items()
                                 if s & under}
    assert query.to_pql(("topn", "cab", 3, None)) == "TopN(cab, n=3)"
    assert query.to_pql(("topn", "cell", 2, ("row", "pc", 4))) == \
        "TopN(cell, Row(pc=4), n=2)"


def test_groupby_matches_a_pass_over_python_sets(cat):
    ref = reference.Reference(cat)
    under = filter_set(cat)
    for fields, tree, keep in (
            (("pc", "cab"), None, None),
            (("seg", "pc"), None, None),            # overlapping rows
            (("cab", "seg", "pc"), FILTER, under),
            (("cell", "cab"), ("row", "seg", 2), as_set(cat, "seg", 2))):
        want = {}

        def walk(rest, cols, prefix):
            for r in sorted(cat.fields[rest[0]]):
                both = as_set(cat, rest[0], r) if cols is None else \
                    cols & as_set(cat, rest[0], r)
                if not both:
                    continue
                if len(rest) == 1:
                    want[prefix + ((rest[0], r),)] = len(both)
                else:
                    walk(rest[1:], both, prefix + ((rest[0], r),))

        walk(fields, keep, ())
        assert query.answer(ref, ("groupby", fields, tree)) == want
        assert len(want) > 3
    assert query.to_pql(("groupby", ("pc", "cab"), None)) == \
        "GroupBy(Rows(field=pc), Rows(field=cab))"
    assert query.to_pql(("groupby", ("pc", "cab", "cell"), ("row", "seg", 2))
                        ) == ("GroupBy(Rows(field=pc), Rows(field=cab), "
                              "Rows(field=cell), filter=Row(seg=2))")


def test_a_row_counts_the_same_packed_and_from_its_columns(cat):
    ref = reference.Reference(cat)
    words = ref.eval(FILTER)
    under = filter_set(cat)
    thin = min(cat.fields["cell"], key=lambda r: cat.fields["cell"][r].count())
    for field, row in (("cab", 1), ("cab", 3), ("cell", thin), ("seg", 0)):
        want = len(as_set(cat, field, row) & under)
        assert ref.row_count(field, row, words, packed=True) == want
        assert ref.row_count(field, row, words, packed=False) == want
        assert ref.row_count(field, row, words) == want
        assert ref.row_count(field, row) == cat.fields[field][row].count()
    # the cheaper form: cab=1 holds 0.6 of the columns (as a list 38 bytes
    # to a word's 8: packed), cell's thinnest under a hundredth (columns)
    assert ref.cheaper_packed("cab", 1)
    assert not ref.cheaper_packed("cell", thin)
    assert reference.exact_count(words, None) == len(under)
    one = cat.fields["cell"][thin].cols
    assert reference.exact_count(None, one) == one.size
    assert np.array_equal(reference.members(words, one),
                          [int(c in under) for c in one.tolist()])


TOP = {"n": 3, "counts": {4: 90, 5: 70, 6: 70, 7: 70, 8: 10}}


def pairs(*id_count):
    return [{"id": i, "count": c} for i, c in id_count]


@pytest.mark.parametrize("got,ok", [
    (pairs((4, 90), (5, 70), (6, 70)), True),
    (pairs((4, 90), (7, 70), (5, 70)), True),     # ties: any, in any order
    (pairs((4, 90), (6, 70), (7, 70)), True),
    (pairs((4, 90), (5, 71), (6, 70)), False),    # one count off by one
    (pairs((4, 90), (5, 70), (6, 69)), False),
    (pairs((4, 90), (5, 70), (5, 70)), False),    # an id twice
    (pairs((4, 90), (5, 70), (8, 70)), False),    # the count is not its own
    (pairs((4, 90), (5, 70), (9, 70)), False),    # a row that is not there
    (pairs((5, 70), (4, 90), (6, 70)), False),    # not most first
    (pairs((4, 90), (5, 70)), False),             # one short
    (pairs((4, 90), (5, 70), (6, 70), (7, 70)), False),
    ([{"id": 4}], False), ({"id": 4, "count": 90}, False), (3, False),
], ids=range(14))
def test_topn_comparison(got, ok):
    assert byfile.load("lib/calls", "topn").same(got, TOP) is ok
    assert query.same(("topn", "f", 3, None), [got], TOP) is ok
    assert query.same(("topn", "f", 3, None), [got, got], TOP) is False


def test_topn_comparison_where_fewer_rows_count_than_n():
    topn = byfile.load("lib/calls", "topn")
    few = {"n": 10, "counts": {1: 5, 2: 3}}
    assert topn.same(pairs((1, 5), (2, 3)), few)
    assert not topn.same(pairs((1, 5)), few)
    assert topn.same(pairs((1, 5), (2, 3)), dict(few, n=0))   # n=0: all
    assert topn.same([], {"n": 4, "counts": {}})


def group(count, *members):
    return {"group": [{"field": f, "rowID": r} for f, r in members],
            "count": count}


GROUPS = {(("a", 1), ("b", 1)): 5, (("a", 1), ("b", 2)): 7,
          (("a", 2), ("b", 2)): 1}
G = [group(5, ("a", 1), ("b", 1)), group(7, ("a", 1), ("b", 2)),
     group(1, ("a", 2), ("b", 2))]


@pytest.mark.parametrize("got,ok", [
    (G, True), (G[::-1], True),
    (G[:2], False),                                          # a group missing
    (G + [group(2, ("a", 2), ("b", 1))], False),             # one too many
    (G + [G[0]], False),                                     # a group twice
    ([group(6, ("a", 1), ("b", 1))] + G[1:], False),         # off by one
    ([group(5, ("b", 1), ("a", 1))] + G[1:], False),         # fields swapped
    ([{"count": 5}] + G[1:], False), (None, False),
], ids=range(9))
def test_groupby_comparison(got, ok):
    assert byfile.load("lib/calls", "groupby").same(got, GROUPS) is ok
    assert query.same(("groupby", ("a", "b"), None), [got], GROUPS) is ok


@pytest.mark.parametrize("got,ok", [
    ([12], True), ([13], False), ([True], False), ([[12]], False),
    (["12"], False), (12, False), ([12, 12], False), ([], False)])
def test_count_comparison(got, ok):
    assert query.same(("count", ("row", "a", 1)), got, 12) is ok


CALLS = [("count", FILTER), ("topn", "cab", 2, None),
         ("topn", "cell", 5, FILTER), ("groupby", ("pc", "cab"), None),
         ("groupby", ("cab", "cell"), FILTER)]


def as_reply(node, ans):
    """The reference's answer as the server would say it."""
    if node[0] == "count":
        return [ans]
    if node[0] == "topn":
        top = sorted(ans["counts"].items(), key=lambda kv: -kv[1])
        return [pairs(*top[:ans["n"]])]
    return [[group(n, *key) for key, n in ans.items()]]


@pytest.mark.parametrize("node", CALLS, ids=[c[0] for c in CALLS])
def test_control_reads_wrong_on_every_call(cat, node):
    exact = reference.Reference(cat)
    ctrl = reference.Reference(cat, control.sampled_count(cat.n_shards))
    want = query.answer(exact, node)
    assert query.same(node, as_reply(node, want), want)
    estimate = query.answer(ctrl, node)
    assert not query.same(node, as_reply(node, estimate), want)
    # an estimate, not nonsense: within a few per cent where counts are big
    if node[0] == "count":
        assert abs(estimate - want) < 0.05 * want


def test_bytes_needed_of_topn_and_groupby(cat):
    need = work.Work(cat)
    field = {f: sum(need.row_bytes(f, r) for r in cat.fields[f])
             for f in cat.fields}
    assert need.field_bytes("cab") == field["cab"]
    # cab's three rows are planes in both shards (the least holds a tenth
    # of the columns, 419 KB as a list); cell's thinnest is a list
    assert field["cab"] == 3 * 2 * 131072
    thin = min(cat.fields["cell"], key=lambda r: cat.fields["cell"][r].count())
    assert need.row_bytes("cell", thin) == 4 * cat.fields["cell"][thin].count()
    under = sum(need.row_bytes(f, r) for f, r in query.leaves(FILTER))
    assert query.bytes_needed(need, ("topn", "cell", 5, None)) == field["cell"]
    assert query.bytes_needed(need, ("topn", "cell", 5, FILTER)) == \
        field["cell"] + under
    assert query.bytes_needed(need, ("groupby", ("pc", "cab"), None)) == \
        field["pc"] + field["cab"]
    assert query.bytes_needed(need, ("groupby", ("cab", "cell", "pc"), FILTER)
                              ) == field["cab"] + field["cell"] \
        + field["pc"] + under
