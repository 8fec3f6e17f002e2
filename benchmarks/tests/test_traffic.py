"""The request generators: `trees` on the mix the benchmark ships (its
stream pinned to the digests taken on the parent tree, before the
generator became a file of its own), and `flight` on a mix made here."""

import collections
import hashlib
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from lib import byfile, datagen, query

traffic = byfile.load("lib/generators", "trees")


@pytest.fixture(scope="module")
def cell():
    with open(os.path.join(BENCH, "configs", "segmentation",
                           "config.json")) as fh:
        data = datagen.make(json.load(fh), 23, shards=2)
    with open(os.path.join(BENCH, "traffic", "adhoc.json")) as fh:
        return data, json.load(fh)


def stream(data, mix, seed, n):
    gen = traffic.Traffic(mix, data, seed)
    return gen.warmup(), [gen.take() for _ in range(n)]


def test_same_seed_same_stream_and_every_seed_the_same_sizes(cell):
    data, mix = cell
    n = 2 * mix["templates"]
    warm_a, a = stream(data, mix, 2_500_000_003, n)
    warm_b, b = stream(data, mix, 2_500_000_003, n)
    _, c = stream(data, mix, 8, n)
    assert [q["pql"] for q in a] == [q["pql"] for q in b]
    assert [q["pql"] for q in warm_a] == [q["pql"] for q in warm_b]
    assert [q["pql"] for q in a] != [q["pql"] for q in c]
    sizes = lambda qs: collections.Counter(  # noqa: E731
        len(query.leaves(q["ast"][1])) for q in qs)
    # every seed sends the same set of tree sizes, in another order, pass
    # by pass over the templates
    for i in range(0, n, mix["templates"]):
        assert sizes(a[i:i + mix["templates"]]) == \
            sizes(c[i:i + mix["templates"]])
    assert [q["label"] for q in a] != [q["label"] for q in c]
    assert all(q["label"] == str(len(query.leaves(q["ast"][1]))) for q in a)
    assert len({q["label"] for q in a}) > 12     # adhoc: 19 leaf counts
    assert all(set(q) == {"pql", "ast", "label"} for q in a)


def test_trees_are_the_random_query_tools(cell):
    data, mix = cell
    gen = traffic.Traffic(mix, data, 5)
    leaves = np.array([traffic.n_leaves(t) for t in gen.templates])
    assert leaves.max() <= (mix["tree"]["max_args"] - 1) ** (
        mix["tree"]["max_depth"] - 1)
    assert 0.15 < np.mean(leaves == 1) < 0.35      # one in four is a leaf
    assert 6.5 < leaves.mean() < 10                # 8.2 expected

    def check(t, depth):
        if t[0] == "leaf":
            assert 0.0 <= t[1] < 1.0
            return
        assert depth > 1 and t[0] in ("difference", "intersect", "union")
        assert 2 <= len(t[1]) <= mix["tree"]["max_args"] - 1
        for c in t[1]:
            check(c, depth - 1)

    for t in gen.templates:
        check(t, mix["tree"]["max_depth"])
    req = gen.take()
    assert req["pql"] == query.to_pql(req["ast"])
    assert req["pql"].startswith("Count(")
    assert int(req["label"]) == len(query.leaves(req["ast"][1]))


def test_every_seed_the_same_operand_sizes_and_rows_uniform(cell):
    data, mix = cell
    rows = data.fields[mix["field"]]
    cls = {r: traffic.size_class(rows[r]) for r in rows}

    def shapes(seed, skip):
        gen = traffic.Traffic(mix, data, seed)
        reqs = [gen.take() for _ in range(skip + mix["templates"])][skip:]

        def shape(t):
            return cls[t[2]] if t[0] == "row" else (
                t[0], tuple(shape(c) for c in t[1]))
        return collections.Counter(shape(q["ast"][1]) for q in reqs), reqs

    a, reqs_a = shapes(41, 0)
    b, reqs_b = shapes(42, mix["templates"])    # another seed, another pass
    assert a == b and len(a) > 50
    assert {q["pql"] for q in reqs_a} != {q["pql"] for q in reqs_b}
    # a leaf's row is uniform over the field's rows: one population holds
    # some 8 leaves a template (836 in adhoc's 100), so count the rows'
    # classes over many populations instead
    n = collections.Counter(cls.values())
    drawn = collections.Counter()
    for k in range(30):
        gen = traffic.Traffic(dict(mix, template_seed=k), data, 1)
        for _ in range(mix["templates"]):
            drawn.update(cls[r] for _, r in query.leaves(gen.take()["ast"][1]))
    total = sum(drawn.values())
    for c, rows_in_class in n.items():
        assert abs(drawn[c] / total - rows_in_class / len(cls)) < 0.02


def test_warmup_is_the_mix_with_other_rows(cell):
    data, mix = cell
    gen = traffic.Traffic(mix, data, 31)
    warm = gen.warmup()
    assert len(warm) == mix["warmup_requests"]
    window = {gen.take()["pql"] for _ in range(2 * mix["templates"])}
    big = [q["pql"] for q in warm if len(query.leaves(q["ast"][1])) > 2]
    assert not window & set(big)


# sha256 over the warm-up's 200 and the window's first 500 request texts,
# one a line, on datagen.make(segmentation, seed, shards=2): taken on the
# parent tree (b998f37), when the generator was lib/traffic.py
PARENT_DIGESTS = {
    2_800_000_011:
        "94bb8ae5c98ef3bd794fd76e8108c5c95667c232b649cb236dc91ed4e9a7799d",
    41: "52758590661d0773e94975d323f7d968593561376ed3b43c5ca7ce4744efb92c",
}


@pytest.mark.parametrize("seed", sorted(PARENT_DIGESTS))
def test_adhoc_stream_is_byte_for_byte_the_parents(seed):
    with open(os.path.join(BENCH, "configs", "segmentation",
                           "config.json")) as fh:
        data = datagen.make(json.load(fh), seed, shards=2)
    with open(os.path.join(BENCH, "traffic", "adhoc.json")) as fh:
        mix = json.load(fh)
    assert "generator" not in mix       # the default is what it always was
    warm, window = stream(data, mix, seed, 500)
    h = hashlib.sha256()
    for q in warm[:200] + window:       # the warm-up has grown since: 600
        h.update(q["pql"].encode() + b"\n")
    assert h.hexdigest() == PARENT_DIGESTS[seed]


# -- the flight generator ---------------------------------------------------

FLIGHT_CFG = {"shards": 2, "fields": [
    {"name": "cab", "kind": "categorical", "rows": 3, "first_id": 1,
     "weights": [6, 3, 1]},
    {"name": "pc", "kind": "categorical", "rows": 6,
     "value_exponent": 1.5, "value_ratio": 0.02}]}
PC = {"row": {"field": "pc", "draw": "uniform"}}
FLIGHT = {
    "generator": "flight", "clients": 2, "warmup_requests": 7,
    "check_sample": 10, "check_min": 1,
    "queries": [
        {"call": "TopN", "label": "q1", "field": "cab", "n": 2},
        {"call": "TopN", "label": "q2", "weight": 3, "field": "cab", "n": 2,
         "filter": PC},
        {"call": "GroupBy", "label": "q3", "fields": ["pc", "cab"],
         "filter": {"union": [PC, {"row": {"field": "cab", "id": 3}}]}},
        {"call": "Count", "weight": 2, "tree": {"difference": [
            {"row": {"field": "cab", "draw": "by_size"}}, PC]}}]}


@pytest.fixture(scope="module")
def flight_data():
    return datagen.make(FLIGHT_CFG, 19)


def flight_stream(data, seed, n):
    gen = byfile.load("lib/generators", "flight").Traffic(FLIGHT, data, seed)
    return gen, gen.warmup(), [gen.take() for _ in range(n)]


def test_flight_sends_weighted_passes_with_slots_drawn_afresh(flight_data):
    gen, warm, a = flight_stream(flight_data, 2_900_000_001, 7 * 40)
    _, warm_b, b = flight_stream(flight_data, 2_900_000_001, 7 * 40)
    _, _, c = flight_stream(flight_data, 6, 7 * 40)
    # the walk (q1 once, q2 and q3 the six rows of pc, Count max(3, 6))
    # and then the 7 drawn
    assert gen.label_key == "by_query" and len(warm) == 1 + 6 + 6 + 6 + 7
    assert [q["pql"] for q in a] == [q["pql"] for q in b]
    assert [q["pql"] for q in warm] == [q["pql"] for q in warm_b]
    assert [q["pql"] for q in a] != [q["pql"] for q in c]
    # every pass holds each query `weight` times, whatever the seed
    for qs in (a, c):
        for i in range(0, len(qs), 7):
            assert collections.Counter(q["label"] for q in qs[i:i + 7]) == {
                "q1": 1, "q2": 3, "q3": 1, "Count": 2}
    assert [q["label"] for q in a] != [q["label"] for q in c]
    assert all(set(q) == {"pql", "ast", "label"} for q in a)
    # a slot is drawn each time it is sent: q2 meets every row of pc, and
    # its text is not one fixed string the result cache could answer
    q2 = {q["pql"] for q in a if q["label"] == "q2"}
    assert q2 == {f"TopN(cab, Row(pc={k}), n=2)" for k in range(6)}
    assert {q["pql"] for q in a if q["label"] == "q1"} == {"TopN(cab, n=2)"}
    q3 = next(q for q in a if q["label"] == "q3")
    assert q3["ast"][0] == "groupby" and q3["ast"][1] == ("pc", "cab")
    assert q3["pql"].startswith(
        "GroupBy(Rows(field=pc), Rows(field=cab), filter=Union(Row(pc=")
    assert q3["pql"].endswith(", Row(cab=3)))")
    for q in a[:50]:
        assert q["pql"] == query.to_pql(q["ast"])


def test_flight_draws_a_row_by_its_size(flight_data):
    _, _, reqs = flight_stream(flight_data, 8, 7 * 400)
    drawn = collections.Counter(
        q["ast"][1][1][0][2] for q in reqs if q["label"] == "Count")
    total = sum(drawn.values())
    for row_id, share in ((1, 0.6), (2, 0.3), (3, 0.1)):
        assert abs(drawn[row_id] / total - share) < 0.06


def test_flight_refuses_what_it_cannot_say(flight_data):
    flight = byfile.load("lib/generators", "flight")
    with pytest.raises(FileNotFoundError, match="lib/calls/median.py"):
        flight.Traffic(dict(FLIGHT, queries=[{"call": "Median"}]),
                       flight_data, 1)
    bad = dict(FLIGHT, queries=[{"call": "Count", "tree": {
        "row": {"field": "cab", "draw": "heaviest"}}}])
    with pytest.raises(ValueError):
        flight.Traffic(bad, flight_data, 1).take()


def slots_of(spec: dict) -> list:
    """The field of every drawn slot of a mix's query."""
    def under(doc):
        (kind, body), = doc.items()
        if kind == "row":
            return [body["field"]] if "draw" in body else []
        return [f for c in body for f in under(c)]
    doc = spec.get("filter") or spec.get("tree")
    return under(doc) if doc else []


def rows_named(ast) -> set:
    tree = ast[1] if ast[0] == "count" else ast[-1]
    return set(query.leaves(tree)) if tree is not None else set()


def walk_names_every_value(mix: dict, data, walk: list) -> None:
    for spec in mix["queries"]:
        label = spec.get("label", spec["call"])
        mine = [q for q in walk if q["label"] == label]
        named = set().union(*(rows_named(q["ast"]) for q in mine))
        slots = slots_of(spec)
        assert len(mine) == max([len(data.fields[f]) for f in slots],
                                default=1), label
        for field in slots:
            assert {r for f, r in named if f == field} == set(
                data.fields[field]), (label, field)


def test_flight_warmup_names_every_value_of_every_slot(flight_data):
    gen, warm, _ = flight_stream(flight_data, 2_900_000_001, 0)
    walk = warm[:-FLIGHT["warmup_requests"]]
    walk_names_every_value(FLIGHT, flight_data, walk)
    # the walk is the same whatever the seed; the drawn part is not
    _, other, _ = flight_stream(flight_data, 6, 0)
    n = len(walk)
    assert [q["pql"] for q in other[:n]] == [q["pql"] for q in walk]
    assert [q["pql"] for q in other[n:]] != [q["pql"] for q in warm[n:]]
    assert all(q["pql"] == query.to_pql(q["ast"]) for q in walk)


def test_the_shipped_flight_warms_up_every_passenger_count_year_and_cab():
    """taxi.flight (PR 36): a passenger count drawn `by_size` once in a
    thousand is a sparse program shape of its own; the 66 drawn requests
    met none and it compiled inside the window, six times a run."""
    with open(os.path.join(BENCH, "configs", "taxi", "config.json")) as fh:
        data = datagen.make(json.load(fh), 36, shards=1)
    with open(os.path.join(BENCH, "traffic", "flight.json")) as fh:
        mix = json.load(fh)
    gen = byfile.load("lib/generators", "flight").Traffic(mix, data, 36)
    walk = gen.warmup()[:-mix["warmup_requests"]]
    walk_names_every_value(mix, data, walk)
    assert {f for spec in mix["queries"] for f in slots_of(spec)} == {
        "passenger_count", "pickup_year", "cab_type"}
    assert len(walk) == 1 + 9 + 1 + 1 + 9 + 8 + 9
