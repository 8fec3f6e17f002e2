"""The general traffic generator on the mix the benchmark ships."""

import collections
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from lib import datagen, query, traffic


@pytest.fixture(scope="module")
def cell():
    with open(os.path.join(BENCH, "configs", "segmentation",
                           "config.json")) as fh:
        data = datagen.make(json.load(fh), 23, shards=2)
    return data, traffic.load_mix(os.path.join(BENCH, "traffic",
                                               "adhoc.json"))


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
        q["leaves"] for q in qs)
    # every seed sends the same set of tree sizes, in another order, pass
    # by pass over the templates
    for i in range(0, n, mix["templates"]):
        assert sizes(a[i:i + mix["templates"]]) == \
            sizes(c[i:i + mix["templates"]])
    assert [q["leaves"] for q in a] != [q["leaves"] for q in c]


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
    assert len(query.leaves(req["ast"][1])) == req["leaves"]


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
    # a leaf's row is uniform over the field's rows: 100 templates hold 900
    # leaves, so count the rows' classes over many populations instead
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
    big = [q["pql"] for q in warm if q["leaves"] > 2]
    assert not window & set(big)
