"""The plain reference against Python sets, the control against the
reference, and the work counter's bytes."""

import numpy as np
import pytest

from lib import control, datagen, query, reference, work

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
    assert ref.answer(("count", tree)) == len(((a1 | a5) - (a2 & b0)) - a5)
    assert ref.answer(("count", ("union", (("row", "a", 1), ("row", "a", 2))))
                      ) == len(a1 | a2)
    assert query.to_pql(("count", tree)) == (
        "Count(Difference(Union(Row(a=1), Row(a=5)), "
        "Intersect(Row(a=2), Row(b=0)), Row(a=5)))")
    with pytest.raises(ValueError):
        ref.answer(("count", ("xor", (("row", "a", 1), ("row", "a", 2)))))


def test_control_breaks_exactness(data):
    exact = reference.Reference(data)
    ctrl = reference.Reference(data, control.sampled_count(data.n_shards))
    q = ("count", ("intersect", (("row", "a", 1), ("row", "b", 0))))
    assert ctrl.answer(q) != exact.answer(q)
    assert abs(ctrl.answer(q) - exact.answer(q)) < 0.05 * exact.answer(q)


def test_bytes_needed(data):
    need = work.Work(data)
    per = data.fields["a"][6].bits_per_shard()
    assert need.row_bytes("a", 6) == int(np.minimum(131072, 4 * per).sum())
    dense = max(data.fields["b"], key=lambda r: data.fields["b"][r].count())
    assert need.row_bytes("b", dense) == 2 * 131072  # a plane a shard
    q = ("count", ("union", (("row", "b", dense), ("row", "a", 6))))
    assert need.bytes_needed(q) == (need.row_bytes("b", dense)
                                    + need.row_bytes("a", 6))
