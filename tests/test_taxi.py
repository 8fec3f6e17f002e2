"""The `taxi` deployment (benchmarks/configs/taxi) through the served path,
against the plain reference: upstream's Transportation example at full
record width — 20 set fields, one value a field a column — cut to 2 shards
and a 400-row grid so that the CPU serves it in seconds. The data is
loaded over import-roaring, as the benchmark loads it; every answer is
held against benchmarks/lib/reference.py (numpy on packed words, nothing
of pilosa_tpu), and a TopN against the exact Pairs order (count
descending, id ascending).

What is looked at besides the flight's own labels is the recount of a
field's small rows from their sorted columns (executor._pairs_entry): a
filter that defeats the threshold prune, an empty filter, n above the row
count, rows on both sides of the sparse threshold in one answer, ties at
the n-th place, `ids=`, `threshold=`, `tanimotoThreshold`, and a Set and a
Clear between two TopNs (the entry has to age out). Every one of these is
served by the new path and none falls to the dense walk for a row below
the threshold; what does fall to it whole is a server with the hybrid
representation off (`sparse-threshold = 0`), which test_pairs.py covers.
"""

import base64
import copy
import json
import os
import sys
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import byfile, datagen, query, reference, roaring_wire  # noqa: E402

from pilosa_tpu.constants import SHARD_WIDTH  # noqa: E402
from pilosa_tpu.server import Server  # noqa: E402

SEED = 2900000029
SHARDS = 2
GRID_ROWS = 400
GRID = "pickup_grid_id"


def post(uri, path, raw=b"", ctype="application/json"):
    req = urllib.request.Request(uri + path, data=raw, method="POST",
                                 headers={"Content-Type": ctype})
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read())


def ask(server, pql):
    return post(server.uri, "/index/taxi/query", pql.encode(),
                "text/plain")["results"][0]


def debug_vars(server):
    with urllib.request.urlopen(server.uri + "/debug/vars", timeout=60) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def taxi(tmp_path_factory):
    with open(os.path.join(BENCH, "configs", "taxi", "config.json")) as fh:
        config = json.load(fh)
    config = copy.deepcopy(config)
    for spec in config["fields"]:
        if spec["rows"] == 10000:
            spec["rows"] = GRID_ROWS
    data = datagen.make(config, SEED, shards=SHARDS)
    s = Server(str(tmp_path_factory.mktemp("taxi") / "n"), port=0).open()
    try:
        post(s.uri, "/index/taxi", b"{}")
        for name in data.fields:
            post(s.uri, f"/index/taxi/field/{name}", json.dumps(
                {"options": data.options[name]}).encode())
            rows = data.fields[name]
            for shard in range(SHARDS):
                body = roaring_wire.fragment_payload(
                    [(r, rows[r].shard_piece(shard)) for r in sorted(rows)])
                post(s.uri, f"/index/taxi/field/{name}/import-roaring/"
                     f"{shard}", json.dumps({"views": {
                         "standard": base64.b64encode(body).decode()}}
                     ).encode())
        with open(os.path.join(BENCH, "traffic", "flight.json")) as fh:
            mix = json.load(fh)
        yield {"server": s, "data": data, "mix": mix,
               "ref": reference.Reference(data)}
    finally:
        s.close()


def counts_under(taxi, field, tree):
    """{row: |row ∩ tree|} of every row of the field, by the reference."""
    ref = taxi["ref"]
    words = ref.eval(tree) if tree is not None else None
    return {r: ref.row_count(field, r, words)
            for r in taxi["data"].fields[field]}


def pairs_of(counts: dict, n=None) -> list:
    """The exact Pairs: count descending, id ascending, nought left out."""
    out = sorted(((c, r) for r, c in counts.items() if c > 0),
                 key=lambda cr: (-cr[0], cr[1]))
    return [{"id": r, "count": c} for c, r in (out[:n] if n else out)]


def rare(taxi, field):
    """The field's row with the fewest bits."""
    rows = taxi["data"].fields[field]
    return min(rows, key=lambda r: (rows[r].count(), r))


# ------------------------------------------------------- the flight's labels

LABELS = ["q1", "q2", "q3", "q4", "grid_pickup", "grid_drop", "count"]


@pytest.mark.parametrize("label", LABELS)
def test_flight_label_equals_reference(taxi, label):
    gen = byfile.load("lib/generators", "flight").Traffic(
        taxi["mix"], taxi["data"], SEED)
    reqs = [r for r in gen.warmup() if r["label"] == label][:2]
    assert reqs, label
    for req in reqs:
        got = post(taxi["server"].uri, "/index/taxi/query",
                   req["pql"].encode(), "text/plain")["results"]
        want = query.answer(taxi["ref"], req["ast"])
        assert query.same(req["ast"], got, want), req["pql"]
        if req["ast"][0] == "topn":
            # stronger than the benchmark's rule: the order among ties too
            _, field, n, under = req["ast"]
            assert got[0] == pairs_of(counts_under(taxi, field, under), n)


# ---------------------------------------------- the recount's awkward cases


def grid_cases(taxi):
    pc, yr = rare(taxi, "passenger_count"), rare(taxi, "pickup_year")
    selective = ("intersect", (("row", "passenger_count", pc),
                               ("row", "pickup_year", yr)))
    wide = ("row", "cab_type", 0)
    return {
        # a filter of a few hundred columns: the n-th best stays below
        # every cached count, so the prune never stops the walk
        "defeats-the-prune": (selective, 10),
        "wide-filter": (wide, 10),
        "n-above-the-row-count": (wide, 5 * GRID_ROWS),
        "n-unlimited": (selective, None),
        # counts of 1 and 2 on most rows: the n-th place is a tie
        "ties-at-the-nth-place": (selective, 25),
    }


@pytest.mark.parametrize("case", ["defeats-the-prune", "wide-filter",
                                  "n-above-the-row-count", "n-unlimited",
                                  "ties-at-the-nth-place"])
def test_grid_topn_is_the_exact_pairs(taxi, case):
    tree, n = grid_cases(taxi)[case]
    before = debug_vars(taxi["server"])
    got = ask(taxi["server"], f"TopN({GRID}, {query.tree_pql(tree)}"
              + (f", n={n})" if n else ")"))
    want = pairs_of(counts_under(taxi, GRID, tree), n)
    assert got == want
    if case == "ties-at-the-nth-place":
        assert want[n - 1]["count"] == want[n - 2]["count"]
    after = debug_vars(taxi["server"])
    if case in ("defeats-the-prune", "n-unlimited"):
        # rows on both sides of the threshold in one answer: the dense
        # rows through stacked planes, the others in one launch
        assert after["topnPairsRecounts"] == before["topnPairsRecounts"] + 1
        assert after["topnRecountRows"] > before["topnRecountRows"]
        bits = taxi["data"].fields[GRID]
        dense = sum(1 for r in bits
                    if bits[r].bits_per_shard().max() > 4096)
        assert 0 < dense < GRID_ROWS
        assert (after["topnRecountRows"] - before["topnRecountRows"]
                == dense)


@pytest.mark.parametrize("filt", ["disjoint", "no-such-row"])
def test_empty_filter(taxi, filt):
    pql = {"disjoint": "Intersect(Row(cab_type=0), Row(cab_type=1))",
           "no-such-row": "Row(passenger_count=77)"}[filt]
    assert ask(taxi["server"], f"TopN({GRID}, {pql}, n=10)") == []


def test_ids_on_both_sides_of_the_threshold(taxi):
    rows = taxi["data"].fields[GRID]
    by_size = sorted(rows, key=lambda r: -rows[r].count())
    ids = by_size[:3] + by_size[-3:] + [by_size[GRID_ROWS // 2]]
    tree = ("row", "passenger_count", 2)
    got = ask(taxi["server"], f"TopN({GRID}, {query.tree_pql(tree)}, "
              f"ids={json.dumps(sorted(ids))})")
    counts = counts_under(taxi, GRID, tree)
    assert got == pairs_of({r: counts[r] for r in ids})


def test_threshold(taxi):
    tree = ("row", "passenger_count", 1)
    counts = counts_under(taxi, GRID, tree)
    floor = sorted(counts.values())[GRID_ROWS // 2]   # cuts small rows too
    assert 0 < floor <= 4096
    got = ask(taxi["server"],
              f"TopN({GRID}, {query.tree_pql(tree)}, n=300, "
              f"threshold={floor})")
    assert got == pairs_of({r: c for r, c in counts.items()
                            if c >= floor}, 300)


@pytest.mark.parametrize("percent", [20, 34])
def test_tanimoto_threshold(taxi, percent):
    """The reference's rule on exact counts: a row stays where
    100 |row ∩ src| > T (|row| + |src| - |row ∩ src|), strictly. The
    source is the union of three grid rows of about one size, the least
    of the dense ones and the two fullest of the small ones, so each is a
    third of it and rows on both sides of the threshold pass or fail by
    the percentage."""
    rows = taxi["data"].fields[GRID]
    fullest = {r: int(rows[r].bits_per_shard().max()) for r in rows}
    dense = min((r for r in rows if fullest[r] > 4096),
                key=lambda r: rows[r].count())
    small = sorted((r for r in rows if fullest[r] <= 4096),
                   key=lambda r: -rows[r].count())[:2]
    src = ("union", tuple(("row", GRID, r) for r in [dense] + small))
    inter = counts_under(taxi, GRID, src)
    scount = taxi["ref"].count(taxi["ref"].eval(src), None)
    keep = {r: c for r, c in inter.items()
            if 100 * c > percent * (rows[r].count() + scount - c)}
    assert set(keep) <= {dense, *small} and keep
    if percent == 20:
        assert set(keep) == {dense, *small}
    else:
        assert len(keep) < 3
    got = ask(taxi["server"], f"TopN({GRID}, {query.tree_pql(src)}, n=50, "
              f"tanimotoThreshold={percent})")
    assert got == pairs_of(keep, 50)


def test_set_and_clear_age_the_entry_out(taxi):
    s = taxi["server"]
    pc = rare(taxi, "passenger_count")
    tree = ("row", "passenger_count", pc)
    pql = f"TopN({GRID}, {query.tree_pql(tree)}, n=0)"
    counts = counts_under(taxi, GRID, tree)
    assert ask(s, pql) == pairs_of(counts)
    # a small row of the grid that holds none of the filter's columns,
    # and a column of the filter
    rows = taxi["data"].fields[GRID]
    row = next(r for r in sorted(rows, key=lambda r: rows[r].count())
               if counts[r] == 0)
    col = int(taxi["data"].fields["passenger_count"][pc].cols[0])
    assert not np.isin(col, rows[row].cols)
    built = debug_vars(s)["pairsEntriesBuilt"]
    assert ask(s, f"Set({col}, {GRID}={row})") is True
    assert ask(s, pql) == pairs_of({**counts, row: 1})
    assert debug_vars(s)["pairsEntriesBuilt"] == built + 1
    assert ask(s, f"Clear({col}, {GRID}={row})") is True
    assert ask(s, pql) == pairs_of(counts)
    assert debug_vars(s)["pairsEntriesBuilt"] == built + 2
    kinds = debug_vars(s)["deviceResidency"]["by_kind"]
    assert kinds["pairs"]["entries"] >= 1
    assert col < SHARDS * SHARD_WIDTH
