"""The `chem-similarity` deployment (benchmarks/configs/chem-similarity)
through the served path, against a plain numpy Tanimoto search: upstream's
chemical-similarity example, TopN(fingerprint, Row(fingerprint=q),
tanimotoThreshold=T), cut to 3,000 molecules so that the CPU serves it in
seconds. The molecules are the benchmark's own `fingerprint` data kind
(families of analogues, 2,048 positions, sizes that vary); they are loaded
over import-roaring, as the benchmark loads them. Beside them the field
holds rows made for the edges of the rule:

  - subsets and supersets of a 40-bit query row whose Tanimoto is exactly
    T/100 (dropped: the rule is strict), just above it, and just inside
    and just outside the band (|q| T/100, |q| 100/T);
  - 25 rows of 38 of another query's 40 bits: a tie at the n-th place;
  - a 5,000-bit query and a 4,800-bit row above the sparse threshold
    beside rows of 2,400-4,000 bits under it, so that the dense walk and
    the field's pairs entry meet in one heap.

Every answer has to be the exact Pairs: count descending, id ascending.
The band's counters (`topnBandIn`, `topnBandKept`) and span (`topn.band`)
are held to what the band predicts.
"""

import base64
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

from lib import datagen, roaring_wire  # noqa: E402

from pilosa_tpu.server import Server  # noqa: E402

SEED = 3700000037
MOLECULES = 3000
FIELD = "fingerprint"

# crafted rows, from this id on
Q, Q_TIE, Q_DENSE = 10_000, 10_001, 10_002
TIED = range(10_100, 10_125)
EDGE = 10_200


def post(uri, path, raw=b"", ctype="application/json"):
    req = urllib.request.Request(uri + path, data=raw, method="POST",
                                 headers={"Content-Type": ctype})
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read())


def ask(server, pql):
    return post(server.uri, "/index/chem/query", pql.encode(),
                "text/plain")["results"][0]


def debug_vars(server):
    with urllib.request.urlopen(server.uri + "/debug/vars", timeout=60) as r:
        return json.loads(r.read())


def crafted() -> tuple:
    """{row id: sorted columns} of the rows made for the rule's edges, and
    which of them sit where: `edges[T]` the subset of exactly T/100, the
    one just above it, and the supersets just inside and just outside
    the band."""
    q = np.arange(1000, 1040)                       # |q| = 40
    far = np.arange(20_000, 20_100)                 # columns no molecule has
    rows = {Q: q}
    edges: dict = {}
    rid = EDGE
    for t in (50, 70, 90):
        exact = 40 * t // 100                       # 20, 28, 36 of 40
        inside = int(np.ceil(40 * 100 / t)) - 1     # 79, 57, 44
        made = {"exact": q[:exact], "above": q[:exact + 1],
                "inside": np.concatenate([q, far[:inside - 40]]),
                "outside": np.concatenate([q, far[:inside + 1 - 40]])}
        edges[t] = {}
        for what, cols in made.items():
            rows[rid] = cols
            edges[t][what] = rid
            rid += 1
    # inside every band, dropped by the mask: 28 of q and 12 elsewhere
    rows[rid] = np.concatenate([q[:28], far[50:62]])
    tie = np.arange(1500, 1540)
    rows[Q_TIE] = tie
    for k, r in enumerate(TIED):
        rows[r] = np.delete(tie, [k, k + 1])        # 38 bits each
    # above the sparse threshold (4,096 bits a shard) and under it
    big = np.arange(30_000, 35_000)                 # 5,000 bits, dense
    rows[Q_DENSE] = big
    rows[rid + 1] = np.concatenate([big[:4500], np.arange(40_000, 40_300)])
    rows[rid + 2] = big[:3500]                      # 0.7 of big
    rows[rid + 3] = big[500:4500]                   # 0.8
    rows[rid + 4] = big[:2400]                      # outside every band
    return {r: np.sort(c).astype(np.uint32) for r, c in rows.items()}, edges


@pytest.fixture(scope="module")
def chem(tmp_path_factory):
    with open(os.path.join(BENCH, "configs", "chem-similarity",
                           "config.json")) as fh:
        config = json.load(fh)
    (spec,) = config["fields"]
    spec = dict(spec, rows=MOLECULES)
    config = dict(config, fields=[spec])
    data = datagen.make(config, SEED, shards=1)
    rows = {r: row.cols for r, row in data.fields[FIELD].items()}
    made, edges = crafted()
    assert not set(made) & set(rows)
    rows.update(made)
    s = Server(str(tmp_path_factory.mktemp("chem") / "n"), port=0).open()
    try:
        post(s.uri, "/index/chem", b"{}")
        options = dict(spec["options"], cacheSize=20_000)
        post(s.uri, f"/index/chem/field/{FIELD}",
             json.dumps({"options": options}).encode())
        body = roaring_wire.fragment_payload(
            [(r, rows[r]) for r in sorted(rows)])
        post(s.uri, f"/index/chem/field/{FIELD}/import-roaring/0",
             json.dumps({"views": {"standard": base64.b64encode(
                 body).decode()}}).encode())
        yield {"server": s, "rows": rows, "edges": edges,
               "molecules": sorted(data.fields[FIELD])}
    finally:
        s.close()


def tanimoto_pairs(rows: dict, q: int, t: int, n: int) -> list:
    """The plain search: |r ∩ q| for every row, kept where
    100 |r ∩ q| > T (|r| + |q| - |r ∩ q|), as Pairs."""
    qc = rows[q]
    out = []
    for r, cols in rows.items():
        inter = np.intersect1d(cols, qc, assume_unique=True).size
        if inter and 100 * inter > t * (cols.size + qc.size - inter):
            out.append((-inter, r))
    out.sort()
    return [{"id": r, "count": -c} for c, r in (out[:n] if n else out)]


def similar(chem, q: int, t: int, n: int) -> list:
    return ask(chem["server"], f"TopN({FIELD}, Row({FIELD}={q}), n={n}, "
               f"tanimotoThreshold={t})")


# ------------------------------------------------- molecules of the library


@pytest.mark.parametrize("n", [20, 0])
@pytest.mark.parametrize("t", [50, 70, 90])
def test_molecules_equal_the_plain_search(chem, t, n):
    mols = chem["molecules"]
    hits = 0
    for q in (mols[7], mols[1234], mols[2999]):
        want = tanimoto_pairs(chem["rows"], q, t, n)
        assert similar(chem, q, t, n) == want, (q, t, n)
        assert want[0]["id"] == q or want[0]["count"] == want[1]["count"]
        hits += len(want) - 1
    if t == 50:
        assert hits > 3     # analogues are found, not the query alone


# ------------------------------------------------------ the rule's edges


@pytest.mark.parametrize("t", [50, 70, 90])
def test_the_rule_is_strict_and_the_band_exact(chem, t):
    want = tanimoto_pairs(chem["rows"], Q, t, 0)
    got = similar(chem, Q, t, 0)
    assert got == want
    ids = {p["id"] for p in got}
    edge = chem["edges"][t]
    assert edge["exact"] not in ids        # Tanimoto exactly T/100
    assert edge["above"] in ids
    assert edge["inside"] in ids           # the band's last size
    assert edge["outside"] not in ids      # its first size outside


def test_a_tie_at_the_nth_place(chem):
    got = similar(chem, Q_TIE, 90, 20)
    whole = tanimoto_pairs(chem["rows"], Q_TIE, 90, 0)
    assert got == whole[:20]
    assert whole[19]["count"] == whole[20]["count"] == 38


@pytest.mark.parametrize("t,n", [(50, 20), (70, 0), (90, 20)])
def test_dense_walk_and_pairs_entry_meet_in_one_heap(chem, t, n):
    s = chem["server"]
    before = debug_vars(s)
    got = similar(chem, Q_DENSE, t, n)
    after = debug_vars(s)
    assert got == tanimoto_pairs(chem["rows"], Q_DENSE, t, n)
    sizes = {p["id"]: chem["rows"][p["id"]].size for p in got}
    if t < 90:
        # rows on both sides of the sparse threshold in one answer
        assert min(sizes.values()) <= 4096 < max(sizes.values())
        assert after["topnRecountRows"] > before["topnRecountRows"]
        assert (after["topnPairsRecounts"]
                == before["topnPairsRecounts"] + 1)
    else:
        assert list(sizes) == [Q_DENSE]


# ------------------------------------------------ the band's instruments


@pytest.mark.parametrize("t", [50, 70, 90])
def test_band_counters_and_span(chem, t):
    s, rows = chem["server"], chem["rows"]
    q = chem["molecules"][42]
    qn = rows[q].size
    sizes = np.array([c.size for c in rows.values()])
    kept = int(((100 * sizes > t * qn) & (t * sizes < 100 * qn)).sum())
    before = debug_vars(s)
    assert similar(chem, q, t, 20) == tanimoto_pairs(rows, q, t, 20)
    after = debug_vars(s)
    assert after["topnBandIn"] - before["topnBandIn"] == len(rows)
    assert after["topnBandKept"] - before["topnBandKept"] == kept
    band = [v["spans"]["byName"].get("topn.band", {"n": 0, "wallMs": 0.0})
            for v in (before, after)]
    assert band[1]["n"] == band[0]["n"] + 1
    assert band[1]["wallMs"] > band[0]["wallMs"]
    if t == 90:
        assert kept < 0.5 * len(rows)      # prunes most rows
    if t == 50:
        assert kept > 0.75 * len(rows)     # barely prunes


def test_no_band_without_a_threshold(chem):
    s = chem["server"]
    q = chem["molecules"][42]
    before = debug_vars(s)
    assert ask(s, f"TopN({FIELD}, Row({FIELD}={q}), n=5)")
    after = debug_vars(s)
    assert after["topnBandIn"] == before["topnBandIn"]
    assert (after["spans"]["byName"].get("topn.band", {}).get("n")
            == before["spans"]["byName"].get("topn.band", {}).get("n"))
