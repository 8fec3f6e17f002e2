"""The pieces of the cell `chem-similarity.similar`: the data kind
`fingerprint`, the call `similar` (this configuration's plain reference)
and the generator `similar`, each on its own, then a small copy of the
configuration through the real server on the CPU, as tests/test_extend.py
runs its cells."""

import concurrent.futures
import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, REPO
from lib import byfile, control, datagen, query, reference

SEED = 3700000011
ROWS = 2_000


def config(rows=ROWS):
    with open(os.path.join(BENCH, "configs", "chem-similarity",
                           "config.json")) as fh:
        doc = json.load(fh)
    doc = copy.deepcopy(doc)
    doc["fields"][0]["rows"] = rows
    doc["fields"][0]["options"]["cacheSize"] = rows
    return doc


@pytest.fixture(scope="module")
def data():
    return datagen.make(config(), SEED, shards=1)


def test_the_shipped_configuration_keeps_the_source_scale():
    with open(os.path.join(BENCH, "configs", "chem-similarity",
                           "config.json")) as fh:
        doc = json.load(fh)
    (spec,) = doc["fields"]
    assert doc["shards"] == 1 and doc["reduced"] == {}
    assert spec["rows"] == 500_000 and spec["positions"] == 2048
    assert spec["options"]["cacheSize"] >= spec["rows"]
    assert {"positions", "cacheSize", "families", "position law",
            "bit counts", "motif share"} <= set(doc["assumed"])


@pytest.mark.parametrize("threads", [1, 3, 8])
def test_the_rows_do_not_depend_on_the_threads(threads):
    (spec,) = config(rows=15_000)["fields"]   # 150 families: three jobs
    with concurrent.futures.ThreadPoolExecutor(max_workers=threads) as pool:
        got = byfile.load("lib/data_kinds", "fingerprint").make_field(
            SEED, 0, spec, 1, pool)
    with concurrent.futures.ThreadPoolExecutor(max_workers=2) as pool:
        want = byfile.load("lib/data_kinds", "fingerprint").make_field(
            SEED, 0, spec, 1, pool)
    assert sorted(got) == list(range(15_000))
    assert all(np.array_equal(got[r].cols, want[r].cols) for r in want)


def test_the_law():
    rows = datagen.make(config(rows=20_000), SEED,
                        shards=1).fields["fingerprint"]   # 200 families
    sizes = np.array([rows[r].count() for r in rows])
    assert 44 <= sizes.mean() <= 52, sizes.mean()
    assert ((sizes >= 25) & (sizes <= 80)).mean() > 0.85
    cols = np.concatenate([rows[r].cols for r in rows])
    assert cols.max() < 2048
    # a few substructure bits lie in most molecules
    share = np.sort(np.bincount(cols, minlength=2048))[::-1] / len(rows)
    assert share[2] > 0.5 and share[200] < 0.1
    for r in list(rows)[:50]:
        c = rows[r].cols
        assert np.all(np.diff(c.astype(np.int64)) > 0)


def brute(rows: dict, q: int, t: int) -> dict:
    """Tanimoto over Python sets, row by row."""
    qs = set(rows[q].cols.tolist())
    out = {}
    for r, row in rows.items():
        rs = set(row.cols.tolist())
        inter = len(rs & qs)
        if inter and 100 * inter > t * len(rs | qs):
            out[r] = inter
    return out


@pytest.mark.parametrize("t", [50, 70, 90])
def test_the_reference_equals_a_brute_force_search(data, t):
    ref = reference.Reference(data)
    call = query.call_of("similar")
    ids = data.row_ids("fingerprint")
    found = 0
    for q in ids[::97]:
        want = brute(data.fields["fingerprint"], q, t)
        got = call.answer(ref, ("similar", "fingerprint", q, 20, t))
        assert got == {"n": 20, "counts": want}
        found += len(want) - 1
    if t == 70:
        assert found > 0


def test_same_and_the_control(data):
    call = query.call_of("similar")
    q = data.row_ids("fingerprint")[5]
    node = ("similar", "fingerprint", q, 3, 50)
    want = call.answer(reference.Reference(data), node)
    best = sorted(want["counts"].items(), key=lambda kv: (-kv[1], kv[0]))
    got = [{"id": r, "count": c} for r, c in best[:3]]
    assert call.same(got, want)
    assert not call.same(got[:2], want)
    assert not call.same([dict(got[0], count=got[0]["count"] + 1)]
                         + got[1:], want)
    broken = call.answer(reference.Reference(
        data, control.sampled_count(data.n_shards)), node)
    assert not call.same(got, broken)
    assert call.to_pql(node) == (f"TopN(fingerprint, Row(fingerprint={q}), "
                                 "n=3, tanimotoThreshold=50)")
    entry = {"field": "fingerprint", "n": 3, "threshold": 50,
             "row": {"row": {"field": "fingerprint", "id": q}}}
    assert call.build(entry, lambda doc: query.tree_from_json(
        doc, None)) == node


def test_bytes_needed_is_the_field_and_the_query_row(data):
    from lib import work
    w = work.Work(data)
    q = data.row_ids("fingerprint")[0]
    rows = data.fields["fingerprint"]
    assert query.bytes_needed(w, ("similar", "fingerprint", q, 20, 70)) == \
        4 * (sum(r.count() for r in rows.values()) + rows[q].count())


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(BENCH, "traffic", "similar.json")) as fh:
        return json.load(fh)


def test_the_shipped_mix(mix):
    assert mix["generator"] == "similar" and mix["clients"] == 8
    assert mix["n"] == 20 and mix["warmup_requests"] == 40
    assert mix["thresholds"] == {"70": 3, "50": 1, "90": 1}
    assert mix["check_min"] >= 30


def test_the_generator_draws_and_does_not_walk(data, mix):
    gen = byfile.load("lib/generators", "similar").Traffic(mix, data, SEED)
    rows = data.fields["fingerprint"]
    warm = gen.warmup()
    # the drawn requests and nothing else: no walk over the rows
    assert len(warm) == mix["warmup_requests"]
    assert all(w["ast"][2] in rows for w in warm)
    assert len({w["ast"][2] for w in warm}) > 0.9 * len(warm)
    window = [gen.take() for _ in range(5_000)]
    for req in warm + window:
        kind, field, q, n, t = req["ast"]
        assert (kind, field, n) == ("similar", "fingerprint", 20)
        assert req["label"] == f"T{t}"
        assert req["pql"] == query.to_pql(req["ast"])
    ts = [w["ast"][4] for w in window]
    for k in range(0, 5_000, 5):            # whole passes, 3:1:1
        assert sorted(ts[k:k + 5]) == [50, 70, 70, 70, 90]
    qs = np.array([w["ast"][2] for w in window])
    # uniform over the rows: every decile of the ids holds about a tenth
    ids = np.array(data.row_ids("fingerprint"))
    deciles = np.bincount(np.searchsorted(ids, qs) * 10 // ids.size,
                          minlength=10)
    assert deciles.min() > 400 and deciles.max() < 600
    assert len(set(qs.tolist())) > 0.85 * ids.size
    again = byfile.load("lib/generators", "similar").Traffic(mix, data, SEED)
    assert [again.take()["pql"] for _ in range(50)] == \
        [w["pql"] for w in window[:50]]
    other = byfile.load("lib/generators", "similar").Traffic(
        mix, data, SEED + 1)
    assert [other.take()["pql"] for _ in range(50)] != \
        [w["pql"] for w in window[:50]]


# --------------------------------------- a small copy through the server


@pytest.fixture(scope="module")
def small_copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("chem_small")
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "pilosa_tpu"), root / "pilosa_tpu")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    (root / "benchmarks/configs/chem-similarity/config.json").write_text(
        json.dumps(config(rows=3_000)))
    return root


def run(root, *extra):
    out = subprocess.run(
        [sys.executable, str(root / "benchmarks/run.py"), "--workload",
         "chem-similarity.similar", "--seed", "3700000012", "--seconds", "3",
         "--rehearse", "--shards", "1", *extra],
        cwd=root, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def test_a_small_copy_is_correct(small_copy):
    res, err = run(small_copy, "--trace", "1")
    assert res["correct"] is True, err[-3000:]
    assert res["compared"]["answers_checked"]["value"] >= 30
    # no walk shapes the warm-up: a rare filter-row size may compile in
    # the window (PERF.md section 7 (k)); the reading is there either way
    got = res["rehearsal_metrics"]
    assert got["window_compiles"]["value"] == \
        res["extra"]["window_compiles"] >= 0
    assert got["leaf_resolve_ms_per_query"]["value"] > 0
    assert 0 < got["tanimoto_band_keep_pct"]["value"] < 100
    assert got["topn_band_ms_per_query"]["value"] > 0
    assert set(res["extra"]["checked_by_label"]) == {"T50", "T70", "T90"}


def test_the_control_is_not_correct(small_copy):
    res, err = run(small_copy, "--trace", "0", "--control")
    assert res["correct"] is False
    wrong = res["compared"]["wrong_answers"]["value"]
    assert wrong == res["compared"]["answers_checked"]["value"] > 0
    assert set(res["extra"]["by_threshold"]) == {"T50", "T70", "T90"}


# ------------------------------------------------- the cell's readers


def reader(name):
    return byfile.load("layer_metrics", name).read


def span(n, wall, self_ms=None):
    return {"n": n, "wallMs": wall,
            "selfMs": wall if self_ms is None else self_ms, "cpuMs": 1.0}


BAND_BEFORE = {"topnBandIn": 1_000_000, "topnBandKept": 400_000,
               "spans": {"nowMs": 0.0, "byName": {
                   "http.request": span(40, 9.0),
                   "topn.band": span(40, 4000.0, 400.0)}}}
# the band's wall is mostly its device.wait child: 1,500 ms a request of
# which 60 its own
BAND_AFTER = {"topnBandIn": 1_000_000 + 6 * 500_000,
              "topnBandKept": 400_000 + 3 * 500_000,
              "spans": {"nowMs": 51_000.0, "byName": {
                  "http.request": span(46, 9.0),
                  "topn.band": span(46, 4000.0 + 6 * 1500.0,
                                    400.0 + 6 * 60.0)}}}


def test_the_readers_over_a_hand_made_window():
    ctx = {"vars_before": BAND_BEFORE, "vars_after": BAND_AFTER,
           "requests": 6}
    assert reader("tanimoto_band_keep_pct")(ctx) == pytest.approx(50.0)
    assert reader("topn_band_ms_per_query")(ctx) == pytest.approx(60.0)


@pytest.mark.parametrize("name,ctx,want", [
    ("window_compiles", {"window_compiles": 3}, 3.0),
    ("recount_roofline", {
        "trace": {"window_s": 2.0,
                  "device_ops": [("jit_pairs_count.1", 0.8),
                                 ("jit_popcount", 0.01)]},
        "vars_before": {"topnPairsBytes": 0, "spans": {"nowMs": 0.0,
                                                       "byName": {}}},
        "vars_after": {"topnPairsBytes": 96_000_000 * 10,
                       "spans": {"nowMs": 4_000.0, "byName": {}}},
        "peaks": {"hbm_bytes_per_s": 819e9}},
     # the traced 2 s of a 4 s window: half of 960 MB, read in 0.8 s
     100 * 480e6 / 819e9 / 0.8),
], ids=["window_compiles", "recount_roofline"])
def test_the_cells_shared_readers_read_under_their_own_names(name, ctx,
                                                             want):
    assert reader(name)(ctx) == pytest.approx(want)


PARENT = {"topnRecountRows": 0, "spans": {"nowMs": 1.0, "byName": {
    "http.request": span(40, 9.0)}}}


@pytest.mark.parametrize("name", ["tanimoto_band_keep_pct",
                                  "topn_band_ms_per_query"])
@pytest.mark.parametrize("before,after", [
    (PARENT, PARENT),                  # a program without the band's pieces
    (BAND_BEFORE, BAND_BEFORE),        # no TopN under a threshold ran
    ({}, {}),                          # no /debug/vars at all
], ids=["parent", "no-delta", "empty"])
def test_the_readers_are_none_where_nothing_was_read(name, before, after):
    assert reader(name)({"vars_before": before, "vars_after": after,
                         "requests": 0}) is None
