"""A row's per-shard statistics kept per write version of its view
(parallel/residency.py RowStatsMemo, models/view.py View.version) and the
one heat touch a request (Executor._heat_charge).

The guarantee: a write changes the bits, then its fragment's generation,
then the view's version, and is acknowledged after all three; a reader
takes the version first and the fragments after. So whatever route a
write takes, a read asked after its acknowledgement sees it.
"""

import os
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import roaring_wire  # noqa: E402
from test_hybrid_fuzz import FIELDS, N_ROWS, SHARDS, setup  # noqa: E402,F401

from pilosa_tpu import planner  # noqa: E402
from pilosa_tpu.api import API  # noqa: E402
from pilosa_tpu.constants import SHARD_WIDTH  # noqa: E402
from pilosa_tpu.executor import Executor  # noqa: E402
from pilosa_tpu.models import FieldOptions, Holder  # noqa: E402
from pilosa_tpu.parallel.cluster import Cluster, Node  # noqa: E402
from pilosa_tpu.parallel.residency import RowStatsMemo  # noqa: E402
from pilosa_tpu.pql.parser import parse_string  # noqa: E402
from pilosa_tpu.utils.heat import HeatTracker  # noqa: E402

# -- (i) every mutation route ------------------------------------------------

THREE_SHARDS = (0, 1, 2)


class Stack:
    """One holder with an executor and an API on it, and the model the
    answers are held to: {field: {row: set of columns}}, {column: value}."""

    def __init__(self, tmp_path):
        self.holder = Holder(str(tmp_path / "data")).open()
        self.idx = self.holder.create_index("i")
        self.f = self.idx.create_field("f")
        self.g = self.idx.create_field("g")
        self.v = self.idx.create_field(
            "v", FieldOptions(type="int", min=0, max=1000))
        self.ex = Executor(self.holder)
        cluster = Cluster("n1")
        cluster.set_static([Node(id="n1", uri="http://localhost:0")])
        self.api = API(self.holder, cluster, executor=self.ex)
        rng = np.random.default_rng(34)
        self.rows = {"f": {}, "g": {}}
        # f lives in shards 0 and 1 only; g in all three, so shard 2 is in
        # every request's shard list while f has no fragment there
        for name, shards in (("f", (0, 1)), ("g", THREE_SHARDS)):
            for row in (1, 2, 3):
                cols = {int(s * SHARD_WIDTH + c) for s in shards
                        for c in rng.choice(5000, size=300, replace=False)}
                self.rows[name][row] = cols
                self.idx.field(name).import_bits(
                    [row] * len(cols), sorted(cols))
        self.values = {int(c): int(rng.integers(0, 1000))
                       for c in rng.choice(5000, size=200, replace=False)}
        self.v.import_values(list(self.values), list(self.values.values()))

    def close(self):
        self.holder.close()

    def ask(self, pql):
        return self.ex.execute("i", pql)[0]

    def version(self, field, view="standard"):
        return self.idx.field(field).view(view).version

    def topn_under(self, filt: set) -> dict:
        return {row: len(cols & filt)
                for row, cols in self.rows["g"].items() if cols & filt}

    def explain(self, pql):
        return self.ex.explain_call(
            self.idx, parse_string(pql).calls[0], None)


@pytest.fixture
def stack(tmp_path):
    s = Stack(tmp_path)
    yield s
    s.close()


def payload(row, cols) -> bytes:
    """An import-roaring payload of one row's shard-local columns."""
    return roaring_wire.fragment_payload(
        [(row, np.array(sorted(cols), dtype=np.uint32))])


def fresh(s, shard, n=40, field="f", row=1):
    """`n` columns of `shard` the row does not hold yet."""
    base = shard * SHARD_WIDTH
    return [c for c in range(base + 6000, base + 6000 + 4 * n)
            if c not in s.rows[field].get(row, ())][:n]


def route_set(s, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_INGEST", "0")   # the per-bit path
    (col,) = fresh(s, 0, 1)
    assert s.ask(f"Set({col}, f=1)") is True
    s.rows["f"][1].add(col)


def route_clear(s, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_INGEST", "0")
    col = min(s.rows["f"][1])
    assert s.ask(f"Clear({col}, f=1)") is True
    s.rows["f"][1].discard(col)


def route_group_commit(s, monkeypatch):
    monkeypatch.delenv("PILOSA_TPU_INGEST", raising=False)
    base = s.ex.ingest_snapshot()["appliedBatches"]
    cols = fresh(s, 1, 30)
    gone = min(s.rows["f"][1])
    pql = "".join(f"Set({c}, f=1)" for c in cols) + f"Clear({gone}, f=1)"
    assert s.ex.execute("i", pql) == [True] * 31
    assert s.ex.ingest_snapshot()["appliedBatches"] > base
    s.rows["f"][1].update(cols)
    s.rows["f"][1].discard(gone)


def route_clear_row(s, monkeypatch):
    assert s.ask("ClearRow(f=1)") is True
    s.rows["f"][1] = set()


def route_store(s, monkeypatch):
    assert s.ask("Store(Row(g=2), f=1)") is True
    s.rows["f"][1] = set(s.rows["g"][2])


def route_import_bits(s, monkeypatch):
    cols = fresh(s, 0) + fresh(s, 1)
    s.api.import_bits("i", "f", row_ids=[1] * len(cols), column_ids=cols)
    s.rows["f"][1].update(cols)


def route_import_bits_clear(s, monkeypatch):
    cols = sorted(s.rows["f"][1])[::3]
    s.api.import_bits("i", "f", row_ids=[1] * len(cols), column_ids=cols,
                      clear=True)
    s.rows["f"][1].difference_update(cols)


def route_roaring_into_empty(s, monkeypatch):
    assert s.f.view("standard").fragment(2) is None
    local = list(range(100, 400, 3))
    s.api.import_roaring("i", "f", 2, {"": payload(1, local)})
    s.rows["f"][1].update(2 * SHARD_WIDTH + c for c in local)


def route_roaring_into_data(s, monkeypatch):
    local = [c - SHARD_WIDTH for c in fresh(s, 1, 120)]
    s.api.import_roaring("i", "f", 1, {"": payload(1, local)})
    s.rows["f"][1].update(SHARD_WIDTH + c for c in local)


def route_roaring_clear(s, monkeypatch):
    local = sorted(c for c in s.rows["f"][1] if c < SHARD_WIDTH)[::2]
    s.api.import_roaring("i", "f", 0, {"": payload(1, local)}, clear=True)
    s.rows["f"][1].difference_update(local)


def route_first_write_creates_the_fragment(s, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_INGEST", "0")
    assert s.f.view("standard").fragment(2) is None
    assert 2 in s.idx.available_shards_list()
    col = 2 * SHARD_WIDTH + 77
    assert s.ask(f"Set({col}, f=1)") is True
    s.rows["f"][1].add(col)


def route_import_values(s, monkeypatch):
    new = {c: 900 for c in range(7000, 7040)}
    new.update({c: 1 for c in list(s.values)[:20]})   # overwrite: clears
    s.api.import_values("i", "v", column_ids=list(new),
                        values=list(new.values()))
    s.values.update(new)


ROUTES = [route_set, route_clear, route_group_commit, route_clear_row,
          route_store, route_import_bits, route_import_bits_clear,
          route_roaring_into_empty, route_roaring_into_data,
          route_roaring_clear, route_first_write_creates_the_fragment,
          route_import_values]


def check_reads(s):
    """A Count, a TopN's filter and explain, held to the model."""
    f1 = s.rows["f"][1]
    assert s.ask("Count(Row(f=1))") == len(f1)
    assert s.ask("Count(Intersect(Row(f=1), Row(g=2)))") == len(
        f1 & s.rows["g"][2])
    assert dict(s.ask("TopN(g, Row(f=1), n=5)")) == s.topn_under(f1)
    doc = s.explain("Count(Row(f=1))")
    assert doc["plan"]["estimates"][0]["est"] == len(f1)
    per_shard = [sum(1 for c in f1 if c // SHARD_WIDTH == sh)
                 for sh in THREE_SHARDS]
    assert doc["tree"]["maxShardCardinality"] == max(per_shard)
    big = {c for c, val in s.values.items() if val > 10}
    assert s.ask("Count(Range(v > 10))") == len(big)
    assert dict(s.ask("TopN(g, Range(v > 10), n=5)")) == s.topn_under(big)


@pytest.mark.parametrize("plan_cache", [True, False],
                         ids=["plan-cache-on", "plan-cache-off"])
@pytest.mark.parametrize("route", ROUTES,
                         ids=[r.__name__[6:] for r in ROUTES])
def test_a_read_after_the_acknowledgement_sees_the_write(
        stack, monkeypatch, route, plan_cache):
    s = stack
    s.ex.plan_cache.enabled = plan_cache
    check_reads(s)           # every cache and the memo warm ...
    check_reads(s)           # ... and served from
    assert s.ex.row_stats.hits > 0
    field, view = (("v", "bsig_v") if route is route_import_values
                   else ("f", "standard"))
    before = s.version(field, view)
    stale = s.explain("Count(Range(v > 10))")["tree"]["residency"]
    assert stale["generationMatch"]
    route(s, monkeypatch)
    assert s.version(field, view) > before
    # explain first: the resident comparison mask is the old planes'
    fresh_ = s.explain("Count(Range(v > 10))")["tree"]["residency"]
    assert fresh_["generationMatch"] is (route is not route_import_values)
    check_reads(s)
    check_reads(s)           # and the entries stored after the write


# -- (ii) a reader and a writer on one row -----------------------------------

def _write_by_execute(s, col):
    assert s.ex.execute("i", f"Set({col}, f=1)") == [True]


def _write_by_import(s, col):
    s.f.import_bits([1], [col])


@pytest.mark.parametrize("write", [_write_by_execute, _write_by_import],
                         ids=["group-commit", "import-bits"])
def test_every_count_after_an_acknowledged_set_includes_it(stack, write):
    """The writer publishes how many Sets have been acknowledged; the
    reader takes that number first and counts after: the count may run
    ahead of it, never behind."""
    s = stack
    rounds = 2000
    base = len(s.rows["f"][1])
    cols = fresh(s, 0, rounds)
    acked = [0]
    errors: list = []
    reads = [0]

    def writer():
        try:
            for i, col in enumerate(cols):
                write(s, col)
                acked[0] = i + 1
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    def reader():
        try:
            while acked[0] < rounds and not errors:
                seen = acked[0]
                got = s.ask("Count(Row(f=1))")
                reads[0] += 1
                if not base + seen <= got <= base + rounds:
                    errors.append(AssertionError(
                        f"{seen} Sets acknowledged, Count read "
                        f"{got - base} of them"))
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=writer),
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors[0]
    assert reads[0] > 0
    assert s.ask("Count(Row(f=1))") == base + rounds


# -- (iii) the memo against the six loops, and choose's transitions ----------

def loops(ex, idx, field, shards, row):
    """What the read path computed of a row before the memo: a walk over
    the shards' fragments for each statistic."""
    view = idx.field(field).view("standard")
    frags = [view.fragment(sh) for sh in shards]
    cards = [0 if fr is None else fr.row_cardinality(row) for fr in frags]
    runs = [(0, 0) if fr is None else fr.row_run_stats(row) for fr in frags]
    return {"gens": ex._leaf_gens(idx, field, "standard", shards, row),
            "max_card": max(cards), "total_card": sum(cards),
            "run_stats": (max(n for n, _ in runs), max(m for _, m in runs)),
            "frag_keys": [("z", field, "standard", sh) for sh in shards]}


def memo(ex, idx, field, shards, row):
    st = ex.row_stats.get(idx, field, "standard", shards, row)
    return {"gens": st.gens, "max_card": st.max_card,
            "total_card": st.total_card, "frag_keys": st.frag_keys,
            "run_stats": ex.row_stats.run_stats(
                idx, field, "standard", shards, row)}


def test_the_memo_equals_the_six_loops_under_churn(setup):  # noqa: F811
    h, hybrid, _plain, _rng = setup
    idx = h.index("z")
    rng = np.random.default_rng(341)
    shards = list(range(SHARDS))
    ex = Executor(h)
    for _round in range(12):
        for field in FIELDS:
            for row in range(N_ROWS):
                want = loops(ex, idx, field, shards, row)
                assert memo(ex, idx, field, shards, row) == want   # a miss
                assert memo(ex, idx, field, shards, row) == want   # a hit
        # churn across both thresholds, by three routes
        field = FIELDS[int(rng.integers(len(FIELDS)))]
        row = int(rng.integers(N_ROWS))
        cols = rng.choice(SHARDS * SHARD_WIDTH, size=int(rng.integers(1, 900)),
                          replace=False).tolist()
        if rng.random() < 0.5:
            idx.field(field).import_bits([row] * len(cols), cols)
        else:
            hybrid.execute("z", "".join(
                f"Clear({c}, {field}={row})" for c in idx.field(field).view(
                    "standard").fragment(0).row_columns(row)[:400].tolist()))
        block = int(rng.integers(0, SHARD_WIDTH - 4000))
        idx.field(field).import_bits(
            [row] * 1500, list(range(block, block + 1500)))    # a long run
    assert ex.row_stats.hits > 0 and ex.row_stats.misses > 0


def test_choose_makes_the_same_transitions_with_and_without_a_warm_memo(
        tmp_path):
    """Promote, demote by the band's floor and demote by cold fragments:
    the representation is chosen anew every request from the memoised
    statistics, so an executor whose memo is dropped before every choice
    and one whose memo stays warm walk the same sequence."""
    h = Holder(str(tmp_path / "data")).open()
    idx = h.create_index("z")
    f = idx.create_field("f")
    warm, cold = Executor(h), Executor(h)
    for ex in (warm, cold):
        ex.hybrid.threshold = 512
        ex.hybrid.run_threshold = 0

    def step():
        out = []
        for ex in (warm, cold):
            if ex is cold:
                ex.row_stats.clear()
            out.append(planner.choose_representation(
                ex, idx, None, "f", "standard", (0,), 7)[:2])
            # the read a request charges after its choice
            ex._touch_reads(idx, "f", "standard", (0,), 1)
        assert out[0] == out[1]
        return out[0][0]

    rng = np.random.default_rng(5)
    cols = rng.permutation(60000)[:1200].tolist()
    seq = []
    f.import_bits([7] * 300, cols[:300])
    seq += [step(), step()]                       # sparse
    f.import_bits([7] * 900, cols[300:])
    seq += [step(), step()]                       # promoted: 1,200 > 512
    f.import_bits([7] * 740, cols[:740], clear=True)
    seq += [step()]                               # 460 in the band, hot: stays
    for ex in (warm, cold):
        ex.heat.clear()
    seq += [step(), step()]                       # cold fragments: demoted
    f.import_bits([7] * 900, cols[300:])
    seq += [step()]                               # promoted again
    f.import_bits([7] * 1100, cols[100:], clear=True)
    seq += [step()]                               # 100 < the band's floor
    assert seq == ["sparse", "sparse", "dense", "dense", "dense", "sparse",
                   "sparse", "dense", "sparse"]
    for ex in (warm, cold):
        snap = ex.hybrid.snapshot()
        assert (snap["promoted"], snap["demoted"]) == (2, 2)
    assert warm.row_stats.hits > 0 and cold.row_stats.hits == 0
    h.close()


# -- (iv) heat: one touch a request, the same charges ------------------------

class CountingLock:
    def __init__(self, lock):
        self.lock, self.taken = lock, 0

    def __enter__(self):
        self.taken += 1
        return self.lock.__enter__()

    def __exit__(self, *exc):
        return self.lock.__exit__(*exc)


NINE = ("Count(Union(Intersect(Row(f=1), Row(f=2), Row(f=3)), "
        "Difference(Row(f=1), Row(f=2)), Intersect(Row(f=3), Row(f=1)), "
        "Row(f=2), Row(f=3)))")
MIXED = "Count(Union(Intersect(Row(f=1), Row(g=2)), Row(f=3)))"


@pytest.mark.parametrize("pql,leaves", [(NINE, {"f": 9}),
                                        (MIXED, {"f": 2, "g": 1})],
                         ids=["nine-leaves-one-field", "two-fields"])
def test_heat_is_charged_once_a_request_and_as_the_ten_touches_did(
        stack, pql, leaves):
    s = stack
    s.ex.plan_cache.enabled = False
    want = s.ask(pql)                # leaves built and uploaded
    tracker = s.ex.heat
    tracker.clear()
    tracker._lock = counting = CountingLock(tracker._lock)
    assert s.ask(pql) == want
    assert 1 <= counting.taken <= 2
    tracker._lock = counting.lock
    got = {(e["field"], e["shard"]): e
           for e in tracker.snapshot(top=0)["hot"]}
    total_ms = tracker.totals()["deviceMs"]
    assert total_ms > 0

    # what a touch a leaf and one touch of the repeated list gave
    old = HeatTracker()
    n_leaves = sum(leaves.values())
    repeated = []
    for field, n in leaves.items():
        keys = [("i", field, "standard", sh) for sh in THREE_SHARDS]
        for _leaf in range(n):
            old.touch_many(keys, reads=1)
            repeated += keys
    old.touch_many(repeated, device_ms=total_ms)
    assert len(repeated) == n_leaves * len(THREE_SHARDS)
    was = {(e["field"], e["shard"]): e for e in old.snapshot(top=0)["hot"]}
    assert set(got) == set(was)
    for key, e in was.items():
        assert got[key]["reads"] == e["reads"] == leaves[key[0]]
        assert got[key]["deviceMs"] == pytest.approx(e["deviceMs"], abs=2e-3)
    assert tracker.totals()["reads"] == old.totals()["reads"]
    assert tracker.totals()["deviceMs"] == pytest.approx(
        old.totals()["deviceMs"])


def test_a_cached_count_still_heats_its_operands_in_one_touch(stack):
    s = stack
    assert s.ex.plan_cache.enabled
    want = s.ask(NINE)
    assert s.ask(NINE) == want       # answered by the plan cache
    tracker = s.ex.heat
    tracker.clear()
    tracker._lock = counting = CountingLock(tracker._lock)
    assert s.ask(NINE) == want
    assert counting.taken == 1
    tracker._lock = counting.lock
    assert tracker.totals()["reads"] == 9 * len(THREE_SHARDS)


# -- (v) the bound and the counters ------------------------------------------

def test_the_memo_is_bounded_and_counts_hits_and_misses(stack):
    s = stack
    memo_ = s.ex.row_stats = RowStatsMemo(bound=4)
    shards = tuple(THREE_SHARDS)
    for row in range(10):
        s.ex.row_stats.get(s.idx, "g", "standard", shards, row)
    assert len(memo_._lru) == 4
    assert (memo_.hits, memo_.misses) == (0, 10)
    # least recently used goes first: rows 6-9 stay, and a hit renews
    s.ex.row_stats.get(s.idx, "g", "standard", shards, 6)
    s.ex.row_stats.get(s.idx, "g", "standard", shards, 10)
    assert (memo_.hits, memo_.misses) == (1, 11)
    assert sorted(k[3] for k in memo_._lru) == [6, 8, 9, 10]
    assert memo_.snapshot() == {"rowStatsHits": 1, "rowStatsMisses": 11,
                                "rowStatsEntries": 4}
    # a view that does not exist yet is asked anew every time
    st = s.ex.row_stats.get(s.idx, "f", "standard_2026", shards, 1)
    assert (st.gens, st.max_card, st.total_card) == ((), 0, 0)
    assert len(memo_._lru) == 4


def test_the_memo_under_more_threads_than_cores(stack):
    """Sixteen readers over ten rows and a bound of four, so hits, misses,
    inserts and evictions of one key meet, while a writer sets bits of one
    row: no reader fails, the bound holds, and after the last
    acknowledgement every row reads what a walk over the shards reads."""
    s = stack
    memo_ = s.ex.row_stats = RowStatsMemo(bound=4)
    shards = tuple(THREE_SHARDS)
    errors: list = []
    stop = threading.Event()

    def reader(seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                row = int(rng.integers(10))
                st = s.ex.row_stats.get(s.idx, "g", "standard", shards, row)
                assert len(st.gens) == 3 and st.max_card <= st.total_card
                assert len(memo_._lru) <= 4 + 16   # inserts race the trim
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for col in range(9000, 9300):
            s.g.set_bit(2, col)
        stop.set()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert not errors, errors[0]
    assert len(memo_._lru) <= 4
    assert memo_.hits > 0 and memo_.misses > 10
    view = s.g.view("standard")
    for row in range(10):
        st = s.ex.row_stats.get(s.idx, "g", "standard", shards, row)
        cards = [view.fragment(sh).row_cardinality(row) for sh in shards]
        assert (st.gens, st.total_card, st.max_card) == (
            s.ex._leaf_gens(s.idx, "g", "standard", shards, row),
            sum(cards), max(cards))


def test_debug_vars_carries_the_two_counters(tmp_path):
    import json
    import urllib.request

    from pilosa_tpu.server import Server
    srv = Server(str(tmp_path / "s"), port=0).open()
    try:
        def call(path, body=None):
            req = urllib.request.Request(srv.uri + path, data=body,
                                         method="POST" if body is not None
                                         else "GET")
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())
        call("/index/i", b"{}")
        call("/index/i/field/f", b"{}")
        call("/index/i/query", b"Set(3, f=1)")
        for _ in range(3):
            assert call("/index/i/query",
                        b"Count(Union(Row(f=1), Row(f=2)))")["results"] == [1]
        res = call("/debug/vars")["deviceResidency"]
        assert res["rowStatsMisses"] >= 2
        assert res["rowStatsHits"] > res["rowStatsMisses"]
        assert res["rowStatsEntries"] >= 2
    finally:
        srv.close()
