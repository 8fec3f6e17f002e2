"""Hybrid sparse/dense device containers (ISSUE 15 tentpole).

Three layers under test:

* the sparse kernel family (ops/bitvector.py): padded sorted-index
  algebra vs a numpy set-algebra oracle, including sentinel padding,
  empty rows, both operand orders and every padded width pair;
* the HybridManager (parallel/residency.py): threshold choice,
  promote/demote hysteresis, heat-informed demotion, kill switches;
* the executor integration: sparse leaves in the residency manager with
  real padded byte accounting, on-device materialization for dense
  consumers, the /debug/vars-shaped snapshot, and equal-budget capacity
  (the ≥4x resident-rows claim, asserted at test scale).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops import bitvector as bv
from pilosa_tpu.parallel.residency import (
    DEFAULT_SPARSE_THRESHOLD,
    HybridManager,
)

W = SHARD_WIDTH // 32
SENT = bv.SPARSE_SENTINEL


def _sparse(cols, slots):
    return jnp.asarray(bv.sparse_from_columns(
        np.asarray(sorted(cols), dtype=np.int64), slots)[None])


def _as_set(sp_row):
    arr = np.asarray(sp_row)[0]
    return set(arr[arr < SENT].tolist())


# ------------------------------------------------------------- kernels


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sparse_kernel_algebra_matches_set_oracle(seed):
    rng = np.random.default_rng(seed)
    na, nb = int(rng.integers(0, 400)), int(rng.integers(1, 2000))
    sa = set(rng.choice(SHARD_WIDTH, size=na, replace=False).tolist())
    sb = set(rng.choice(SHARD_WIDTH, size=nb, replace=False).tolist())
    a = _sparse(sa, HybridManager.pad_slots(max(na, 1)))
    b = _sparse(sb, HybridManager.pad_slots(max(nb, 1)))
    assert _as_set(bv.sparse_intersect(a, b)) == sa & sb
    assert _as_set(bv.sparse_union(a, b)) == sa | sb
    assert _as_set(bv.sparse_xor(a, b)) == sa ^ sb
    assert _as_set(bv.sparse_difference(a, b)) == sa - sb
    assert int(np.asarray(bv.sparse_count(a))[0]) == len(sa)
    dense_b = jnp.asarray(
        bv.dense_from_columns(np.asarray(sorted(sb)))[None])
    assert _as_set(bv.sparse_intersect_dense(a, dense_b)) == sa & sb
    assert _as_set(bv.sparse_difference_dense(a, dense_b)) == sa - sb
    assert int(np.asarray(bv.sparse_dense_count(a, dense_b))[0]) \
        == len(sa & sb)
    # round trip through the materializer
    md = np.asarray(bv.sparse_to_dense(a, W))[0]
    assert set(bv.columns_from_dense(md).tolist()) == sa


def test_sparse_kernels_sorted_sentinel_contract():
    """Every kernel's output is sorted with sentinel padding at the tail
    — the invariant that lets compositions chain without re-normalizing."""
    rng = np.random.default_rng(7)
    sa = set(rng.choice(SHARD_WIDTH, 100, replace=False).tolist())
    sb = set(rng.choice(SHARD_WIDTH, 300, replace=False).tolist())
    a, b = _sparse(sa, 128), _sparse(sb, 512)
    for out in (bv.sparse_intersect(a, b), bv.sparse_union(a, b),
                bv.sparse_xor(a, b), bv.sparse_difference(a, b)):
        row = np.asarray(out)[0]
        assert (np.diff(row) >= 0).all()
        live = row[row < SENT]
        assert live.size == np.unique(live).size


def test_sparse_kernels_empty_rows():
    empty = _sparse([], 8)
    full = _sparse([1, 5, 9], 8)
    assert _as_set(bv.sparse_intersect(empty, full)) == set()
    assert _as_set(bv.sparse_union(empty, full)) == {1, 5, 9}
    assert _as_set(bv.sparse_difference(full, empty)) == {1, 5, 9}
    assert int(np.asarray(bv.sparse_count(empty))[0]) == 0
    assert np.asarray(bv.sparse_to_dense(empty, W)).sum() == 0


_MERGE_WIDTHS = (128, 4096, 8192, 12288)


def _merge_case_rows(ka, kb, rng):
    """One (set_a, set_b) per shard row: the shapes the merge has to get
    right, at every width pair."""
    small = min(ka, kb)
    ragged_a = set(rng.choice(SHARD_WIDTH, ka // 3, replace=False).tolist())
    ragged_b = set(rng.choice(SHARD_WIDTH, kb // 2, replace=False).tolist())
    # share a third of a's values so the intersection is not empty by luck
    ragged_b |= set(sorted(ragged_a)[::3][:kb - len(ragged_b)])
    same = set(rng.choice(SHARD_WIDTH, small, replace=False).tolist())
    evens = set(range(0, 2 * ka, 2))
    odds = set(range(1, 2 * kb, 2))
    last = SHARD_WIDTH - 1
    return [
        (ragged_a, ragged_b),                   # ragged, overlapping
        (set(), ragged_b),                      # a all-pad
        (ragged_a, set()),                      # b all-pad
        (set(), set()),                         # pad meets pad
        ({0, 7, last}, {7, last}),              # the last legal column
        ({last}, set(range(kb))),               # ... against a full b
        (same, same),                           # identical operands
        (evens, odds),                          # full rows, disjoint
        (set(range(ka)), set(range(kb))),       # full rows, one inside
    ]


@pytest.mark.parametrize("kb", _MERGE_WIDTHS)
@pytest.mark.parametrize("ka", _MERGE_WIDTHS)
def test_sparse_merge_kernels_match_set_oracle(ka, kb):
    """sparse ∩ / − over every padded width pair, both orders, with a
    leading shard axis: set equality with Python sets, the
    sorted-sentinel contract and the output widths callers rely on."""
    rows = _merge_case_rows(ka, kb, np.random.default_rng(ka * 31 + kb))
    a = jnp.asarray(np.stack([bv.sparse_from_columns(
        np.asarray(sorted(sa), dtype=np.int64), ka) for sa, _ in rows]))
    b = jnp.asarray(np.stack([bv.sparse_from_columns(
        np.asarray(sorted(sb), dtype=np.int64), kb) for _, sb in rows]))
    for got, want_of, width in (
            (bv.sparse_intersect(a, b), lambda x, y: x & y, min(ka, kb)),
            (bv.sparse_difference(a, b), lambda x, y: x - y, ka)):
        got = np.asarray(got)
        assert got.shape == (len(rows), width) and got.dtype == np.int32
        assert (np.diff(got, axis=-1) >= 0).all()
        assert got.min() >= 0 and got.max() <= SENT
        for row, (sa, sb) in zip(got, rows):
            live = row[row < SENT]
            assert live.size == np.unique(live).size
            assert set(live.tolist()) == want_of(sa, sb)


@pytest.mark.parametrize("kernel", ["sparse_intersect", "sparse_difference"])
def test_sparse_merge_kernels_lower_without_gather_or_loop(kernel):
    """The two kernels are sorts and elementwise compares. A binary-search
    probe would bring a `while` of dependent `gather`s back: on the chip
    that cost 37-40 ms a call against 0.55 for the merge (PERF.md §5)."""
    import re

    import jax

    spec = jax.ShapeDtypeStruct((64, 4096), jnp.int32)
    lowered = getattr(bv, kernel).lower(spec, spec)
    ops = set(re.findall(r"stablehlo\.(\w+)", lowered.as_text()))
    assert "sort" in ops
    assert not ops & {"gather", "scatter", "while"}, sorted(ops)


def test_eval_hybrid_mixed_tree():
    rng = np.random.default_rng(11)
    sets = [set(rng.choice(SHARD_WIDTH, n, replace=False).tolist())
            for n in (120, 350, 7000)]
    leaves = [_sparse(sets[0], 128), _sparse(sets[1], 512),
              jnp.asarray(bv.dense_from_columns(
                  np.asarray(sorted(sets[2])))[None])]
    kinds = ["sparse", "sparse", "dense"]
    prog = ("andnot", ("or", ("leaf", 0), ("leaf", 1)),
            ("and", ("leaf", 1), ("leaf", 2)))
    expect = (sets[0] | sets[1]) - (sets[1] & sets[2])
    kind, arr = bv.eval_hybrid(prog, leaves, kinds, W)
    dense = np.asarray(bv.sparse_to_dense(arr, W)
                       if kind == "sparse" else arr)[0]
    assert set(bv.columns_from_dense(dense).tolist()) == expect
    assert bv.hybrid_count(prog, leaves, kinds) == len(expect)


def test_eval_hybrid_union_cap_densifies():
    """A union whose combined slot count would exceed SPARSE_UNION_CAP
    falls back to a dense plane instead of growing index arrays toward
    plane size."""
    rng = np.random.default_rng(13)
    sa = set(rng.choice(SHARD_WIDTH, 12000, replace=False).tolist())
    sb = set(rng.choice(SHARD_WIDTH, 12000, replace=False).tolist())
    # 16384 + 16384 slots > SPARSE_UNION_CAP -> the union densifies
    leaves = [_sparse(sa, 1 << 14), _sparse(sb, 1 << 14)]
    kind, arr = bv.eval_hybrid(("or", ("leaf", 0), ("leaf", 1)),
                               leaves, ["sparse", "sparse"], W)
    assert kind == "dense"
    assert set(bv.columns_from_dense(np.asarray(arr)[0]).tolist()) \
        == sa | sb


# ------------------------------------------------------------- manager


def test_manager_threshold_and_slots():
    m = HybridManager(threshold=1000)
    rep, slots = m.choose(("i", "f", "standard", 1), 100)
    assert rep == "sparse" and slots == 128
    rep, slots = m.choose(("i", "f", "standard", 2), 1001)
    assert rep == "dense"
    assert m.pad_slots(0) == 8 and m.pad_slots(8) == 8
    assert m.pad_slots(9) == 16 and m.pad_slots(4096) == 4096


def test_manager_hysteresis_band():
    """Promote at threshold crossing; inside the band a dense row stays
    dense (no heat tracker = never cold), demote below the band floor."""
    m = HybridManager(threshold=1000, hysteresis=0.25)
    key = ("i", "f", "standard", 7)
    assert m.choose(key, 900)[0] == "sparse"   # first sight, under thr
    assert m.choose(key, 1200)[0] == "dense"   # promoted
    assert m.promoted == 1
    assert m.choose(key, 900)[0] == "dense"    # band [750, 1000]: sticky
    assert m.choose(key, 700)[0] == "sparse"   # below band floor: demoted
    assert m.demoted == 1
    assert m.choose(key, 900)[0] == "sparse"   # band is one-sided: only a
    assert m.demoted == 1                      # DENSE row is sticky in it


def test_manager_heat_informed_demotion():
    """A band-resident dense row demotes when every covered fragment is
    cold — the 'cold dense rows re-enter as sparse' rule."""

    class FakeTracker:
        enabled = True

        def __init__(self):
            self.score = 1.0

        def scores_for(self, keys):
            return [self.score] * len(keys)

    t = FakeTracker()
    m = HybridManager(threshold=1000, hysteresis=0.25, heat=t)
    key = ("i", "f", "standard", 9)
    fkeys = [("i", "f", "standard", 0)]
    m.choose(key, 1200, fkeys)                      # dense
    assert m.choose(key, 900, fkeys)[0] == "dense"  # band + hot: sticky
    t.score = 0.0                                   # fragment went cold
    assert m.choose(key, 900, fkeys)[0] == "sparse"
    assert m.demoted == 1


def test_manager_kill_switches(monkeypatch):
    m = HybridManager(threshold=1000)
    monkeypatch.setenv("PILOSA_TPU_HYBRID", "0")
    assert not m.active()
    assert m.choose(("i", "f", "standard", 1), 10) == ("dense", 0)
    monkeypatch.delenv("PILOSA_TPU_HYBRID")
    assert m.active()
    m.threshold = 0
    assert not m.active()


# ------------------------------------------------- executor integration


@pytest.fixture()
def holder_ex(tmp_path):
    h = Holder(str(tmp_path / "data")).open()
    idx = h.create_index("hy", track_existence=False)
    f = idx.create_field("f")
    rng = np.random.default_rng(5)
    sets = {}
    for rid, n in ((0, 150), (1, 800), (2, 6000)):
        cols = rng.choice(2 * SHARD_WIDTH, size=n, replace=False)
        f.import_bits([rid] * n, cols.tolist())
        sets[rid] = set(cols.tolist())
    ex = Executor(h)
    yield h, ex, sets
    h.close()


def test_executor_sparse_residency_accounting(holder_ex):
    """Sparse leaves land in the residency LRU under the 'sparse' kind at
    their real padded byte cost — a 150-bit row over 2 shards is a
    2x256-slot int32 array (2 KiB), not two 128 KiB planes."""
    h, ex, sets = holder_ex
    (n,) = ex.execute("hy", "Count(Intersect(Row(f=0), Row(f=1)))")
    assert n == len(sets[0] & sets[1])
    # slots bucket by the LARGEST per-shard cardinality, not the total
    slots = {}
    for rid in (0, 1):
        per_shard = max(
            sum(1 for c in sets[rid] if c // SHARD_WIDTH == s)
            for s in (0, 1))
        slots[rid] = HybridManager.pad_slots(per_shard)
    by_kind = ex.residency.snapshot()["by_kind"]
    assert by_kind["sparse"]["entries"] == 2
    assert by_kind["sparse"]["bytes"] == 2 * 4 * (slots[0] + slots[1])
    snap = ex.hybrid_snapshot()
    assert snap["sparseUploads"] == 2
    assert snap["residentSparseLeaves"] == 2
    plan_reps = None  # representation rides the plan node
    from pilosa_tpu import planner as _planner
    call = __import__("pilosa_tpu.pql", fromlist=["parse_string_cached"]) \
        .parse_string_cached("Count(Intersect(Row(f=0), Row(f=1)))")
    planned, info = ex.planner.plan_call(
        h.index("hy"), call.calls[0], [0, 1])
    # plan info carries no hybrid entries yet (recorded at compile), but
    # executing under a profile does — assert via current_plan
    tok = _planner.current_plan.set(info)
    try:
        ex._compile(h.index("hy"), planned.children[0], [0, 1])
    finally:
        _planner.current_plan.reset(tok)
    plan_reps = info.get("hybrid")
    assert plan_reps and all(r["rep"] == "sparse" for r in plan_reps)
    assert {r["slots"] for r in plan_reps} == {slots[0], slots[1]}


def test_executor_dense_consumer_materializes_on_device(holder_ex):
    """A dense consumer (TopN recount path: _row_leaf_dev) of a row that
    is sparse-resident gets its plane by on-device materialization — no
    second host upload of the row."""
    h, ex, sets = holder_ex
    idx = h.index("hy")
    ex.execute("hy", "Count(Row(f=0))")  # sparse-resident now
    before = ex.hybrid.snapshot()
    dense = ex._row_leaf_dev(idx, "f", "standard", [0, 1], 0)
    after = ex.hybrid.snapshot()
    assert after["materialized"] == before["materialized"] + 1
    assert after["denseUploads"] == before["denseUploads"]  # no upload
    cols = set()
    host = np.asarray(dense)
    for s in (0, 1):
        cols |= {int(c) + s * SHARD_WIDTH
                 for c in bv.columns_from_dense(host[s]).tolist()}
    assert cols == sets[0]


def test_executor_kill_switch_restores_pure_dense(holder_ex, monkeypatch):
    h, ex, sets = holder_ex
    monkeypatch.setenv("PILOSA_TPU_HYBRID", "0")
    (n,) = ex.execute("hy", "Count(Row(f=0))")
    assert n == len(sets[0])
    assert ex.residency.snapshot()["by_kind"].get("sparse") is None
    assert ex.hybrid_snapshot()["sparseUploads"] == 0


def test_equal_budget_capacity_multiplier(tmp_path):
    """The headline claim at test scale: at an HBM budget that holds only
    4 dense planes, hybrid keeps the WHOLE 32-row sparse working set
    resident — ≥4x the resident-row capacity, with zero evictions."""
    h = Holder(str(tmp_path / "cap")).open()
    try:
        idx = h.create_index("cap", track_existence=False)
        f = idx.create_field("f")
        rng = np.random.default_rng(9)
        n_rows = 32
        for r in range(n_rows):
            cols = rng.choice(SHARD_WIDTH, size=300, replace=False)
            f.import_bits([r] * cols.size, cols.tolist())

        def sweep(ex):
            ex.plan_cache.enabled = False
            ex.residency.budget = 4 * (SHARD_WIDTH // 8)
            for _ in range(2):
                for r in range(n_rows):
                    ex.execute("cap", f"Count(Row(f={r}))")
            bk = ex.residency.snapshot()["by_kind"]
            return (bk.get("sparse", {}).get("entries", 0)
                    + bk.get("row", {}).get("entries", 0))

        hybrid_ex = Executor(h)
        assert hybrid_ex.hybrid.active()
        resident_hybrid = sweep(hybrid_ex)
        dense_ex = Executor(h)
        dense_ex.hybrid.threshold = 0
        resident_dense = sweep(dense_ex)
        assert resident_dense <= 4
        assert resident_hybrid >= 4 * resident_dense
        assert resident_hybrid == n_rows  # everything stayed resident
    finally:
        h.close()
