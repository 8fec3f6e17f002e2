"""Hybrid sparse/run/dense parity fuzz (ISSUE 15 satellite; ISSUE 17
extended it three-way).

Two executors share one holder: `hybrid` runs with the default sparse
threshold AND the plan cache deliberately left warm (the interleaved
writes must invalidate it through generation keys even as rows change
representation), `plain` runs with sparse-threshold 0 — pure dense.
Rounds interleave randomized nested PQL trees with set/clear churn that
drives rows across BOTH thresholds in BOTH directions: a sparse row
bulks up past the cardinality threshold, a dense row is cleared below
it, a runny row's runs are SPLIT by mid-run clears (interval count
crossing the run threshold promotes it dense) and MERGED back by
adjacent contiguous sets (demoting it to runs again). The promote/
demote hysteresis, the generation-keyed residency entries of all three
kinds, and the mixed-representation kernels are all exercised against
the dense oracle. Any divergence — results, or error-vs-result
behavior — is a hybrid bug.

A final phase flips the PILOSA_TPU_HYBRID=0 kill switch at runtime and
asserts the hybrid executor immediately behaves purely dense.
"""

import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.executor import ExecutionError, Executor, Pairs
from pilosa_tpu.models.holder import Holder

FIELDS = ("f", "g")
N_ROWS = 6
SHARDS = 2
# the hybrid executor's threshold for this test: small enough that churn
# rounds can push rows across it both ways quickly
THRESHOLD = 512
# interval-count threshold for the run representation — small so a few
# dozen mid-run clears (splits) push a runny row across it
RUN_THRESHOLD = 48
# row N_ROWS-2 is the dedicated RUNNY row: seeded as contiguous blocks
# (cardinality above THRESHOLD, interval count far below RUN_THRESHOLD)
RUNNY_ROW = N_ROWS - 2
RUNNY_BASE = {"f": 70_000, "g": SHARD_WIDTH + 90_000}
RUNNY_LEN = 1500


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("hybridfuzz")
    h = Holder(str(tmp / "data")).open()
    rng = np.random.default_rng(23)
    idx = h.create_index("z")
    for fname in FIELDS:
        f = idx.create_field(fname)
        for rid in range(N_ROWS - 1):  # last row starts empty
            if rid == RUNNY_ROW:
                # the runny row: two contiguous blocks — cardinality
                # well past THRESHOLD but only 2 intervals, so the
                # three-way planner picks the run representation
                base = RUNNY_BASE[fname]
                cols = np.concatenate([
                    np.arange(base, base + RUNNY_LEN),
                    np.arange(base + 50_000, base + 50_000 + RUNNY_LEN),
                ])
            else:
                # rows straddle the threshold: some well under, some over
                n = int(rng.integers(16, 96) * (8 ** (rid % 3)))
                cols = rng.choice(SHARDS * SHARD_WIDTH,
                                  size=min(n, 6000), replace=False)
            f.import_bits([rid] * len(cols), cols.tolist())
            for c in cols[:32]:
                idx.mark_exists(int(c))
    hybrid = Executor(h)
    hybrid.hybrid.threshold = THRESHOLD
    hybrid.hybrid.run_threshold = RUN_THRESHOLD
    assert hybrid.hybrid.active() and hybrid.plan_cache is not None
    plain = Executor(h)
    plain.hybrid.threshold = 0
    assert not plain.hybrid.active()
    yield h, hybrid, plain, rng
    h.close()


def _rand_bitmap(rng, depth: int) -> str:
    if depth <= 0 or rng.random() < 0.35:
        fname = FIELDS[int(rng.integers(len(FIELDS)))]
        rid = int(rng.integers(N_ROWS))
        return f"Row({fname}={rid})"
    op = ("Intersect", "Union", "Difference", "Xor",
          "Not")[int(rng.integers(5))]
    if op == "Not":
        return f"Not({_rand_bitmap(rng, depth - 1)})"
    n = int(rng.integers(2, 4))
    kids = ", ".join(_rand_bitmap(rng, depth - 1) for _ in range(n))
    return f"{op}({kids})"


def _rand_query(rng) -> str:
    inner = _rand_bitmap(rng, int(rng.integers(1, 4)))
    shape = rng.random()
    if shape < 0.5:
        return f"Count({inner})"
    if shape < 0.65:
        fname = FIELDS[int(rng.integers(len(FIELDS)))]
        return f"TopN({fname}, {inner}, n=4)"
    return inner


def _canon(result):
    if isinstance(result, Pairs):
        return ("pairs", tuple(result))
    if isinstance(result, list):
        return ("list", tuple(
            tuple(sorted(r.items())) if isinstance(r, dict) else r
            for r in result))
    if hasattr(result, "columns"):
        return ("row", tuple(int(c) for c in result.columns()))
    return ("val", result)


def _both(hybrid, plain, pql):
    outs = []
    for e in (hybrid, plain):
        try:
            (res,) = e.execute("z", pql)
            outs.append(("ok", _canon(res)))
        except ExecutionError as err:
            outs.append(("err", type(err).__name__, str(err)[:80]))
    assert outs[0] == outs[1], f"divergence on {pql}: {outs}"


def _churn(h, hybrid, plain, rng):
    """Interleaved writes through BOTH executors' shared holder — chosen
    to cross BOTH thresholds in both directions: bulk imports fatten a
    sparse row past the cardinality threshold, clears thin a dense row
    below it, mid-run single-bit clears SPLIT the runny row's intervals
    past the run threshold (run -> dense), and a contiguous re-import
    MERGES them back under it (dense -> run)."""
    idx = h.index("z")
    fname = FIELDS[int(rng.integers(len(FIELDS)))]
    f = idx.field(fname)
    rid = int(rng.integers(N_ROWS))
    if rid == RUNNY_ROW:
        # keep scattered writes off the runny row: its interval count
        # is owned by the split/merge arms below, and random scatter
        # would inflate it past RUN_THRESHOLD permanently
        rid = N_ROWS - 1
    action = rng.random()
    if action < 0.35:
        # fatten: push toward/past the threshold
        cols = rng.choice(SHARDS * SHARD_WIDTH,
                          size=int(rng.integers(64, 2 * THRESHOLD)),
                          replace=False)
        f.import_bits([rid] * len(cols), cols.tolist())
    elif action < 0.45:
        # empty the row outright: the decisive downward crossing (a
        # dense row's next upload must come back sparse — demotion)
        from pilosa_tpu.pql import Call
        hybrid._execute_clear_row(idx, Call("ClearRow", {fname: rid}),
                                  None)
    elif action < 0.6:
        # run SPLIT: scattered single-bit clears inside the runny row's
        # contiguous block — each interior clear splits an interval, a
        # couple of these actions push the count past RUN_THRESHOLD
        from pilosa_tpu.pql import Call
        base = RUNNY_BASE[fname]
        offs = rng.choice(RUNNY_LEN, size=int(rng.integers(16, 48)),
                          replace=False)
        for o in offs.tolist():
            hybrid._execute_clear(
                idx, Call("Clear", {"_col": int(base + o),
                                    fname: RUNNY_ROW}), None)
    elif action < 0.7:
        # run MERGE: contiguous re-import heals the splits back to one
        # interval (and restores cardinality a ClearRow may have zeroed)
        base = RUNNY_BASE[fname]
        cols = np.arange(base, base + RUNNY_LEN)
        f.import_bits([RUNNY_ROW] * len(cols), cols.tolist())
    elif action < 0.85:
        # thin: single-bit clears through the write path
        cols = rng.integers(0, SHARDS * SHARD_WIDTH,
                            size=int(rng.integers(8, 64)))
        for c in cols.tolist():
            hybrid._execute_clear(
                idx, __import__("pilosa_tpu.pql",
                                fromlist=["Call"]).Call(
                    "Clear", {"_col": int(c), fname: rid}), None)
    else:
        # single sets through the executor write path
        cols = rng.integers(0, SHARDS * SHARD_WIDTH,
                            size=int(rng.integers(8, 64)))
        for c in cols.tolist():
            hybrid._execute_set(
                idx, __import__("pilosa_tpu.pql",
                                fromlist=["Call"]).Call(
                    "Set", {"_col": int(c), fname: rid}), None)


def test_hybrid_parity_under_threshold_churn(setup):
    h, hybrid, plain, rng = setup
    for round_no in range(40):
        for _ in range(4):
            _both(hybrid, plain, _rand_query(rng))
        _churn(h, hybrid, plain, rng)
    snap = hybrid.hybrid.snapshot()
    # the churn really drove representation across all three kinds
    assert snap["sparseUploads"] > 0 and snap["denseUploads"] > 0
    assert snap["runUploads"] > 0, snap
    assert snap["promoted"] > 0, snap
    assert snap["demoted"] > 0, snap
    assert snap["runTransitions"] > 0, snap


def test_hybrid_kill_switch_parity(setup, monkeypatch):
    """PILOSA_TPU_HYBRID=0 flips the hybrid executor to pure dense at
    runtime — same results, no new sparse uploads."""
    h, hybrid, plain, rng = setup
    monkeypatch.setenv("PILOSA_TPU_HYBRID", "0")
    before = hybrid.hybrid.snapshot()["sparseUploads"]
    for _ in range(12):
        _both(hybrid, plain, _rand_query(rng))
    assert hybrid.hybrid.snapshot()["sparseUploads"] == before


def test_zero_threshold_restores_pure_dense(setup):
    """[query] sparse-threshold = 0 is the config-side off switch."""
    h, hybrid, plain, rng = setup
    old = hybrid.hybrid.threshold
    hybrid.hybrid.threshold = 0
    try:
        before = hybrid.hybrid.snapshot()["sparseUploads"]
        for _ in range(12):
            _both(hybrid, plain, _rand_query(rng))
        assert hybrid.hybrid.snapshot()["sparseUploads"] == before
    finally:
        hybrid.hybrid.threshold = old
