"""GroupBy parity: the device cross-count path vs a host brute-force oracle.

The single-program GroupBy (executor._execute_group_by over the
cross_count_matrix kernel family) must agree bit-for-bit with a naive
host-side set walk on randomized multi-axis schemas — across filter, limit
(including limit=0), single-axis, empty-axis, and mesh vs single-device
runners — and must pay at most ONE host sync per cross-product level
(the groupby_host_syncs dispatch-count contract, analogous to the
topn_recount_rows assertion in test_topn.py).
"""

import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.models import Holder
from pilosa_tpu.parallel.mesh import DeviceRunner, make_mesh


def build_random_index(holder, rng, axes, n_cols, bits_per_row,
                       name="gpar"):
    """Create fields with random rows; returns {(field, row): set(cols)}."""
    idx = holder.create_index(name, track_existence=False)
    sets = {}
    for fname, row_ids in axes:
        f = idx.create_field(fname)
        rids, cids = [], []
        for r in row_ids:
            cols = rng.choice(n_cols, size=bits_per_row, replace=False)
            sets[(fname, r)] = set(int(c) for c in cols)
            rids += [r] * len(cols)
            cids += [int(c) for c in cols]
        f.import_bits(rids, cids)
    return sets


def oracle_groups(sets, axes, filter_cols=None, limit=None):
    """Brute-force lexicographic cross product with intersection counts."""
    out = []

    def rec(level, acc_cols, group):
        if limit is not None and len(out) >= limit:
            return
        if level == len(axes):
            if acc_cols:
                out.append({"group": list(group), "count": len(acc_cols)})
            return
        fname, row_ids = axes[level]
        for r in sorted(row_ids):
            cols = sets[(fname, r)]
            nxt = acc_cols & cols if acc_cols is not None else set(cols)
            rec(level + 1, nxt,
                group + [{"field": fname, "rowID": r}])

    base = set(filter_cols) if filter_cols is not None else None
    rec(0, base, [])
    return out


@pytest.fixture(params=["single", "mesh"])
def gex(tmp_path, request):
    h = Holder(str(tmp_path / "data")).open()
    mesh = make_mesh() if request.param == "mesh" else None
    e = Executor(h, runner=DeviceRunner(mesh))
    yield e
    h.close()


def test_randomized_two_axis_parity(gex):
    rng = np.random.default_rng(31)
    axes = [("a", list(range(12))), ("b", list(range(9)))]
    sets = build_random_index(gex.holder, rng, axes, 3000, 150)
    (groups,) = gex.execute("gpar", "GroupBy(Rows(field=a), Rows(field=b))")
    assert list(groups) == oracle_groups(sets, axes)


def test_randomized_three_axis_filter_parity(gex):
    rng = np.random.default_rng(33)
    axes = [("a", [0, 2, 5, 7]), ("b", [1, 3, 4]), ("c", [0, 1, 2])]
    # span two shards so per-shard reduction is exercised
    sets = build_random_index(gex.holder, rng, axes,
                              SHARD_WIDTH + 5000, 400)
    filt = sets[("a", 0)] | sets[("a", 5)]
    (groups,) = gex.execute(
        "gpar", "GroupBy(Rows(field=a), Rows(field=b), Rows(field=c), "
                "filter=Union(Row(a=0), Row(a=5)))")
    assert list(groups) == oracle_groups(sets, axes, filter_cols=filt)


def test_limit_zero_and_limit_parity(gex):
    rng = np.random.default_rng(35)
    axes = [("a", list(range(6))), ("b", list(range(6)))]
    sets = build_random_index(gex.holder, rng, axes, 2000, 200)
    (zero,) = gex.execute("gpar",
                          "GroupBy(Rows(field=a), Rows(field=b), limit=0)")
    assert list(zero) == []
    for limit in (1, 5, 17):
        (got,) = gex.execute(
            "gpar", f"GroupBy(Rows(field=a), Rows(field=b), limit={limit})")
        assert list(got) == oracle_groups(sets, axes, limit=limit)


def test_single_axis_and_empty_axis(gex):
    rng = np.random.default_rng(37)
    axes = [("a", [1, 4, 9])]
    sets = build_random_index(gex.holder, rng, axes, 1500, 80)
    gex.holder.index("gpar").create_field("empty")
    (groups,) = gex.execute("gpar", "GroupBy(Rows(field=a))")
    assert list(groups) == oracle_groups(sets, axes)
    # an axis with no rows short-circuits to no groups (and no device work)
    before = gex.groupby_host_syncs
    (none,) = gex.execute("gpar",
                          "GroupBy(Rows(field=a), Rows(field=empty))")
    assert list(none) == []
    assert gex.groupby_host_syncs == before


def test_one_host_sync_per_level(gex):
    """The pipelined device path's dispatch contract: every chunk of a
    level is enqueued before one batched fetch — multi-axis GroupBy pays
    exactly len(axes)-1 syncs, single-axis exactly 1, warm or cold."""
    rng = np.random.default_rng(39)
    axes = [("a", list(range(10))), ("b", list(range(8))),
            ("c", list(range(5)))]
    build_random_index(gex.holder, rng, axes, 4000, 120)
    for _ in range(2):  # cold (slab upload) and warm (residency hit)
        before = gex.groupby_host_syncs
        gex.execute("gpar",
                    "GroupBy(Rows(field=a), Rows(field=b), Rows(field=c))")
        assert gex.groupby_host_syncs - before == 2
    before = gex.groupby_host_syncs
    gex.execute("gpar", "GroupBy(Rows(field=a))")
    assert gex.groupby_host_syncs - before == 1


def test_live_bound_overflow_fallback(gex):
    """A chunk whose live combinations exceed the static prune bound must
    fall back to the full count-matrix fetch — exact results, extra sync
    counted, no group silently dropped."""
    rng = np.random.default_rng(41)
    axes = [("a", list(range(7))), ("b", list(range(7)))]
    sets = build_random_index(gex.holder, rng, axes, 800, 300)
    (expect,) = gex.execute("gpar", "GroupBy(Rows(field=a), Rows(field=b))")
    gex._groupby_live_cap = 1  # force overflow on every chunk
    before = gex.groupby_host_syncs
    (got,) = gex.execute("gpar", "GroupBy(Rows(field=a), Rows(field=b))")
    assert list(got) == list(expect) == oracle_groups(sets, axes)
    assert gex.groupby_host_syncs - before > 1  # fallback syncs recorded


def test_limited_final_level_waves(tmp_path):
    """A limited final level spanning multiple chunks: the lex-first-chunk
    probe satisfies a small limit in one sync; a limit beyond the probe's
    yield pays exactly one extra sync for the remaining chunks and still
    returns the full lexicographic prefix."""
    h = Holder(str(tmp_path / "data")).open()
    ex = Executor(h, runner=DeviceRunner())
    try:
        rng = np.random.default_rng(47)
        # 40x26 live prefixes = 1040 > the 512-prefix chunk cap, so the
        # final (c) level runs 3 chunks; a shared core column block keeps
        # every combination nonzero
        axes = [("a", list(range(40))), ("b", list(range(26))),
                ("c", list(range(5)))]
        core = list(range(20))
        sets = {}
        idx = h.create_index("gw", track_existence=False)
        for fname, rows in axes:
            f = idx.create_field(fname)
            rids, cids = [], []
            for r in rows:
                cols = set(core) | set(
                    int(c) for c in rng.choice(480, size=40, replace=False))
                sets[(fname, r)] = cols
                rids += [r] * len(cols)
                cids += list(cols)
            f.import_bits(rids, cids)
        q = "GroupBy(Rows(field=a), Rows(field=b), Rows(field=c))"
        before = ex.groupby_host_syncs
        (unlimited,) = ex.execute("gw", q)
        assert ex.groupby_host_syncs - before == 2  # one per level
        assert list(unlimited) == oracle_groups(sets, axes)
        # small limit: probe chunk alone satisfies it — still 2 syncs
        before = ex.groupby_host_syncs
        (small,) = ex.execute("gw", q[:-1] + ", limit=100)")
        assert ex.groupby_host_syncs - before == 2
        assert list(small) == list(unlimited)[:100]
        # limit beyond the whole result: the probe misses, the second
        # wave covers the remaining chunks — exactly one extra sync
        before = ex.groupby_host_syncs
        (huge,) = ex.execute("gw", q[:-1] + ", limit=100000)")
        assert ex.groupby_host_syncs - before == 3
        assert list(huge) == list(unlimited)
    finally:
        h.close()


def test_mesh_vs_single_device_agreement(tmp_path):
    """The sharded shard_map form and the single-device form must produce
    identical groups on identical data — including with a filter and a
    limit in play."""
    rng_bits = np.random.default_rng(43)
    cols = {}
    axes = [("a", list(range(9))), ("b", list(range(7)))]
    for fname, rows in axes:
        for r in rows:
            cols[(fname, r)] = rng_bits.choice(
                2 * SHARD_WIDTH, size=250, replace=False)
    results = {}
    for mode in ("single", "mesh", "replica_mesh"):
        h = Holder(str(tmp_path / mode)).open()
        mesh = None
        if mode == "mesh":
            mesh = make_mesh()
        elif mode == "replica_mesh":
            mesh = make_mesh(replicas=2)
        ex = Executor(h, runner=DeviceRunner(mesh))
        idx = h.create_index("gm", track_existence=False)
        for fname, rows in axes:
            f = idx.create_field(fname)
            rids, cids = [], []
            for r in rows:
                rids += [r] * len(cols[(fname, r)])
                cids += [int(c) for c in cols[(fname, r)]]
            f.import_bits(rids, cids)
        out = {}
        (out["plain"],) = ex.execute(
            "gm", "GroupBy(Rows(field=a), Rows(field=b))")
        (out["filtered"],) = ex.execute(
            "gm", "GroupBy(Rows(field=a), Rows(field=b), filter=Row(a=3))")
        (out["limited"],) = ex.execute(
            "gm", "GroupBy(Rows(field=a), Rows(field=b), limit=11)")
        results[mode] = {k: list(v) for k, v in out.items()}
        h.close()
    assert results["single"] == results["mesh"] == results["replica_mesh"]


# ------------------------------------------------- the kernels by themselves


@pytest.mark.parametrize("p,r,s,w", [
    (1, 1, 1, 512), (5, 7, 1, 512), (8, 128, 1, 1024), (9, 130, 1, 512),
    (6, 9, 3, 512),
])
def test_cross_count_matrix_matches_numpy(p, r, s, w):
    """counts[P, R] of [P, S, W] against [R, S, W] over ragged prefix,
    row, shard and word counts."""
    from pilosa_tpu.ops.bitvector import cross_count_matrix

    rng = np.random.default_rng(21)
    a = rng.integers(0, 2**32, size=(p, s, w), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(r, s, w), dtype=np.uint32)
    got = np.asarray(cross_count_matrix(a, b))
    expect = np.bitwise_count(a[:, None] & b[None]).sum(axis=(-2, -1))
    np.testing.assert_array_equal(got, expect)


@pytest.mark.parametrize("replicas", [None, 1, 2],
                         ids=["single", "mesh", "replica_mesh"])
def test_groupby_chunk_contract(replicas):
    """DeviceRunner.groupby_chunk's (n_live, flat_idx, counts) — gather,
    AND, cross count, mask of the padding rows, on-device prune — and the
    dense matrix of the overflow fallback, against a numpy count matrix;
    on a mesh the per-device partials meet in one psum over the shard
    axis."""
    import jax
    import jax.numpy as jnp

    runner = DeviceRunner(replicas and make_mesh(replicas=replicas))
    rng = np.random.default_rng(23)
    host_a = rng.integers(0, 2**32, size=(6, 4, 512), dtype=np.uint32)
    host_b = rng.integers(0, 2**32, size=(5, 4, 512), dtype=np.uint32)
    host_b[3] = 0  # an axis row no prefix meets: pruned on the device
    pick = np.array([0, 2, 5, 0], dtype=np.int32)  # the last is padding
    idx = (jnp.asarray(pick),)
    slab_a = runner.put_plane_slab(host_a)
    slab_b = runner.put_plane_slab(host_b)
    cmat = np.bitwise_count(
        host_a[pick[:3]][:, None] & host_b[None]).reshape(3, 5, -1).sum(-1)
    lp, lr = np.nonzero(cmat)
    n_live, flat_idx, counts = jax.device_get(runner.groupby_chunk(
        (slab_a,), idx, slab_b, jnp.int32(3), 30))
    assert int(n_live) == lp.size == 12
    np.testing.assert_array_equal(flat_idx[:lp.size], lp * 5 + lr)
    np.testing.assert_array_equal(counts[:lp.size], cmat[lp, lr])
    assert (flat_idx[lp.size:] == 4 * 5).all() and not counts[lp.size:].any()
    dense = np.asarray(runner.groupby_cmat((slab_a,), idx, slab_b,
                                           jnp.int32(3)))
    np.testing.assert_array_equal(dense[:3], cmat)
    assert not dense[3].any()
