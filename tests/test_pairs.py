"""The recount of a field's small rows from their sorted columns: the
kernel (ops/bitvector.py pairs_count, its shard_map form in
parallel/mesh.py) in its three layouts against a numpy oracle, and the
resident entry (executor._pairs_entry) on a field of 10,000 rows: no plane
stacked for a row below the sparse threshold, one build however many
threads ask, the layout the rows' columns choose, and the cases that fall
to the dense walk whole (the hybrid representation off; an entry that
would not fit a quarter of the residency budget)."""

import json
import pathlib
import re
import threading
import urllib.request

import jax
import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.models import FieldOptions, Holder
from pilosa_tpu.ops import bitvector as bv
from pilosa_tpu.parallel.mesh import DeviceRunner, make_mesh

W = SHARD_WIDTH // 32

# ------------------------------------------------------------------ kernel


def _kept(rows_by_shard: list) -> list:
    """[{rank: columns}] a shard -> [(columns, ranks)] a shard, rows in rank
    order and a row's columns sorted: what executor._pairs_entry hands the
    two builders of ops/bitvector.py, through which every entry here is
    laid out too (the test and the executor cannot drift)."""
    kept = []
    for rows in rows_by_shard:
        ranks = sorted(rows)
        cols = [np.sort(np.asarray(rows[r], np.int32)) for r in ranks]
        kept.append((
            np.concatenate(cols + [np.empty(0, np.int32)]),
            np.repeat(np.asarray(ranks, np.int32),
                      [c.size for c in cols]).astype(np.int32)))
    return kept


def entry_of(rows_by_shard: list, n_rows: int, slots: int) -> np.ndarray:
    """[{rank: sorted columns}] a shard -> int32[2, S, slots], as
    executor._pairs_entry lays it out."""
    return bv.pairs_by_pairs(_kept(rows_by_shard), slots)


def oracle(rows_by_shard: list, src: np.ndarray, n_rows: int) -> np.ndarray:
    out = np.zeros(n_rows, np.int64)
    for s, rows in enumerate(rows_by_shard):
        for rank, cols in rows.items():
            cols = np.asarray(cols, np.int64)
            out[rank] += int(((src[s][cols >> 5] >> (cols & 31)) & 1).sum())
    return out


def random_entry(seed, n_shards, n_rows, slots, empty_shards=()):
    rng = np.random.default_rng(seed)
    shards = []
    for s in range(n_shards):
        rows = {}
        if s not in empty_shards:
            left = slots - 2
            for rank in rng.permutation(n_rows):
                # ragged: most rows a few bits, some none, one or two many
                n = int(min(left, rng.choice([0, 1, 3, 17, 200])))
                if n:
                    rows[int(rank)] = rng.choice(SHARD_WIDTH, n,
                                                 replace=False)
                    left -= n
        shards.append(rows)
    # the last column of a shard, in the filter and in a row
    shards[0].setdefault(0, np.empty(0, np.int64))
    shards[0][0] = np.union1d(shards[0][0][:5], [SHARD_WIDTH - 1, 0])
    src = rng.integers(0, 1 << 32, size=(n_shards, W), dtype=np.uint32)
    src[0, -1] |= np.uint32(1 << 31)
    return shards, src


KERNEL_CASES = [
    # shards, rows, slots, shards left all-pad
    (1, 5, 8, ()),                      # H = 1, K far below one lane tile
    (2, 130, 1 << 11, ()),
    (3, 700, 1 << 13, (1,)),
    (4, 300, 1 << 16, (0, 3)),          # more than one histogram step
    (2, 9966, 1 << 14, ()),             # a grid field's count vector
    (9, 31, 1 << 9, (4,)),              # H = 1, two gather steps, K < a tile
    (1, 128, 1 << 7, ()),               # H = 1 full, K one vector of lanes
    (2, 129, 1536, ()),                 # K no multiple of the tile
]


@pytest.mark.parametrize("n_shards,n_rows,slots,empty", KERNEL_CASES)
def test_pairs_count_equals_oracle(n_shards, n_rows, slots, empty):
    shards, src = random_entry(n_rows, n_shards, n_rows, slots, empty)
    pairs = entry_of(shards, n_rows, slots)
    n_slots = bv.pairs_count_slots(n_rows)
    assert n_slots >= n_rows and n_slots % 128 == 0
    got = np.asarray(bv.pairs_count(pairs, src, n_slots))
    want = oracle(shards, src, n_rows)
    assert (got[:n_rows] == want).all()
    assert not got[n_rows:].any()
    assert want[0] >= 1  # column 2^20 - 1 counted


def column_entry(seed, n_shards, n_rows, fill, empty_shards=()):
    """An entry whose columns hold at most one row each, in both layouts:
    ([{rank: columns}] a shard, int32[1, S, 32, W] of rank or -1 laid
    bit-major by the executor's builder, the filter)."""
    rng = np.random.default_rng(seed)
    shards = []
    for s in range(n_shards):
        rows: dict = {}
        if s not in empty_shards:
            cols = rng.permutation(SHARD_WIDTH)[:int(fill * SHARD_WIDTH)]
            cols = np.union1d(cols, [0, SHARD_WIDTH - 1])
            rank = rng.integers(0, n_rows, cols.size)
            rank[-1] = 0                      # the last column, in row 0
            rows = {int(r): cols[rank == r] for r in np.unique(rank)}
        shards.append(rows)
    by_col = bv.pairs_by_column(_kept(shards))
    src = rng.integers(0, 1 << 32, size=(n_shards, W), dtype=np.uint32)
    src[0, -1] |= np.uint32(1 << 31)
    return shards, by_col, src


def test_by_column_lies_bit_major():
    """At [0, s, b, w] the rank of the row that holds column 32 · w + b."""
    shards, by_col, _ = column_entry(5, 2, 40, 0.01)
    assert by_col.shape == (1, 2, 32, W) and by_col.dtype == np.int32
    flat = np.full((2, SHARD_WIDTH), -1, np.int32)
    for s, rows in enumerate(shards):
        for rank, cols in rows.items():
            flat[s, cols] = rank
    assert (by_col[0] == flat.reshape(2, W, 32).transpose(0, 2, 1)).all()


@pytest.mark.parametrize("n_shards,n_rows,fill,empty", [
    (1, 3, 0.001, ()), (2, 9966, 0.7, ()), (3, 500, 0.2, (0, 2)),
    (2, 1, 0.3, ()), (1, 128, 0.05, ())])             # H = 1
def test_pairs_count_by_column_equals_oracle(n_shards, n_rows, fill, empty):
    shards, by_col, src = column_entry(n_rows, n_shards, n_rows, fill, empty)
    n_slots = bv.pairs_count_slots(n_rows)
    got = np.asarray(bv.pairs_count(by_col, src, n_slots))
    want = oracle(shards, src, n_rows)
    assert (got[:n_rows] == want).all() and not got[n_rows:].any()
    if 0 not in empty:
        assert want[0] >= 1  # column 2^20 - 1 counted
        # and the two layouts of one entry count alike
        slots = 8
        while slots < max(sum(c.size for c in r.values()) for r in shards):
            slots *= 2
        pairs = entry_of(shards, n_rows, slots)
        assert (np.asarray(bv.pairs_count(pairs, src, n_slots)) == got).all()


@pytest.mark.parametrize("layout", ["pairs", "column"])
def test_all_pad_entry_counts_nothing(layout):
    pairs = (np.full((2, 2, 8), bv.SPARSE_SENTINEL, np.int32)
             if layout == "pairs" else np.full((1, 2, 32, W), -1, np.int32))
    src = np.full((2, W), 0xFFFFFFFF, np.uint32)
    assert not np.asarray(bv.pairs_count(pairs, src, 128)).any()


def test_a_count_past_the_float_limit_is_exact():
    """One row, every column of 17 shards in it, under a filter of all
    ones: 17 x 2^20 is past 2^24, where a float32 sum stops counting by
    ones; the partial sums are turned to int32 before they get there."""
    n_shards = 17
    by_col = np.zeros((1, n_shards, 32, W), np.int32)
    src = np.full((n_shards, W), 0xFFFFFFFF, np.uint32)
    got = np.asarray(bv.pairs_count(by_col, src, 128))
    assert got[0] == n_shards * SHARD_WIDTH > 1 << 24
    assert not got[1:].any()


def _shapes(by_column: bool):
    entry = ((1, 2, 32, W) if by_column else (2, 2, 1 << 12))
    return (jax.ShapeDtypeStruct(entry, np.int32),
            jax.ShapeDtypeStruct((2, W), np.uint32))


def test_by_column_lowers_without_gather_or_scatter():
    """By column nothing is gathered: the filter's words are unpacked where
    they lie. By pairs the bit test is a gather, and only that."""
    ops = {by_column: set(re.findall(
        r"stablehlo\.(\w+)",
        bv.pairs_count.lower(*_shapes(by_column), 128 * 128).as_text()))
        for by_column in (True, False)}
    assert not ops[True] & {"gather", "scatter"}, sorted(ops[True])
    assert "dot_general" in ops[True]
    assert "gather" in ops[False] and "scatter" not in ops[False]


@pytest.mark.parametrize("by_column", [True, False])
def test_the_program_is_named_jit_pairs_count(by_column):
    """The device trace names a program after its jitted entry point, and
    benchmarks/layer_metrics/recount_roofline.py finds the recount by it."""
    reader = (pathlib.Path(__file__).parent.parent / "benchmarks"
              / "layer_metrics" / "recount_roofline.py").read_text()
    (program,) = re.findall(r'^PROGRAM = "(\w+)"$', reader, re.M)
    text = bv.pairs_count.lower(*_shapes(by_column), 128).compile().as_text()
    assert re.search(r"HloModule (\w+)", text).group(1) == program \
        == "jit_pairs_count"


@pytest.mark.parametrize("n_devices,n_shards,slots", [
    (4, 4, 1 << 12), (4, 6, 1 << 12), (8, 3, 1 << 12),
    (4, 5, 1536),       # K no multiple of the tile, shards none of the mesh
    (8, 3, 8)])         # K below one vector of lanes
def test_mesh_form_agrees(n_devices, n_shards, slots):
    """The shard_map + psum form on the CPU's forced devices: the entry
    and the filter padded to the mesh and sharded on the shard axis, the
    counts the single-device kernel's."""
    mesh = make_mesh(jax.devices()[:n_devices])
    runner = DeviceRunner(mesh)
    n_rows = 300 if slots > 8 else 3
    shards, src = random_entry(7 + n_shards, n_shards, n_rows, slots)
    pairs = entry_of(shards, n_rows, slots)
    n_slots = bv.pairs_count_slots(n_rows)
    dev_pairs, dev_src = runner.put_pairs(pairs), runner.put_leaf(src)
    assert dev_pairs.shape[1] % n_devices == 0
    got = np.asarray(runner.pairs_count(dev_pairs, dev_src, n_slots))
    assert (got[:n_rows] == oracle(shards, src, n_rows)).all()
    single = np.asarray(DeviceRunner().pairs_count(pairs, src, n_slots))
    assert (got == single).all()
    # by column: sharded on the same axis, pad shards hold no rank
    shards, by_col, src = column_entry(n_shards, n_shards, n_rows, 0.05)
    dev = runner.put_pairs(by_col)
    assert dev.shape[1] % n_devices == 0 and dev.shape[2:] == (32, W)
    got = np.asarray(runner.pairs_count(dev, runner.put_leaf(src), n_slots))
    assert (got[:n_rows] == oracle(shards, src, n_rows)).all()


def window_entry(seed, n_shards, n_rows, c_lo, c_hi, empty_shards=()):
    """Rows of 1 to 60 bits a shard whose columns all lie in [c_lo, c_hi)
    (a fingerprint's positions), the window's first and last column in
    row 0 of the first shard that holds rows; the filter random."""
    rng = np.random.default_rng(seed)
    shards = []
    for s in range(n_shards):
        rows = {}
        if s not in empty_shards:
            for rank in range(n_rows):
                n = int(rng.integers(1, min(60, c_hi - c_lo) + 1))
                rows[rank] = np.sort(rng.choice(np.arange(c_lo, c_hi), n,
                                                replace=False))
        shards.append(rows)
    first = next(r for r in shards if r)
    first[0] = np.union1d(first[0], [c_lo, c_hi - 1])
    src = rng.integers(0, 1 << 32, size=(n_shards, W), dtype=np.uint32)
    return shards, src


def slab_of(shards, n_rows):
    kept = _kept(shards)
    lo, n_words = bv.slab_window(kept)
    n_slots = bv.pairs_count_slots(n_rows)
    return bv.pairs_by_slab(kept, lo, n_words, n_slots), np.int32(lo)


SLAB_CASES = {
    # shards, rows, the columns' window, shards left without rows
    "one-shard-fingerprints": (1, 500, 0, 2048, ()),
    "several-shards": (3, 300, 0, 2048, ()),
    "an-empty-shard": (3, 130, 0, 2048, (1,)),
    "window-off-word-0": (2, 129, 5000, 5700, ()),      # R no multiple of 128
    "window-at-the-shard-end": (2, 77, SHARD_WIDTH - 1000, SHARD_WIDTH, ()),
    "two-words-across-an-edge": (1, 1, 31, 33, ()),
}


@pytest.mark.parametrize("case", SLAB_CASES)
def test_slab_count_equals_pairs_and_oracle(case):
    n_shards, n_rows, c_lo, c_hi, empty = SLAB_CASES[case]
    shards, src = window_entry(n_rows, n_shards, n_rows, c_lo, c_hi, empty)
    slab, lo = slab_of(shards, n_rows)
    n_slots = bv.pairs_count_slots(n_rows)
    assert slab.dtype == np.uint32 and slab.shape[:2] == (1, n_shards)
    assert slab.shape[3] == n_slots
    got = np.asarray(bv.pairs_count(slab, src, n_slots, lo))
    want = oracle(shards, src, n_rows)
    assert (got[:n_rows] == want).all() and not got[n_rows:].any()
    slots = 8
    while slots < max(sum(c.size for c in r.values()) for r in shards):
        slots *= 2
    pairs = entry_of(shards, n_rows, slots)
    assert (np.asarray(bv.pairs_count(pairs, src, n_slots)) == got).all()
    # the window's edges are in it: a filter of those two columns alone
    one = np.zeros_like(src)
    for c in (c_lo, c_hi - 1):
        one[:, c >> 5] |= np.uint32(1 << (c & 31))
    edge = np.asarray(bv.pairs_count(slab, one, n_slots, lo))
    assert edge[0] == oracle(shards, one, n_rows)[0] >= 2


def test_slab_window_covers_the_columns_in_a_power_of_two():
    """One window for every shard: the words any shard's columns touch,
    a power of two of them and at least SLAB_WORDS_MIN, moved down where
    it would pass the shard's last word; no column, the least window."""
    def kept(*cols):
        return [(np.asarray(c, np.int32), np.zeros(len(c), np.int32))
                for c in cols]

    assert bv.slab_window(kept([0, 2047])) == (0, 64)
    assert bv.slab_window(kept([64], [2047, 2048])) == (2, 64)
    assert bv.slab_window(kept([100])) == (3, bv.SLAB_WORDS_MIN)
    assert bv.slab_window(kept([SHARD_WIDTH - 1])) == (W - 8, 8)
    assert bv.slab_window(kept([], [])) == (0, bv.SLAB_WORDS_MIN)
    assert bv.slab_window(kept([0, SHARD_WIDTH - 1])) == (0, W)


def test_slab_lowers_to_a_streaming_sum():
    """As a window slab nothing is gathered or scattered and nothing goes
    to the matrix unit, and the program keeps the recount's name."""
    slab = jax.ShapeDtypeStruct((1, 2, 64, 512), np.uint32)
    src = jax.ShapeDtypeStruct((2, W), np.uint32)
    lo = jax.ShapeDtypeStruct((), np.int32)
    lowered = bv.pairs_count.lower(slab, src, 512, lo)
    ops = set(re.findall(r"stablehlo\.(\w+)", lowered.as_text()))
    assert not ops & {"gather", "scatter", "dot_general"}, sorted(ops)
    assert "popcnt" in ops
    text = lowered.compile().as_text()
    assert re.search(r"HloModule (\w+)", text).group(1) == "jit_pairs_count"


@pytest.mark.parametrize("n_devices,n_shards", [(4, 4), (4, 6), (4, 1),
                                                (8, 3)])
def test_mesh_form_agrees_as_a_slab(n_devices, n_shards):
    """A window slab on the CPU's forced devices: sharded on its second
    axis, pad shards of no word, the window's first word replicated; the
    counts the single-device kernel's."""
    mesh = make_mesh(jax.devices()[:n_devices])
    runner = DeviceRunner(mesh)
    n_rows = 200
    shards, src = window_entry(n_shards + 40, n_shards, n_rows, 3000, 5048)
    slab, lo = slab_of(shards, n_rows)
    n_slots = bv.pairs_count_slots(n_rows)
    dev = runner.put_pairs(slab)
    assert dev.shape[1] % n_devices == 0 and dev.dtype == np.uint32
    got = np.asarray(runner.pairs_count(dev, runner.put_leaf(src), n_slots,
                                        lo))
    assert (got[:n_rows] == oracle(shards, src, n_rows)).all()
    single = np.asarray(DeviceRunner().pairs_count(slab, src, n_slots, lo))
    assert (got == single).all()


# ------------------------------------------------------- the resident entry

N_ROWS = 10_000
N_DENSE = 3


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """One field of 10,000 rows over 2 shards: three above the sparse
    threshold, the others 3 to 40 bits a shard; a filter field of one row
    holding every third column."""
    rng = np.random.default_rng(2929)
    h = Holder(str(tmp_path_factory.mktemp("wide") / "d")).open()
    idx = h.create_index("w", track_existence=False)
    sets: dict = {}
    rows_l, cols_l = [], []
    for r in range(N_ROWS):
        n = 9000 if r < N_DENSE else int(rng.integers(6, 80))
        c = np.unique(rng.integers(0, 2 * SHARD_WIDTH, n))
        sets[r] = c
        rows_l.append(np.full(c.size, r, np.uint64))
        cols_l.append(c.astype(np.uint64))
    g = idx.create_field("g", FieldOptions(cache_size=50000))
    g.import_bits(np.concatenate(rows_l), np.concatenate(cols_l))
    filt = np.arange(0, 2 * SHARD_WIDTH, 3, dtype=np.uint64)
    idx.create_field("f").import_bits(np.zeros(filt.size, np.uint64), filt)
    want = sorted(((int((sets[r] % 3 == 0).sum()), -r)
                   for r in range(N_ROWS)), reverse=True)
    try:
        yield h, [(-nr, c) for c, nr in want if c > 0]
    finally:
        h.close()


def test_no_plane_for_a_row_below_the_threshold(wide):
    h, want = wide
    ex = Executor(h)
    # n above the dense rows' reach: the prune cannot stop before the
    # small rows, whose cached counts (6-80) are all above the 25th best
    (got,) = ex.execute("w", "TopN(g, Row(f=0), n=25)")
    assert [tuple(p) for p in got] == want[:25]
    assert ex.topn_recount_rows == N_DENSE
    assert ex.topn_pairs_recounts == 1
    kinds = ex.residency.snapshot()["by_kind"]
    assert kinds["pairs"]["entries"] == 1
    assert kinds["row"]["entries"] == N_DENSE + 1       # and the filter
    # rows that share columns: the entry is pairs, 2 x 4 B a stored bit
    # rounded up to a power of two, not one rank a column
    assert kinds["pairs"]["bytes"] == 2 * 2 * (1 << 18) * 4
    # 4 bytes a stored bit of the rows recounted, 128 KiB a shard
    stored = sum(int(np.unique(ex.holder.index("w").field("g").view(
        "standard").fragment(s).rows_columns()[0], return_counts=True
    )[1][N_DENSE:].sum()) for s in range(2))
    assert ex.topn_pairs_bytes == 4 * stored + 2 * W * 4
    # where the n-th best beats every small row's cached count, no launch
    (got,) = ex.execute("w", "TopN(g, Row(f=0), n=2)")
    assert [tuple(p) for p in got] == want[:2]
    assert ex.topn_pairs_recounts == 1


def test_an_attribute_field_lies_by_column(tmp_path):
    """One value a column, 10,000 values, seven columns in ten filled: no
    column holds two rows and the sorted columns would take 2^20 slots a
    shard, sixteen times PAIRS_BY_COLUMN_SLOTS, so the entry is one rank a
    column, laid bit-major (4 MiB a shard, here half the pairs) and the
    recount gathers nothing; same Pairs as brute force."""
    rng = np.random.default_rng(31)
    h = Holder(str(tmp_path / "d")).open()
    try:
        idx = h.create_index("a", track_existence=False)
        cols = np.flatnonzero(rng.random(2 * SHARD_WIDTH) < 0.7)
        vals = rng.integers(0, N_ROWS, cols.size)
        vals[:9000] = 7                       # one row above the threshold
        idx.create_field("g", FieldOptions(cache_size=50000)).import_bits(
            vals.astype(np.uint64), cols.astype(np.uint64))
        filt = np.arange(0, 2 * SHARD_WIDTH, 5, dtype=np.uint64)
        idx.create_field("f").import_bits(np.zeros(filt.size, np.uint64),
                                          filt)
        ex = Executor(h)
        (got,) = ex.execute("a", "TopN(g, Row(f=0), n=40)")
        under = np.bincount(vals[cols % 5 == 0], minlength=N_ROWS)
        want = sorted(((int(c), -r) for r, c in enumerate(under) if c),
                      reverse=True)[:40]
        assert [tuple(p) for p in got] == [(-nr, c) for c, nr in want]
        assert ex.topn_recount_rows == 1 and ex.topn_pairs_recounts == 1
        (key,) = [k for k, _ in ex.residency.entries_snapshot()
                  if k[0] == "pairs"]
        assert ex.residency.peek(key).dev.shape == (1, 2, 32, W)
    finally:
        h.close()


LAYOUT_CASES = {
    # stored bits a shard over PAIRS_BY_COLUMN_SLOTS, whether one column
    # holds two rows, the residency budget, whether it lies by column
    "at-the-crossover": (0.75, False, None, True),
    "below-the-crossover": (0.375, False, None, False),
    "a-column-holds-two-rows": (0.75, True, None, False),
    # a quarter of it is one byte short of 2 shards x 4 MiB
    "no-room-by-column": (0.75, False, 4 * 2 * 4 * SHARD_WIDTH - 1, False),
}


@pytest.mark.parametrize("case", LAYOUT_CASES)
def test_the_layout_is_the_faster_recount(tmp_path, case):
    """An attribute field of 300 thin rows over 2 shards: by column from
    PAIRS_BY_COLUMN_SLOTS slots a shard, though its pairs would be a
    fraction of the 4 MiB a shard; by pairs below that, where a column
    holds two rows, and where the by-column entry would pass a quarter of
    the residency budget and the pairs do not (by pairs, not the entry of
    no rows). Same Pairs as brute force every time, and
    topn_pairs_recounts_by_column counts the by-column launches alone."""
    fill, shared, budget, by_column = LAYOUT_CASES[case]
    rng = np.random.default_rng(32)
    n_rows, per_shard = 300, int(fill * bv.PAIRS_BY_COLUMN_SLOTS)
    h = Holder(str(tmp_path / "d")).open()
    try:
        idx = h.create_index("a", track_existence=False)
        cols = np.concatenate([
            s * SHARD_WIDTH + rng.permutation(SHARD_WIDTH)[:per_shard]
            for s in range(2)])
        vals = rng.integers(0, n_rows, cols.size)
        if shared:      # one column of the second shard in two rows
            cols = np.append(cols, cols[-1])
            vals = np.append(vals, (vals[-1] + 1) % n_rows)
        idx.create_field("g", FieldOptions(cache_size=50000)).import_bits(
            vals.astype(np.uint64), cols.astype(np.uint64))
        filt = np.arange(0, 2 * SHARD_WIDTH, 5, dtype=np.uint64)
        idx.create_field("f").import_bits(np.zeros(filt.size, np.uint64),
                                          filt)
        ex = Executor(h)
        if budget is not None:
            ex.residency.budget = budget
        under = np.bincount(vals[cols % 5 == 0], minlength=n_rows)
        want = sorted(((int(c), -r) for r, c in enumerate(under) if c),
                      reverse=True)
        for launches, n in enumerate((40, 7), start=1):
            (got,) = ex.execute("a", f"TopN(g, Row(f=0), n={n})")
            assert [tuple(p) for p in got] == [(-nr, c)
                                               for c, nr in want[:n]]
            assert ex.topn_recount_rows == 0
            assert ex.topn_pairs_recounts == launches
            assert ex.topn_pairs_recounts_by_column == launches * by_column
            assert ex.topn_pairs_recounts_by_slab == 0
        (key,) = [k for k, _ in ex.residency.entries_snapshot()
                  if k[0] == "pairs"]
        entry = ex.residency.peek(key)
        assert entry.dev.shape == ((1, 2, 32, W) if by_column else (
            2, 2, ex.hybrid.pad_slots(per_shard + shared)))
        assert entry.by_column == by_column and entry.ids.size == n_rows
    finally:
        h.close()


def test_sixteen_threads_build_the_entry_once(wide):
    h, want = wide
    ex = Executor(h)
    out, errs = [None] * 16, []
    gate = threading.Barrier(16)

    def one(i):
        try:
            gate.wait()
            (got,) = ex.execute("w", f"TopN(g, Row(f=0), n={30 + i})")
            out[i] = [tuple(p) for p in got]
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=one, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    assert all(out[i] == want[:30 + i] for i in range(16))
    assert ex.pairs_entries_built == 1
    assert ex.residency.snapshot()["by_kind"]["pairs"]["entries"] == 1
    # and the three dense rows and the filter were each uploaded once
    assert ex.residency.misses == N_DENSE + 2


def test_a_block_of_rows_resolves_as_the_rows_one_by_one(wide):
    """_row_leaves_dev (a recount block): the leaves _row_leaf_dev gives
    for each row, under the same residency keys, and the same read heat
    on every fragment, charged in one round trip of the tracker's lock
    where the rows one by one take one each."""
    h, _ = wide
    idx, rows, shards = h.index("w"), [2, 0, 5000, 1], [0, 1]
    one, block = Executor(h), Executor(h)
    a = [one._row_leaf_dev(idx, "g", "standard", shards, r) for r in rows]
    calls = []
    touch_many = block.heat.touch_many
    block.heat.touch_many = lambda keys, **kw: (calls.append(kw),
                                                touch_many(keys, **kw))
    b = block._row_leaves_dev(idx, "g", "standard", shards, rows)
    assert [kw for kw in calls if "reads" in kw] == [{"reads": len(rows)}]
    assert all((np.asarray(x) == np.asarray(y)).all() for x, y in zip(a, b))
    assert ([k for k, _ in one.residency.entries_snapshot()]
            == [k for k, _ in block.residency.entries_snapshot()])

    def reads(ex):
        return sorted((e["field"], e["shard"], e["reads"])
                      for e in ex.heat.snapshot(top=0)["hot"])

    assert reads(one) == reads(block) == [("g", 0, 4), ("g", 1, 4)]


@pytest.mark.parametrize("why", ["hybrid-off", "entry-over-a-quarter"])
def test_cases_that_fall_to_the_dense_walk(wide, why):
    """Same answer, every candidate through stacked planes."""
    h, want = wide
    ex = Executor(h)
    if why == "hybrid-off":
        ex.hybrid.threshold = 0
    else:
        ex.residency.budget = 1 << 20     # the entry alone is 4 MiB
    (got,) = ex.execute("w", "TopN(g, Row(f=0), ids=[0, 1, 5000, 9999])")
    by_id = dict(want)
    assert sorted(tuple(p) for p in got) == sorted(
        (r, by_id[r]) for r in (0, 1, 5000, 9999) if r in by_id)
    assert ex.topn_pairs_recounts == 0
    assert ex.topn_recount_rows == 4


def _fingerprints(rng, n_rows, n_shards, spread):
    """Rows of 30 to 60 bits a shard over the first 2,048 columns of every
    shard (`spread` False: a fingerprint's positions) or anywhere in it."""
    width = SHARD_WIDTH if spread else 2048
    rows_l, cols_l = [], []
    for s in range(n_shards):
        for r in range(n_rows):
            c = rng.choice(width, int(rng.integers(30, 61)), replace=False)
            rows_l.append(np.full(c.size, r, np.uint64))
            cols_l.append((s * SHARD_WIDTH + c).astype(np.uint64))
    return np.concatenate(rows_l), np.concatenate(cols_l)


SLAB_RULE_CASES = {
    # shards, rows spread over the whole shard, the residency budget,
    # the layout the entry takes
    "fingerprints-one-shard": (1, False, None, "slab"),
    "fingerprints-two-shards": (2, False, None, "slab"),
    "rows-across-the-shard": (2, True, None, "pairs"),
    # a quarter of it is one byte short of the slab: no room in either
    "no-room-for-the-slab": (1, False, 4 * 64 * 512 * 4 - 4, "none"),
}


@pytest.mark.parametrize("case", SLAB_RULE_CASES)
def test_the_rows_columns_choose_the_slab(tmp_path, case):
    """500 rows of 30-60 bits a shard: over a fingerprint's 2,048 columns
    the window slab (64 words × 512 lanes, 128 KiB a shard) is smaller
    than the pairs (2^15 slots × 8 B) and the entry takes it; across the
    whole shard the slab would be 64 MiB a shard, and the entry lies by
    pairs; where the slab passes a quarter of the budget so do the pairs,
    and every row goes to the dense walk. Same Pairs as brute force every
    time, and topn_pairs_recounts_by_slab counts the slab launches
    alone."""
    n_shards, spread, budget, layout = SLAB_RULE_CASES[case]
    rng = np.random.default_rng(38)
    n_rows = 500
    rows, cols = _fingerprints(rng, n_rows, n_shards, spread)
    h = Holder(str(tmp_path / "d")).open()
    try:
        idx = h.create_index("c", track_existence=False)
        idx.create_field("fp", FieldOptions(cache_size=50000)).import_bits(
            rows, cols)
        filt = np.flatnonzero(rng.random(n_shards * SHARD_WIDTH) < 0.3)
        idx.create_field("f").import_bits(np.zeros(filt.size, np.uint64),
                                          filt.astype(np.uint64))
        ex = Executor(h)
        if budget is not None:
            ex.residency.budget = budget
        hit = np.isin(cols, filt)
        under = np.bincount(rows[hit].astype(np.int64), minlength=n_rows)
        want = sorted(((int(c), -r) for r, c in enumerate(under) if c),
                      reverse=True)
        for launches, n in enumerate((40, 7), start=1):
            (got,) = ex.execute("c", f"TopN(fp, Row(f=0), n={n})")
            assert [tuple(p) for p in got] == [(-nr, c)
                                               for c, nr in want[:n]]
            if layout == "none":
                assert ex.topn_pairs_recounts == 0 < ex.topn_recount_rows
                continue
            assert ex.topn_recount_rows == 0
            assert ex.topn_pairs_recounts == launches
            assert ex.topn_pairs_recounts_by_slab == launches * (
                layout == "slab")
            assert ex.topn_pairs_recounts_by_column == 0
        if layout == "none":
            return
        (key,) = [k for k, _ in ex.residency.entries_snapshot()
                  if k[0] == "pairs"]
        entry = ex.residency.peek(key)
        assert entry.by_slab == (layout == "slab") and not entry.by_column
        assert entry.ids.size == n_rows
        if layout == "slab":
            assert entry.dev.shape == (1, n_shards, 64, 512)
            assert entry.dev.dtype == np.uint32 and int(entry.lo) == 0
        else:
            assert entry.dev.shape[:2] == (2, n_shards)
    finally:
        h.close()


def test_debug_vars_count_the_slab_launches(tmp_path):
    """/debug/vars: `topnPairsRecountsBySlab` beside `topnPairsRecounts`,
    one a filtered TopN over a field of fingerprints, none over a field
    whose rows run across the shard."""
    from pilosa_tpu.server import Server

    rng = np.random.default_rng(3838)
    s = Server(str(tmp_path / "n"), port=0).open()

    def ask(pql):
        req = urllib.request.Request(
            s.uri + "/index/c/query", data=pql.encode(), method="POST",
            headers={"Content-Type": "text/plain"})
        with urllib.request.urlopen(req, timeout=120) as r:
            return json.loads(r.read())["results"][0]

    def dvars():
        with urllib.request.urlopen(s.uri + "/debug/vars", timeout=60) as r:
            v = json.loads(r.read())
        return v["topnPairsRecounts"], v["topnPairsRecountsBySlab"]

    try:
        idx = s.holder.create_index("c", track_existence=False)
        for name, spread in (("fp", False), ("wide", True)):
            idx.create_field(name, FieldOptions(cache_size=50000)
                             ).import_bits(*_fingerprints(rng, 300, 1,
                                                          spread))
        filt = np.arange(0, SHARD_WIDTH, 3, dtype=np.uint64)
        idx.create_field("f").import_bits(np.zeros(filt.size, np.uint64),
                                          filt)
        assert dvars() == (0, 0)
        ask("TopN(fp, Row(f=0), n=5)")
        ask("TopN(fp, Row(f=0), n=9)")
        assert dvars() == (2, 2)
        ask("TopN(wide, Row(f=0), n=5)")
        assert dvars() == (3, 2)
    finally:
        s.close()


def test_every_slot_bucket_compiles_at_the_first_widening(tmp_path):
    """A similarity search brings a cold filter row every request, and the
    row's size picks the slot bucket its sparse list is widened from: the
    first filtered TopN over a block of shards compiles the widening of
    every bucket under the sparse threshold, and filter rows of any other
    size compile nothing after it. Same Pairs as brute force."""
    from pilosa_tpu.utils.telemetry import xla

    rng = np.random.default_rng(3839)
    sizes = [3, 12, 40, 100, 200, 700, 1500]
    rows_l, cols_l, sets = [], [], {}
    for r in range(140):
        c = rng.choice(2048, sizes[r % len(sizes)], replace=False)
        sets[r] = set(c.tolist())
        rows_l.append(np.full(c.size, r, np.uint64))
        cols_l.append((2 * SHARD_WIDTH + c).astype(np.uint64))  # shard 2
    h = Holder(str(tmp_path / "d")).open()
    try:
        idx = h.create_index("c", track_existence=False)
        idx.create_field("fp", FieldOptions(cache_size=50000)).import_bits(
            np.concatenate(rows_l), np.concatenate(cols_l))
        ex = Executor(h)

        def check(q):
            (got,) = ex.execute("c", f"TopN(fp, Row(fp={q}), n=4)")
            want = sorted(((len(sets[q] & s), -r) for r, s in sets.items()
                           if sets[q] & s), reverse=True)[:4]
            assert [tuple(p) for p in got] == [(-nr, c) for c, nr in want]

        check(2)                                    # 40 bits: 64 slots
        compiles = xla.snapshot()["families"]["sparse"]["compiles"]
        for q in (0, 1, 3, 4, 5, 6):                # 8 ... 2,048 slots
            check(q)
        assert xla.snapshot()["families"]["sparse"]["compiles"] == compiles
    finally:
        h.close()
