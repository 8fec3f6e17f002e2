"""BSI kernel tests vs. numpy integer ground truth.

Mirrors the reference's fragment BSI tests (fragment_internal_test.go:
setValue/sum/min/max/range cases) with randomized values.
"""

import numpy as np
import pytest

from pilosa_tpu.ops import bitvector as bv
from pilosa_tpu.ops import bsi

WIDTH = 1 << 16  # small shard width for test speed
DEPTH = 12
RNG = np.random.default_rng(7)


def make_planes(values: dict[int, int], depth=DEPTH, width=WIDTH):
    """Build dense bit planes + existence row from {column: value}."""
    planes = np.zeros((depth, width // 32), dtype=np.uint32)
    exists_cols = np.array(sorted(values), dtype=np.int64)
    for i in range(depth):
        cols = [c for c, v in values.items() if (v >> i) & 1]
        planes[i] = bv.dense_from_columns(np.array(cols, dtype=np.int64), width)
    exists = bv.dense_from_columns(exists_cols, width)
    return planes, exists


@pytest.fixture(scope="module")
def data():
    cols = np.unique(RNG.integers(0, WIDTH, size=800))
    values = {int(c): int(RNG.integers(0, 1 << DEPTH)) for c in cols}
    planes, exists = make_planes(values)
    return values, planes, exists


def test_sum(data):
    values, planes, exists = data
    counts = np.asarray(bsi.plane_counts(planes, exists))
    assert bsi.counts_to_sum(counts) == sum(values.values())
    assert int(bv.popcount(exists)) == len(values)


def test_sum_with_filter(data):
    values, planes, exists = data
    keep = [c for c in values if c % 3 == 0]
    filt = bv.dense_from_columns(np.array(keep, dtype=np.int64), WIDTH)
    filt = np.asarray(bv.band(filt, exists))
    counts = np.asarray(bsi.plane_counts(planes, filt))
    assert bsi.counts_to_sum(counts) == sum(values[c] for c in keep)


def test_min_max(data):
    values, planes, exists = data
    bits, cnt = bsi.bsi_min(planes, exists)
    vmin = min(values.values())
    assert bsi.bits_to_value(np.asarray(bits)) == vmin
    assert int(cnt) == sum(1 for v in values.values() if v == vmin)

    bits, cnt = bsi.bsi_max(planes, exists)
    vmax = max(values.values())
    assert bsi.bits_to_value(np.asarray(bits)) == vmax
    assert int(cnt) == sum(1 for v in values.values() if v == vmax)


def test_min_max_empty_candidate(data):
    _, planes, _ = data
    empty = np.zeros(WIDTH // 32, dtype=np.uint32)
    _, cnt = bsi.bsi_min(planes, empty)
    assert int(cnt) == 0
    _, cnt = bsi.bsi_max(planes, empty)
    assert int(cnt) == 0


def _pack(mask):
    """bool[..., n] -> uint32[..., n // 32], column 32 w + i at bit i of
    word w (the layout of bv.dense_from_columns)."""
    return np.packbits(mask, axis=-1, bitorder="little").view(np.uint32)


@pytest.fixture(scope="module")
def compare_sets(data):
    """name -> (values int64[..., columns], planes, exists, depth): the
    module's scattered values (`data`, one row of words a plane), and
    depth 6 over [3, 640] words (ragged shard and word counts) with a
    value in every column, the planes packed from the values, once under
    a full and once under a random existence mask."""
    values, planes, exists = data
    flat = np.zeros(WIDTH, dtype=np.int64)
    flat[list(values)] = list(values.values())
    rng = np.random.default_rng(11)
    vals = rng.integers(0, 1 << 6, size=(3, 640 * 32), dtype=np.int64)
    packed = np.stack([_pack(((vals >> i) & 1).astype(bool))
                       for i in range(6)])
    full = np.full((3, 640), 0xFFFFFFFF, dtype=np.uint32)
    masked = rng.integers(0, 2**32, size=(3, 640), dtype=np.uint32)
    return {"scattered": (flat, planes, exists, DEPTH),
            "packed": (vals, packed, full, 6),
            "packed_masked": (vals, packed, masked, 6)}


@pytest.mark.parametrize("op,pyop", [
    (bsi.LT, lambda v, p: v < p),
    (bsi.LTE, lambda v, p: v <= p),
    (bsi.GT, lambda v, p: v > p),
    (bsi.GTE, lambda v, p: v >= p),
    (bsi.EQ, lambda v, p: v == p),
    (bsi.NEQ, lambda v, p: v != p),
])
@pytest.mark.parametrize("name,pred", [
    ("scattered", p) for p in (0, 1, 1000, (1 << DEPTH) - 1, 2048)
] + [(name, p) for name in ("packed", "packed_masked")
     for p in (0, 1, 17, 63)])
def test_compare(compare_sets, name, op, pyop, pred):
    """Every op against numpy on the values the planes were built from;
    no column outside the existence row ever matches."""
    values, planes, exists, depth = compare_sets[name]
    pred_bits = bsi.value_to_bits(pred, depth)
    got = np.asarray(bsi.compare(planes, exists, pred_bits, op))
    np.testing.assert_array_equal(got, _pack(pyop(values, pred)) & exists)


@pytest.mark.parametrize("depth,s,w", [(1, 1, 512), (8, 3, 640),
                                       (24, 9, 512)])
def test_sum_counts_matches_numpy(depth, s, w):
    """Packed int32[depth + 1, S]: a row a plane of popcounts under the
    filter, the filter's own count last."""
    rng = np.random.default_rng(depth)
    planes = rng.integers(0, 2**32, size=(depth, s, w), dtype=np.uint32)
    filt = rng.integers(0, 2**32, size=(s, w), dtype=np.uint32)
    got = np.asarray(bsi.sum_counts(planes, filt))
    expect = np.concatenate([
        np.bitwise_count(planes & filt[None]).sum(axis=-1),
        np.bitwise_count(filt).sum(axis=-1)[None]])
    np.testing.assert_array_equal(got, expect)


def test_between(data):
    values, planes, exists = data
    a, b = 500, 3000
    lo = bsi.compare(planes, exists, bsi.value_to_bits(a, DEPTH), bsi.GTE)
    hi = bsi.compare(planes, exists, bsi.value_to_bits(b, DEPTH), bsi.LTE)
    got = set(bv.columns_from_dense(np.asarray(bv.band(lo, hi))).tolist())
    expect = {c for c, v in values.items() if a <= v <= b}
    assert got == expect


def test_value_bits_roundtrip():
    for v in (0, 1, 12345, (1 << 40) + 17):
        assert bsi.bits_to_value(bsi.value_to_bits(v, 48)) == v
    with pytest.raises(ValueError):
        bsi.value_to_bits(-1, 8)


def test_plane_slab_residency_reuse(tmp_path):
    """The stacked [depth, S', W] plane slab is residency-cached by plane
    generations: repeat aggregations must not re-miss, and a write must
    invalidate (new key -> one new miss)."""
    import numpy as np

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models import FieldOptions, FieldType, Holder

    h = Holder(str(tmp_path)).open()
    ex = Executor(h)
    idx = h.create_index("ps", track_existence=False)
    v = idx.create_field("v", FieldOptions(type=FieldType.INT, min=0, max=63))
    v.import_values(np.arange(100, dtype=np.uint64),
                    np.arange(100, dtype=np.int64) % 64)
    ex.execute("ps", "Sum(field=v)")
    misses0 = ex.residency.misses
    for _ in range(3):
        ex.execute("ps", "Sum(field=v)")
        ex.execute("ps", "Min(field=v)")
    assert ex.residency.misses == misses0  # warm: no new uploads or stacks
    (vc,) = ex.execute("ps", "Sum(field=v)")
    assert vc.count == 100
    ex.execute("ps", "Set(7, v=5)")  # mutation bumps plane generations
    (vc2,) = ex.execute("ps", "Sum(field=v)")
    assert vc2.val == vc.val - (7 % 64) + 5
    assert ex.residency.misses > misses0  # slab re-keyed and rebuilt
    h.close()
