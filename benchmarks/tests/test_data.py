"""The configuration's data: the same bytes for the same seed, and the
law its file states, for each data kind. The digests pinned here were
taken on the parent tree (b998f37), before the law became a file of its
own: the refactor leaves every byte of `segmentation` where it was."""

import concurrent.futures
import hashlib
import json
import os

import numpy as np
import pytest

from conftest import BENCH
from lib import byfile, datagen, roaring_wire

zipf = byfile.load("lib/data_kinds", "zipf")
categorical = byfile.load("lib/data_kinds", "categorical")

# sha256 over every (field, shard)'s import-roaring payload, shards=2
PARENT_DIGESTS = {
    2_800_000_011:
        "830fcce8d5b5a907dd493759fb0235931a8a62cade9cd49266aeb0ca50c688fa",
    41: "e074125313c5554a675ff3b63555af6c185fab6779f7c044e8ff829c9495982d",
}

SHARDS = 2


def config(name):
    with open(os.path.join(BENCH, "configs", name, "config.json")) as fh:
        return json.load(fh)


def digest(data) -> str:
    h = hashlib.sha256()
    for field in data.fields:
        rows = data.fields[field]
        for s in range(data.n_shards):
            h.update(roaring_wire.fragment_payload(
                [(r, rows[r].shard_piece(s)) for r in sorted(rows)]))
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(PARENT_DIGESTS))
def test_segmentation_is_byte_for_byte_the_parents(seed):
    data = datagen.make(config("segmentation"), seed, shards=SHARDS)
    assert digest(data) == PARENT_DIGESTS[seed]


def test_same_seed_same_bytes():
    cfg = config("segmentation")
    a = digest(datagen.make(cfg, 3_000_000_019, shards=SHARDS))
    b = digest(datagen.make(cfg, 3_000_000_019, shards=SHARDS))
    c = digest(datagen.make(cfg, 7, shards=SHARDS))
    assert a == b and a != c


def test_offset_follows_from_the_ratio():
    for n, s, ratio in ((400, 1.01, 0.25), (1 << 26, 1.01, 0.25),
                        (1000, 2.0, 0.01)):
        v = datagen.zipf_offset(n, s, ratio)
        assert abs((v / (v + n - 1)) ** s - ratio) < 1e-9
    w = datagen.rank_weights(400, 1.01, 0.25)
    assert abs(w.sum() - 1) < 1e-12 and abs(w[-1] / w[0] - 0.25) < 1e-9
    assert np.all(np.diff(w) < 0)


def test_column_ranks_follow_the_law():
    rng = np.random.default_rng(3)
    n = 1 << 22
    ranks = zipf.draw_ranks(rng, 4_000_000, n, 1.01, 0.25)
    assert ranks.min() >= 0 and ranks.max() < n
    got = np.bincount(ranks * 8 // n, minlength=8) / ranks.size
    v = datagen.zipf_offset(n, 1.01, 0.25)
    edges = (v + np.arange(9) * n / 8) ** -0.01
    want = (edges[:-1] - edges[1:]) / (edges[0] - edges[-1])
    assert np.all(np.abs(got - want) < 0.002)
    assert 3.0 < got[0] / got[-1] < 4.0     # 4 : 1 end to end


def test_segmentation_cardinalities():
    cfg = config("segmentation")
    spec = cfg["fields"][0]
    assert cfg["rows"] == sum(f["rows"] for f in cfg["fields"]) == 400
    assert cfg["columns"] == cfg["shards"] << 20
    data = datagen.make(cfg, 11, shards=SHARDS)
    rows = data.fields["seg"]
    assert sorted(rows) == list(range(400))
    total = sum(r.count() for r in rows.values())
    want = spec["set_bits_per_shard"] * SHARDS
    assert 0.99 * want < total <= want          # a bit set twice is one bit
    per = np.sort([r.count() / SHARDS for r in rows.values()])
    assert 3.6 < per[-1] / per[0] < 4.4         # ratio 0.25
    # rows on both sides of 4096 bits a shard, none under 2048: two sizes
    # of sorted list at the most, and planes
    top = np.array([r.bits_per_shard().max() for r in rows.values()])
    assert 150 < np.count_nonzero(top > 4096) < 250 and top.min() > 2048
    for r in (0, 57, 399):
        cols = rows[r].cols
        assert cols.dtype == np.uint32 and np.all(np.diff(cols) > 0)
        assert cols[-1] < SHARDS << 20
        assert sum(rows[r].shard_piece(s).size for s in range(SHARDS)) \
            == cols.size
    # shards hold alike: the permutation scatters the likely columns
    per_shard = sum(r.bits_per_shard() for r in rows.values())
    assert per_shard.max() / per_shard.min() < 1.02


def test_row_forms_agree():
    """A row's columns and the same row packed are the same set."""
    rng = np.random.default_rng(5)
    cols = np.unique(rng.integers(0, SHARDS << 20, size=200_000,
                                  dtype=np.uint32))
    words = datagen.pack_columns(cols, SHARDS)
    back = np.flatnonzero(np.unpackbits(words.view(np.uint8),
                                        bitorder="little"))
    assert np.array_equal(back, cols)
    assert datagen.Row(SHARDS, cols).count() == cols.size
    assert int(np.bitwise_count(words).sum()) == cols.size


CATEGORICAL = {"shards": SHARDS, "fields": [
    {"name": "cab", "kind": "categorical", "rows": 3, "first_id": 1,
     "weights": [6, 3, 1]},
    {"name": "cell", "kind": "categorical", "rows": 500,
     "value_exponent": 1.2, "value_ratio": 0.01, "present": 0.75},
    {"name": "seg", "rows": 5, "set_bits_per_shard": 50_000,
     "row_exponent": 1.01, "row_ratio": 0.5,
     "column_exponent": 1.01, "column_ratio": 0.25}]}


def test_categorical_every_column_holds_one_value():
    data = datagen.make(CATEGORICAL, 3_000_000_007)
    n_cols = SHARDS << 20
    cab, cell = data.fields["cab"], data.fields["cell"]
    assert sorted(cab) == [1, 2, 3] and sorted(cell) == list(range(500))
    for rows, present in ((cab, 1.0), (cell, 0.75)):
        cols = np.concatenate([r.cols for r in rows.values()])
        assert cols.dtype == np.uint32
        assert np.unique(cols).size == cols.size       # never two values
        assert abs(cols.size / n_cols - present) < 0.002
        for r in rows.values():
            assert np.all(np.diff(r.cols.astype(np.int64)) > 0)
            assert sum(r.shard_piece(s).size for s in range(SHARDS)) \
                == r.cols.size
    assert sum(r.count() for r in cab.values()) == n_cols   # and never none
    # the stated weights, id by id
    for row_id, share in zip((1, 2, 3), (0.6, 0.3, 0.1)):
        assert abs(cab[row_id].count() / n_cols - share) < 0.002
    # the stated law over ranks, scattered over the ids
    sizes = np.sort([r.count() for r in cell.values()])[::-1]
    want = datagen.rank_weights(500, 1.2, 0.01) * 0.75 * n_cols
    assert np.all(np.abs(sizes - want) < 5 * np.sqrt(want) + 1)
    by_id = np.array([cell[i].count() for i in range(500)])
    assert not np.array_equal(np.argsort(-by_id), np.arange(500))
    # the zipf field beside them is made as ever
    assert len(data.fields["seg"]) == 5


def test_categorical_bytes_do_not_depend_on_threads():
    spec = CATEGORICAL["fields"][1]
    made = []
    for workers in (1, 8):
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            rows = categorical.make_field(77, 1, spec, 4, pool)
        h = hashlib.sha256()
        for r in sorted(rows):
            h.update(rows[r].cols.tobytes())
        made.append(h.hexdigest())
    assert made[0] == made[1]
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        other = categorical.make_field(78, 1, spec, 4, pool)
    assert any(not np.array_equal(other[r].cols, rows[r].cols) for r in rows)


def test_categorical_refuses_weights_that_do_not_fit():
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        with pytest.raises(ValueError):
            categorical.make_field(1, 0, {"name": "f", "rows": 3,
                                          "weights": [1, 2]}, 1, pool)


def test_an_unknown_kind_names_the_file_it_wants():
    with pytest.raises(FileNotFoundError, match="lib/data_kinds/nosuch.py"):
        datagen.make({"shards": 1, "fields": [
            {"name": "f", "kind": "nosuch"}]}, 1)
