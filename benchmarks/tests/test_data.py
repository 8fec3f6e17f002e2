"""The configuration's data: the same bytes for the same seed, and the
law its file states."""

import hashlib
import json
import os

import numpy as np

from conftest import BENCH
from lib import datagen, roaring_wire

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
    ranks = datagen.draw_ranks(rng, 4_000_000, n, 1.01, 0.25)
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
