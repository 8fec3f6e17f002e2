"""import-roaring into an empty fragment through the flat store
(Fragment.import_roaring -> Bitmap.flat_store_from_bytes ->
storage/frozen.py) against the container path: the same rows, counts,
rank cache, snapshot read back after a restart, and WAL behaviour, on a
payload like `segmentation`'s (a few hundred rows of some thousand bits)
and one like a grid field's (10,000 rows of a hundred). The container path
is the same method on a fragment that already holds a bit, which the
first import's test is `not self.storage.any()`.

And Field.add_available_shard under concurrent imports (ROADMAP D0): four
imports at once used to lose an acknowledged shard.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import roaring_wire  # noqa: E402

from pilosa_tpu.constants import SHARD_WIDTH  # noqa: E402
from pilosa_tpu.models import FieldOptions, Holder  # noqa: E402
from pilosa_tpu.models.view import View  # noqa: E402
from pilosa_tpu.storage.fragment import Fragment  # noqa: E402
from pilosa_tpu.storage.roaring import Bitmap  # noqa: E402


def rows_like(kind: str) -> dict:
    """{row: sorted shard-local columns}."""
    rng = np.random.default_rng(len(kind))
    if kind == "segmentation":
        sizes = {r: int(rng.integers(2400, 9700)) for r in range(120)}
        sizes[7] = 70000          # bitmap containers too
    else:                          # a grid field: 10,000 rows, few bits each
        sizes = {r: int(rng.integers(1, 200)) for r in range(10000)}
        sizes[3] = 21000
    return {r: np.unique(rng.integers(0, SHARD_WIDTH, n)).astype(np.uint32)
            for r, n in sizes.items()}


def payload(rows: dict) -> bytes:
    return roaring_wire.fragment_payload([(r, rows[r]) for r in sorted(rows)])


def open_frag(path) -> Fragment:
    return Fragment(str(path), "i", "f", "standard", 0).open()


def state(frag: Fragment, rows: dict) -> dict:
    ids = sorted(rows)
    view = View.__new__(View)      # only _rebuilt_rank_cache's own needs
    view.cache_type, view.cache_size = "ranked", 50000
    cache = view._rebuilt_rank_cache(frag)
    return {
        "row_ids": frag.row_ids(),
        "counts": frag.row_counts(ids).tolist(),
        "bits": frag.bit_count(),
        "columns": {r: frag.row_columns(r).tolist()
                    for r in (ids[0], ids[3], ids[7], ids[-1])},
        "rank": [tuple(p) for p in cache.top()[:50]],
        "positions": frag.storage.positions().tolist(),
    }


@pytest.mark.parametrize("kind", ["segmentation", "grid"])
def test_flat_import_equals_the_container_path(tmp_path, kind):
    rows = rows_like(kind)
    data = payload(rows)
    flat = open_frag(tmp_path / "flat")
    assert Bitmap.flat_store_from_bytes(data) is not None
    flat.import_roaring(data)
    # the container path: the fragment holds a bit when the import comes
    # (a bit of the payload's own, so the union is the payload)
    cont = open_frag(tmp_path / "cont")
    cont.set_bit(0, int(rows[0][0]))
    cont.import_roaring(data)
    want = {r: c.tolist() for r, c in rows.items()}
    a, b = state(flat, rows), state(cont, rows)
    assert a == b
    assert a["row_ids"] == sorted(rows)
    assert a["counts"] == [len(want[r]) for r in sorted(rows)]
    assert all(a["columns"][r] == want[r] for r in a["columns"])
    # durable like the container path: the request is answered after the
    # snapshot, the WAL is attached and empty
    for frag in (flat, cont):
        assert not frag._volatile and frag.op_n == 0
        assert frag.storage.op_writer is not None
    # later Sets go to the WAL and survive a restart with the snapshot
    col = int(np.setdiff1d(np.arange(64), rows[5])[0])
    for frag in (flat, cont):
        assert frag.set_bit(5, col) and frag.op_n == 1
        frag.close()
    sizes = {}
    for name in ("flat", "cont"):
        sizes[name] = os.path.getsize(tmp_path / name)
        again = open_frag(tmp_path / name)
        got = state(again, rows)
        assert got["bits"] == a["bits"] + 1
        assert col in again.row_columns(5).tolist()
        assert got["columns"][sorted(rows)[-1]] == want[sorted(rows)[-1]]
        again.close()
    assert sizes["flat"] == sizes["cont"]


def test_a_second_import_and_clear_keep_the_container_path(tmp_path):
    rows = rows_like("segmentation")
    frag = open_frag(tmp_path / "f")
    frag.import_roaring(payload({r: rows[r] for r in range(60)}))
    frag.import_roaring(payload({r: rows[r] for r in range(40, 120)}))
    assert frag.row_counts(sorted(rows)).tolist() == [
        rows[r].size for r in sorted(rows)]
    frag.import_roaring(payload({3: rows[3]}), clear=True)
    assert frag.row_count(3) == 0 and frag.row_count(4) == rows[4].size
    frag.close()


@pytest.mark.parametrize("what", ["official-format", "empty", "op-log-tail",
                                  "truncated"])
def test_payloads_the_flat_parser_leaves_to_the_container_path(what):
    rows = {0: np.array([1, 5, 70000], np.uint32)}
    data = payload(rows)
    if what == "official-format":
        data = b"\x3a\x30" + data[2:]
    elif what == "empty":
        data = Bitmap().to_bytes()
    elif what == "op-log-tail":
        data = data + b"\x00" * 13
    else:
        data = data[:-3]
    assert Bitmap.flat_store_from_bytes(data) is None


def test_four_concurrent_imports_lose_no_shard(tmp_path):
    """Every acknowledged shard is in the field's available shards, in
    memory and in the file a restart reads (ROADMAP D0)."""
    h = Holder(str(tmp_path / "d")).open()
    f = h.create_index("i", track_existence=False).create_field(
        "f", FieldOptions())
    n_shards, rounds = 64, 2
    data = payload({0: np.array([3], np.uint32)})
    # the window between reading the bitmap's container and storing the
    # grown one, held open: without the field's lock two adds read the
    # same container and one of them is lost, every time
    read = f.available_shards._with_key

    def read_slowly(key):
        c = read(key)
        time.sleep(0.002)
        return c

    f.available_shards._with_key = read_slowly
    gate = threading.Barrier(4)
    errs = []

    def importer(k):
        try:
            gate.wait()
            for shard in range(k, n_shards, 4):
                frag = f.create_view_if_not_exists(
                    "standard").create_fragment_if_not_exists(shard)
                frag.import_roaring(data)
                f.add_available_shard(shard)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    for _ in range(rounds):
        threads = [threading.Thread(target=importer, args=(k,))
                   for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs, errs
    assert f.shards() == list(range(n_shards))
    assert f.shards_version == n_shards
    h.close()
    h = Holder(str(tmp_path / "d")).open()
    assert h.index("i").field("f").shards() == list(range(n_shards))
    h.close()
