"""Native C++ kernel tests: parity with the Python/numpy implementations."""

import numpy as np
import pytest

from pilosa_tpu import native
from pilosa_tpu.parallel.placement import fnv64a as py_fnv64a
from pilosa_tpu.storage.roaring import fnv1a32 as py_fnv1a32

RNG = np.random.default_rng(13)

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native build unavailable")


def test_build_succeeded():
    assert native.lib() is not None


def test_hashes_match_python():
    for data in (b"", b"a", b"foobar", bytes(RNG.integers(0, 256, 100, dtype=np.uint8))):
        assert native.fnv1a32(data) == py_fnv1a32(data)
        assert native.fnv64a(data) == py_fnv64a(data)


def test_popcounts():
    words = RNG.integers(0, 2**64, 4096, dtype=np.uint64)
    other = RNG.integers(0, 2**64, 4096, dtype=np.uint64)
    assert native.popcount64(words) == int(np.sum(np.bitwise_count(words)))
    assert native.and_count(words, other) == int(np.sum(np.bitwise_count(words & other)))


@pytest.mark.parametrize("kind,npop", [
    ("and", lambda a, b: np.intersect1d(a, b)),
    ("or", lambda a, b: np.union1d(a, b)),
    ("andnot", lambda a, b: np.setdiff1d(a, b)),
    ("xor", lambda a, b: np.setxor1d(a, b)),
])
def test_array_ops(kind, npop):
    a = np.unique(RNG.integers(0, 1 << 16, 3000)).astype(np.uint16)
    b = np.unique(RNG.integers(0, 1 << 16, 5000)).astype(np.uint16)
    got = native.array_op(a, b, kind)
    np.testing.assert_array_equal(got, npop(a, b).astype(np.uint16))
    # empties
    empty = np.empty(0, dtype=np.uint16)
    np.testing.assert_array_equal(native.array_op(a, empty, kind),
                                  npop(a, empty).astype(np.uint16))


def test_bits_roundtrip():
    vals = np.unique(RNG.integers(0, 1 << 16, 9000)).astype(np.uint16)
    words = native.array_to_bits(vals)
    assert native.popcount64(words) == vals.size
    back = native.bits_to_array(words)
    np.testing.assert_array_equal(back, vals)
    # edges
    edge = np.array([0, 63, 64, 65535], dtype=np.uint16)
    np.testing.assert_array_equal(native.bits_to_array(native.array_to_bits(edge)), edge)


def test_positions_to_dense():
    width = 1 << 20
    start = 5 * width
    offs = np.unique(RNG.integers(0, width, 5000)).astype(np.uint64)
    positions = offs + np.uint64(start)
    # plus out-of-range noise that must be ignored
    noise = np.array([0, start - 1, start + width, 2**63], dtype=np.uint64)
    dense = native.positions_to_dense(np.concatenate([positions, noise]), start, width)
    from pilosa_tpu.ops.bitvector import columns_from_dense
    np.testing.assert_array_equal(columns_from_dense(dense), offs.astype(np.int64))


def test_oplog_parse():
    import struct
    from pilosa_tpu.storage.roaring import OP_ADD, OP_REMOVE
    recs = []
    for typ, val in [(OP_ADD, 5), (OP_ADD, 2**40), (OP_REMOVE, 5)]:
        body = struct.pack("<BQ", typ, val)
        recs.append(body + struct.pack("<I", py_fnv1a32(body)))
    data = b"".join(recs)
    types, values = native.oplog_parse(data)
    assert types.tolist() == [OP_ADD, OP_ADD, OP_REMOVE]
    assert values.tolist() == [5, 2**40, 5]
    # corruption detected
    assert native.oplog_parse(data[:-1]) is None
    bad = bytearray(data)
    bad[9] ^= 0xFF
    assert native.oplog_parse(bytes(bad)) is None


# -- the loader: built from what git commits, never a foreign library --------


def _fresh_loader(monkeypatch, tmp_path):
    """Point the loader at a scratch directory holding only the source."""
    import shutil

    shutil.copy(native._SRC, tmp_path / "roaring_native.cc")
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(tmp_path / "roaring_native.cc"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)


def test_loader_never_loads_a_foreign_library(monkeypatch, tmp_path):
    """A library built on another machine (the git-ignored .so rides along
    in a directory copy) has another name — the name keys on this host's
    CPU flags — so it is never dlopen'ed: the loader builds its own."""
    _fresh_loader(monkeypatch, tmp_path)
    mine = native._so_path()
    monkeypatch.setattr(native, "_cpu_flags", lambda: "x86_64 some other cpu")
    foreign = native._so_path()
    monkeypatch.undo()
    _fresh_loader(monkeypatch, tmp_path)
    assert foreign != mine and native._so_path() == mine
    # what a copied tree carries: the old fixed name and a foreign build.
    # Loading either would fail loudly here (garbage, not ELF) — on a real
    # foreign CPU it would be an illegal instruction with no traceback
    for name in ("libroaring_native.so", foreign.rsplit("/", 1)[1]):
        (tmp_path / name).write_bytes(b"built for another machine")
    handle = native.lib()
    assert handle is not None and handle._name == mine
    assert native.popcount64(np.array([3, 1], dtype=np.uint64)) == 3
    # built under a temporary name and renamed into place: nothing left over
    assert not [p.name for p in tmp_path.iterdir()
                if p.name.startswith(".build-")]


def test_loader_name_follows_the_source(monkeypatch, tmp_path):
    _fresh_loader(monkeypatch, tmp_path)
    before = native._so_path()
    with open(native._SRC, "a") as f:
        f.write("\n// edited\n")
    assert native._so_path() != before


def test_failed_build_is_logged_not_swallowed(monkeypatch, tmp_path, caplog):
    _fresh_loader(monkeypatch, tmp_path)
    monkeypatch.setattr(native, "_CXX", ["g++", "--no-such-flag"])
    with caplog.at_level("WARNING", logger="pilosa_tpu.native"):
        assert native.lib() is None
        assert native.lib() is None  # logged once: the failure is latched
    logged = [r for r in caplog.records if "NOT built" in r.getMessage()]
    assert len(logged) == 1
    # the numpy path still answers
    assert native.popcount64(np.array([3, 1], dtype=np.uint64)) == 3
