"""Host-side microbenchmarks — the analog of the reference's Go benchmark
suite (SURVEY.md §6: roaring container ops roaring/roaring_test.go:1364-1522,
fragment import/snapshot/checksum fragment_internal_test.go:1135-1986).

These measure the storage plane (numpy + C++ kernels); the TPU query plane
is measured by benchmarks/run.py on the chip. Prints one JSON line per metric:
    {"metric": ..., "value": ..., "unit": ...}

Run: python benches/micro.py [--quick]
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from pilosa_tpu.storage.fragment import Fragment  # noqa: E402
from pilosa_tpu.storage.roaring import Bitmap, Container  # noqa: E402


def timeit(fn, repeat=5):
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def emit(metric, seconds, unit="ops/s", scale=1.0):
    print(json.dumps({"metric": metric,
                      "value": round(scale / seconds, 2),
                      "unit": unit}))


def bench_container_ops(quick):
    rng = np.random.default_rng(1)
    arr_a = Container.from_values(np.unique(rng.integers(0, 65536, 3000).astype(np.uint16)))
    arr_b = Container.from_values(np.unique(rng.integers(0, 65536, 3000).astype(np.uint16)))
    bm_a = Container.from_values(np.unique(rng.integers(0, 65536, 20000).astype(np.uint16)))
    bm_b = Container.from_values(np.unique(rng.integers(0, 65536, 20000).astype(np.uint16)))
    cases = {
        "container_intersect_count_array_array": (arr_a, arr_b),
        "container_intersect_count_array_bitmap": (arr_a, bm_b),
        "container_intersect_count_bitmap_bitmap": (bm_a, bm_b),
    }
    n = 200 if quick else 2000
    for name, (a, b) in cases.items():
        dt = timeit(lambda a=a, b=b: [a.op_count(b, "and") for _ in range(n)])
        emit(name, dt, scale=n)
    for kind in ("and", "or", "xor", "andnot"):
        dt = timeit(lambda: [bm_a.op(bm_b, kind) for _ in range(n)])
        emit(f"container_op_{kind}_bitmap_bitmap", dt, scale=n)


def bench_bitmap(quick):
    rng = np.random.default_rng(2)
    size = 200_000 if quick else 2_000_000
    vals = np.unique(rng.integers(0, 1 << 26, size).astype(np.uint64))
    parts = np.array_split(vals, 8)
    bitmaps = [Bitmap(p) for p in parts]

    dt = timeit(lambda: Bitmap(vals))
    emit("bitmap_build", dt, unit="bits/s", scale=vals.size)

    def union_in_place():
        dst = Bitmap()
        dst.union_in_place(*bitmaps)
    dt = timeit(union_in_place)
    emit("bitmap_union_in_place_8way", dt, unit="bits/s", scale=vals.size)

    b = Bitmap(vals)
    dt = timeit(lambda: b.to_bytes())
    emit("bitmap_serialize", dt, unit="bits/s", scale=vals.size)
    blob = b.to_bytes()
    dt = timeit(lambda: Bitmap.from_bytes(blob))
    emit("bitmap_parse", dt, unit="bits/s", scale=vals.size)
    probe = vals[:: max(1, vals.size // 100_000)]
    dt = timeit(lambda: b.contains_many(probe))
    emit("bitmap_contains_many", dt, unit="probes/s", scale=probe.size)


def bench_fragment(quick):
    rng = np.random.default_rng(3)
    n = 100_000 if quick else 1_000_000
    rows = rng.integers(0, 100, n).astype(np.uint64)
    cols = rng.integers(0, 1 << 20, n).astype(np.uint64)
    with tempfile.TemporaryDirectory() as d:
        frag = Fragment(os.path.join(d, "0"), "i", "f", "standard", 0).open()
        t0 = time.perf_counter()
        frag.bulk_import(rows, cols)
        dt = time.perf_counter() - t0
        emit("fragment_bulk_import", dt, unit="bits/s", scale=n)

        dt = timeit(lambda: frag.blocks())
        emit("fragment_block_checksums", dt, unit="blocks/s",
             scale=len(frag.blocks()))

        dt = timeit(lambda: frag.snapshot())
        emit("fragment_snapshot", dt, unit="snapshots/s", scale=1)

        dt = timeit(lambda: [frag.row_dense(int(r)) for r in range(10)])
        emit("fragment_row_materialize", dt, unit="rows/s", scale=10)
        frag.close()


def bench_container_stores(quick):
    """dict vs B+Tree container stores (storage/containers.py — the
    sliceContainers vs enterprise/b comparison): point ops and the ordered
    walks the ordered store exists for."""
    rng = np.random.default_rng(5)
    size = 100_000 if quick else 1_000_000
    # sparse high-48-bit key space: the memory-lean-sparse-fragment shape
    vals = np.unique(
        rng.integers(0, 1 << 40, size).astype(np.uint64) << np.uint64(16))
    for store in ("dict", "btree"):
        b = Bitmap(store=store)
        t0 = time.perf_counter()
        b.add_many(vals)
        emit(f"store_{store}_build", time.perf_counter() - t0,
             unit="keys/s", scale=len(b.containers))
        lo = int(vals[vals.size // 4])
        hi = int(vals[3 * vals.size // 4])
        dt = timeit(lambda: b._keys_in(lo, hi))
        emit(f"store_{store}_range_keys", dt, unit="walks/s", scale=1)
        dt = timeit(lambda: (b.min(), b.max()))
        emit(f"store_{store}_min_max", dt, unit="calls/s", scale=2)


def main():
    quick = "--quick" in sys.argv
    bench_container_ops(quick)
    bench_bitmap(quick)
    bench_fragment(quick)
    bench_container_stores(quick)


if __name__ == "__main__":
    main()
