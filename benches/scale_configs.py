"""BASELINE configs 2-4 at spec scale (the 1B-row regime).

Runs the three synthetic BASELINE.json configs that round 3 never exercised
at size, through PRODUCTION code paths (frozen bulk load -> Holder/Field ->
Executor.execute):

  config2  100M-row x 10K-col set field; Union/Intersect/Xor/Difference
           (+Count) between heavy rows.
  config3  TopN(n=1000) over a ranked-cache field with 1B rows across 8
           shards (zipf head + 1-bit tail). Asserts the threshold walk
           recounts ≪ total rows and reports peak host RSS + HBM residency.
  config4  BSI int field over ~1B columns (954 shards): Sum(Range(v>thr))
           through the device plane kernels.

Each config appends one JSON line to benches/scale_results.jsonl as it
finishes (a wedge loses only the unfinished tail) and prints it. Scale via
PILOSA_SCALE=1.0 (full spec) / 0.01 (smoke). Platform: uses the default
backend (the chip, when run through the chip tool; force cpu for a smoke
with PILOSA_SCALE_PLATFORM=cpu).

Reference anchors: fragment.go:1018-1150 (TopN threshold walk),
fragment.go:718-985 + executor.go:363 (BSI range+sum), executor.go:1521
(Count), roaring bulk import fragment.go:1445-1706.
"""

import json
import os
import resource
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from pilosa_tpu.constants import SHARD_WIDTH  # noqa: E402

SCALE = float(os.environ.get("PILOSA_SCALE", "1.0"))
PLATFORM = os.environ.get("PILOSA_SCALE_PLATFORM", "")
OUT = os.path.join(HERE, "scale_results.jsonl")

C2_ROWS = int(100_000_000 * SCALE)
C3_ROWS = int(1_000_000_000 * SCALE)
C3_SHARDS = 8
C4_COLS = int(1_000_000_000 * SCALE)


def rss_gb() -> float:
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2)


def emit(rec: dict) -> None:
    rec["scale"] = SCALE
    rec["peak_rss_gb"] = rss_gb()
    with open(OUT, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec), flush=True)


def _p50(samples):
    return sorted(samples)[len(samples) // 2]


def config2(holder, ex):
    """100M rows x 10K cols: tail rows 1 bit, head rows dense-ish."""
    t0 = time.time()
    rng = np.random.default_rng(2)
    n_cols = 10_000
    # tail: one bit per row; head rows 0..63: ~2000 bits each
    tail_rows = np.arange(64, C2_ROWS, dtype=np.uint64)
    tail_cols = rng.integers(0, n_cols, tail_rows.size).astype(np.uint64)
    head_rows = np.repeat(np.arange(64, dtype=np.uint64), 2000)
    head_cols = rng.integers(0, n_cols, head_rows.size).astype(np.uint64)
    rows = np.concatenate([head_rows, tail_rows])
    cols = np.concatenate([head_cols, tail_cols])
    idx = holder.create_index("c2", track_existence=False)
    f = idx.create_field("f")
    f.import_rows_frozen(rows, cols)
    build_s = time.time() - t0
    del rows, cols, tail_rows, tail_cols

    sets = {r: set() for r in range(4)}
    for r, c in zip(head_rows[head_rows < 4], head_cols[head_rows < 4]):
        sets[int(r)].add(int(c))
    expect = {
        "union": len(sets[0] | sets[1]),
        "intersect": len(sets[0] & sets[1]),
        "xor": len(sets[0] ^ sets[1]),
        "difference": len(sets[0] - sets[1]),
    }
    qs = {
        "union": "Count(Union(Row(f=0), Row(f=1)))",
        "intersect": "Count(Intersect(Row(f=0), Row(f=1)))",
        "xor": "Count(Xor(Row(f=0), Row(f=1)))",
        "difference": "Count(Difference(Row(f=0), Row(f=1)))",
    }
    lat = {}
    for name, q in qs.items():
        (got,) = ex.execute("c2", q)  # warm + correctness
        assert got == expect[name], (name, got, expect[name])
        samples = []
        for _ in range(9):
            t = time.perf_counter()
            ex.execute("c2", q)
            samples.append(time.perf_counter() - t)
        lat[name] = round(_p50(samples) * 1e3, 3)
    emit({"config": 2, "rows": C2_ROWS, "cols": n_cols,
          "build_s": round(build_s, 1), "p50_ms": lat,
          "bits": int(head_rows.size + C2_ROWS - 64)})
    holder.delete_index("c2")
    ex.clear_caches()


def config3(holder, ex):
    """1B rows / 8 shards: zipf head + 1-bit tail; TopN(n=1000).

    Generation is PER SHARD so peak transient memory stays ~O(rows/shards)
    — materializing the global (rows, cols) pair at 1B rows costs ~100 GB
    of transients, which is exactly the regime the frozen path exists to
    avoid. Tail rows stripe across shards (row r -> shard r % 8, one bit
    at a random column); head rows 0..50k scatter bits over every shard."""
    t0 = time.time()
    rng = np.random.default_rng(3)
    idx = holder.create_index("c3", track_existence=False)
    f = idx.create_field("t")
    view = f.create_view_if_not_exists("standard")
    head_n = np.minimum(2000, C3_ROWS // (10 * (np.arange(50_000) + 1)))
    head_n = np.maximum(head_n, 1)
    head_rows_all = np.repeat(np.arange(50_000, dtype=np.uint64), head_n)
    w = np.uint64(SHARD_WIDTH)
    n_bits = 0
    for s in range(C3_SHARDS):
        # this shard's slice of each head row's bits (random subset by
        # assigning each head bit a random shard)
        head_shards = rng.integers(0, C3_SHARDS, head_rows_all.size)
        h_rows = head_rows_all[head_shards == s]
        h_cols = rng.integers(0, SHARD_WIDTH, h_rows.size).astype(np.uint64)
        t_rows = np.arange(50_000 + s, C3_ROWS, C3_SHARDS, dtype=np.uint64)
        t_cols = rng.integers(0, SHARD_WIDTH, t_rows.size).astype(np.uint64)
        positions = np.concatenate([h_rows * w + h_cols, t_rows * w + t_cols])
        del h_rows, h_cols, t_rows, t_cols
        positions = np.unique(positions)
        n_bits += positions.size
        view.load_frozen_fragment(s, positions)
        f.add_available_shard(s)
        del positions
    build_s = time.time() - t0
    del head_rows_all

    ex.topn_recount_rows = 0
    (pairs,) = ex.execute("c3", "TopN(t, n=1000)")  # warm + compile
    assert len(pairs) == 1000
    # winners must be zipf-head rows (capped head counts tie, so the
    # exact top row varies with the random shard split)
    assert pairs[0][0] < 50_000 and pairs[0][1] >= pairs[-1][1]
    samples = []
    for _ in range(9):
        t = time.perf_counter()
        ex.execute("c3", "TopN(t, n=1000)")
        samples.append(time.perf_counter() - t)
    recounts = ex.topn_recount_rows
    res = ex.residency.snapshot()
    assert recounts < C3_ROWS // 1000, \
        f"recounted {recounts} of {C3_ROWS} rows — pruning broken"
    assert res["bytes"] <= ex.residency.budget, res
    # Rows paging at 1B rows: the per-shard limit pushdown keeps this
    # O(shards * k) instead of O(total rows)
    (first,) = ex.execute("c3", "Rows(field=t, limit=100)")
    assert list(first) == list(range(100))
    rows_samples = []
    for i in range(9):
        t = time.perf_counter()
        ex.execute("c3", f"Rows(field=t, previous={i * 1000}, limit=100)")
        rows_samples.append(time.perf_counter() - t)
    rec = {"config": 3, "rows": C3_ROWS, "shards": C3_SHARDS,
           "bits": n_bits, "build_s": round(build_s, 1),
           "topn_p50_ms": round(_p50(samples) * 1e3, 3),
           "topn_recount_rows": recounts,
           "rows_page100_p50_ms": round(_p50(rows_samples) * 1e3, 3),
           "residency_bytes": res["bytes"],
           "residency_budget": ex.residency.budget}
    if os.environ.get("PILOSA_SCALE_SNAPSHOT") == "1":
        # durable round trip at scale: vectorized snapshot of shard 0's
        # frozen fragment + frozen reopen (storage/frozen.py write_pilosa)
        frag = f.view("standard").fragment(0)
        t = time.perf_counter()
        frag.snapshot()
        rec["snapshot_shard0_s"] = round(time.perf_counter() - t, 1)
        rec["snapshot_shard0_gb"] = round(
            os.path.getsize(frag.path) / 1e9, 2)
    emit(rec)
    holder.delete_index("c3")
    ex.clear_caches()


def config4(holder, ex):
    """~1B columns of BSI ints over ceil(C4/2^20) shards: Sum(Range)."""
    from pilosa_tpu.models import FieldOptions, FieldType

    t0 = time.time()
    rng = np.random.default_rng(4)
    n_shards = max(1, C4_COLS // SHARD_WIDTH)
    n = n_shards * SHARD_WIDTH
    idx = holder.create_index("c4", track_existence=False)
    v = idx.create_field("v", FieldOptions(type=FieldType.INT,
                                           min=0, max=1023))
    # import in 64M-column chunks to bound transient memory; track the
    # exact sums for correctness without keeping all values resident
    chunk = 64 * SHARD_WIDTH
    tot_all = 0
    cnt_gt = 0
    sum_gt = 0
    thr = 511
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        vals = rng.integers(0, 1024, hi - lo).astype(np.int64)
        v.import_values_frozen(np.arange(lo, hi, dtype=np.uint64), vals)
        m = vals > thr
        tot_all += int(vals.sum())
        cnt_gt += int(m.sum())
        sum_gt += int(vals[m].sum())
        del vals, m
    build_s = time.time() - t0

    (vc,) = ex.execute("c4", f"Sum(Range(v > {thr}), field=v)")
    assert vc.val == sum_gt and vc.count == cnt_gt, \
        (vc, sum_gt, cnt_gt)
    samples = []
    for i in range(7):
        t = time.perf_counter()
        ex.execute("c4", f"Sum(Range(v > {256 + 32 * i}), field=v)")
        samples.append(time.perf_counter() - t)
    res = ex.residency.snapshot()
    emit({"config": 4, "columns": n, "shards": n_shards,
          "build_s": round(build_s, 1),
          "sum_range_p50_ms": round(_p50(samples) * 1e3, 3),
          "residency_bytes": res["bytes"]})
    holder.delete_index("c4")
    ex.clear_caches()


def main() -> None:
    if PLATFORM:
        from pilosa_tpu.parallel.mesh import force_platform

        force_platform(PLATFORM)
    import shutil
    import tempfile

    import jax

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models import Holder

    only = set(sys.argv[1:])
    tmp = tempfile.mkdtemp(prefix="pilosa-scale-")
    try:
        holder = Holder(tmp).open()
        ex = Executor(holder)
        print(f"# scale={SCALE} backend={jax.default_backend()} "
              f"device={jax.devices()[0]}", flush=True)
        for name, fn in (("config2", config2), ("config3", config3),
                         ("config4", config4)):
            if only and name not in only:
                continue
            try:
                fn(holder, ex)
            except Exception as e:  # noqa: BLE001 — keep measuring
                emit({"config": name, "error": f"{type(e).__name__}: {e}"})
        holder.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
