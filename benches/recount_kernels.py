#!/usr/bin/env python3
"""Timings that chose the kernel of the TopN recount from sorted columns
(PR 29 the layouts, PR 30 the sum by rank; PERF.md section 6 has the
numbers). One resident entry of a field is, per shard, the concatenated
sorted columns of its small rows with the row's rank beside each; the
recount is counts[R] = sum over the entry's bits of the filter plane's bit
at that column. Variants timed here:

  gather            the bit test alone: take_along_axis of the filter's
                    word at every stored column (what every gather variant
                    has to pay)
  gather+scatter    bit test, then jax.ops.segment_sum by rank
  gather+cumsum     bit test, a cumsum along the entry and a gather of the
                    running sum at every row's end (ranks are sorted)
  gather+onehot     bit test, then the histogram by rank as a product of
                    two one-hot matrices on the matrix unit, the slot index
                    on the major axis of both ([step, H] and [step, 128])
  bycolumn+onehot   no gather: the entry laid out by column (one rank a
                    column, -1 where none), the filter's bits unpacked in
                    place, the same one-hot product. Only a field whose
                    columns hold at most one row of the entry allows it
  slot-major one-hot (PR 29), by pairs / by column
                    what PR 29 shipped: bit test and slot-major product
                    inside one scan step of 2^15 slots, the entry by
                    column in column order (int32[S, 2^20], bit minor)
  lane-major one-hot, XLA
                    PR 30's step: the entry by column laid bit-major
                    (int32[1, S, 32, W]), the one-hots transposed ([H,
                    slots] and [128, slots], slots on the lane axis), the
                    product contracting the last axis of both, left to XLA
                    in a scan (--steps sweeps the slots a step)
  shipped           ops/bitvector.py pairs_count as the executor calls it,
                    by pairs and by column; with the bytes of temporaries
                    the compiled program allocates

--crossover times the shipped program alone, in both layouts of one entry
at 2^10 ... 2^19 slots a shard under a count vector of 128 (H = 1) and of
16,384 (H = 128): the table ops/bitvector.py PAIRS_BY_COLUMN_SLOTS was set
from (PR 32), the slots from which an entry that may lie by column does.

--slab times the shipped program as a window slab (the rows' own words
over the window their columns touch, a row a lane) beside by pairs, in two
shapes of one shard. The similarity search's: --slab-rows fingerprints of
about 48 bits over 2,048 columns (500,000 rows: 2^25 slots, H = 4,096),
where by pairs' launch is also split into its gather (the bit test alone)
and its one-hot product (the sum by rank with every bit taken as set). A
grid field's (--rows rows across all 2^20 columns, --slots slots a shard,
by column too), where the window is the whole shard and the slab 2 GiB a
shard at 10,000 rows: the executor never takes it there. Each time is the
host's, to the fetched count vector: at the similarity shape that fetch
(2 MB) is most of the slab's time, and the device trace
(`recount_roofline`) gives the launch alone.

    chiprun -- python3 benches/recount_kernels.py            # the chip
    chiprun -- python3 benches/recount_kernels.py --crossover
    python3 benches/recount_kernels.py --slab                # on the chip
    python3 benches/recount_kernels.py --shards 2 --slots 8192 --rows 300
    python3 benches/recount_kernels.py --slab --slab-rows 3000 --rows 300 \
        --slots 8192
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

W = 1 << 15
C = 1 << 20


def make(seed, S, K, R, fill, density):
    rng = np.random.default_rng(seed)
    w = (5.0 + np.arange(R)) ** -1.1
    w /= w.sum()
    cols = np.full((S, K), C, np.int32)
    rank = np.full((S, K), R, np.int32)
    bycol = np.full((S, C), -1, np.int32)
    ends = np.zeros((S, R), np.int32)
    n = int(K * fill)
    for s in range(S):
        c = rng.permutation(C)[:n].astype(np.int32)
        r = rng.choice(R, size=n, p=w).astype(np.int32)
        order = np.lexsort((c, r))
        cols[s, :n], rank[s, :n] = c[order], r[order]
        bycol[s, c] = r
        ends[s] = np.cumsum(np.bincount(r, minlength=R))
    src = rng.integers(0, 1 << 32, size=(S, W), dtype=np.uint32)
    keep = rng.random((S, W, 32)) < density
    mask = (keep * (1 << np.arange(32, dtype=np.uint64))).sum(-1)
    src &= mask.astype(np.uint32)
    return cols, rank, bycol, ends, src


def oracle(cols, rank, src, R):
    out = np.zeros(R + 1, np.int64)
    for s in range(cols.shape[0]):
        ok = cols[s] < C
        c = cols[s][ok]
        bit = (src[s][c >> 5] >> (c & 31).astype(np.uint32)) & 1
        out += np.bincount(rank[s][ok], weights=bit, minlength=R + 1
                           ).astype(np.int64)
    return out[:R]


def timed(fn, args, check, reps):
    """Compile and two warm calls, then the median of `reps` fetches."""
    t0 = time.perf_counter()
    got = np.asarray(fn(*args))
    first = time.perf_counter() - t0
    ok = None if check is None else bool((got == check).all())
    np.asarray(fn(*args))     # a second warm call before the timed ones
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    return {"ms_min": min(ts), "ms_median": sorted(ts)[len(ts) // 2],
            "first_s": first, "agrees": ok}


def crossover(args):
    """The shipped program in both layouts of one entry, over the slots a
    shard and the count vector: one line a (H, slots), then the table."""
    import jax

    from pilosa_tpu.ops import bitvector as bv
    S = args.shards
    out = {"device": jax.devices()[0].device_kind, "shards": S, "rows": []}
    for R in (31, 9966):
        n_slots = bv.pairs_count_slots(R)
        for lg in range(args.min_log_slots, 20):
            K = 1 << lg
            cols, rank, _, _, src = make(32 + lg, S, K, R, 0.73, 0.05)
            want = oracle(cols, rank, src, R)
            kept = [(cols[s][cols[s] < C], rank[s][cols[s] < C])
                    for s in range(S)]
            src = jax.device_put(src)
            row = {"H": n_slots // 128, "slots": K,
                   "bits": int((cols < C).sum())}
            for layout, arr in (("pairs", np.stack([cols, rank])),
                                ("column", bv.pairs_by_column(kept))):
                arr = jax.device_put(arr)
                row[layout] = timed(
                    lambda p, f: bv.pairs_count(p, f, n_slots)[:R],
                    (arr, src), want, args.reps)
                del arr
            print(json.dumps(row), flush=True)
            out["rows"].append(row)
    print("| H | slots a shard | by pairs ms | by column ms | ns a slot by "
          "pairs | agrees |\n|---|---|---|---|---|---|")
    for r in out["rows"]:
        p, c = r["pairs"], r["column"]
        print(f"| {r['H']} | 2^{r['slots'].bit_length() - 1} | "
              f"{p['ms_median']:.2f} | {c['ms_median']:.2f} | "
              f"{p['ms_median'] * 1e6 / (S * r['slots']):.2f} | "
              f"{p['agrees'] and c['agrees']} |")
    print(json.dumps(out))


def fingerprints(seed, R, draws=49, width=2048):
    """R rows of `draws` positions drawn over `width` columns, repeats
    dropped (about 48 bits a row at 49 draws): (columns, ranks) sorted by
    rank, then column."""
    rng = np.random.default_rng(seed)
    p = np.sort(rng.integers(0, width, (R, draws), dtype=np.int32), axis=1)
    keep = np.ones(p.shape, bool)
    keep[:, 1:] = p[:, 1:] != p[:, :-1]
    rank = np.repeat(np.arange(R, dtype=np.int32), draws)[keep.ravel()]
    return p.ravel()[keep.ravel()], rank


def slab(args):
    """The shipped program by pairs and as a window slab, one shard, in the
    similarity search's shape and in a grid field's: one line a shape."""
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.ops import bitvector as bv
    out = {"device": jax.devices()[0].device_kind, "shapes": []}

    def one(name, kept, R, slots, extra=()):
        n_slots = bv.pairs_count_slots(R)
        cols, rank = kept[0]
        want = oracle(cols[None], rank[None], src, R)
        lo, n_words = bv.slab_window(kept)
        row = {"shape": name, "rows": R, "bits": int(cols.size),
               "slots": slots, "H": n_slots // 128, "window_words": n_words,
               "slab_bytes": n_words * n_slots * 4, "pairs_bytes": slots * 8}
        dsrc = jax.device_put(src)
        arr = jax.device_put(bv.pairs_by_pairs(kept, slots))
        row["pairs"] = timed(lambda p, f: bv.pairs_count(p, f, n_slots)[:R],
                             (arr, dsrc), want, args.reps)
        for label, fn, a in extra:
            row[label] = timed(fn, a(arr, dsrc), None, args.reps)
        del arr
        dev = jax.device_put(bv.pairs_by_slab(kept, lo, n_words, n_slots))
        row["slab"] = timed(
            lambda p, f, w: bv.pairs_count(p, f, n_slots, w)[:R],
            (dev, dsrc, np.int32(lo)), want, args.reps)
        row["slab_gb_per_s"] = (row["slab_bytes"] / 1e6
                                / row["slab"]["ms_median"])
        del dev
        print(json.dumps(row), flush=True)
        out["shapes"].append(row)

    # the similarity search: fingerprints over 2,048 columns of one shard
    R = args.slab_rows
    cols, rank = fingerprints(38, R)
    slots = 8
    while slots < cols.size:
        slots *= 2
    n_slots = bv.pairs_count_slots(R)
    rng = np.random.default_rng(380)
    src = rng.integers(0, 1 << 32, size=(1, W), dtype=np.uint32)

    @jax.jit
    def gather_alone(pairs, f):
        """by pairs' bit test over every slot, summed: the gather alone"""
        return jnp.sum(bv._dense_bit_test(pairs[0, 0], f[0]).astype(
            jnp.int32))

    def onehot_alone(pairs, f):
        """by pairs' launch with the bit test taken as every bit set: the
        one-hot product alone (the body traced with the test replaced)"""
        was = bv._dense_bit_test
        bv._dense_bit_test = lambda c, plane: c < bv.SPARSE_SENTINEL
        try:
            return onehot_fn(pairs, f)
        finally:
            bv._dense_bit_test = was

    onehot_fn = jax.jit(lambda p, f: bv.pairs_count_local(p, f, n_slots))
    one("similarity", [(cols, rank)], R, slots, extra=(
        ("pairs_gather_alone", gather_alone, lambda a, f: (a, f)),
        ("pairs_onehot_alone", onehot_alone, lambda a, f: (a, f))))

    # a grid field's shard: rows across all 2^20 columns
    R, K = args.rows, args.slots
    gcols, grank, _, _, gsrc = make(29, 1, K, R, 0.73, 0.05)
    ok = gcols[0] < C
    src = gsrc
    kept = [(gcols[0][ok], grank[0][ok])]
    n_slots = bv.pairs_count_slots(R)
    bycol = jax.device_put(bv.pairs_by_column(kept))
    one("grid", kept, R, K, extra=(
        ("column", lambda b, f: bv.pairs_count(b, f, n_slots)[:R],
         lambda a, f: (bycol, f)),))
    del bycol
    print("| shape | rows | bits | slab MB | pairs MB | by pairs ms | "
          "slab ms | slab GB/s | agrees |\n|---|---|---|---|---|---|---|---|"
          "---|")
    for r in out["shapes"]:
        print(f"| {r['shape']} | {r['rows']} | {r['bits']} | "
              f"{r['slab_bytes'] / 1e6:.1f} | {r['pairs_bytes'] / 1e6:.1f} | "
              f"{r['pairs']['ms_median']:.3f} | {r['slab']['ms_median']:.3f} "
              f"| {r['slab_gb_per_s']:.1f} | "
              f"{r['pairs']['agrees'] and r['slab']['agrees']} |")
    print(json.dumps(out))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=32)
    ap.add_argument("--slots", type=int, default=1 << 20)
    ap.add_argument("--rows", type=int, default=9966)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--steps", type=int, nargs="*", default=[],
                    help="slots a scan step of the XLA body, to time "
                    "beside the shipped PAIRS_STEP")
    ap.add_argument("--explore", action="store_true",
                    help="also the count vector rounded to 16 rows of "
                    "128, tried and not shipped")
    ap.add_argument("--only", default="",
                    help="time only the variants whose name holds this")
    ap.add_argument("--crossover", action="store_true",
                    help="only the shipped program, by pairs against by "
                    "column, over the slots a shard at H = 1 and H = 128")
    ap.add_argument("--min-log-slots", type=int, default=10,
                    help="--crossover starts at 2^this slots a shard")
    ap.add_argument("--slab", action="store_true",
                    help="only the shipped program as a window slab beside "
                    "by pairs, at the similarity search's shape and a grid "
                    "field's, one shard each")
    ap.add_argument("--slab-rows", type=int, default=500_000,
                    help="--slab: fingerprint rows of the similarity shape")
    args = ap.parse_args()
    if args.crossover:
        return crossover(args)
    if args.slab:
        return slab(args)
    import jax
    import jax.numpy as jnp
    S, K, R = args.shards, args.slots, args.rows
    H = 1
    while H * 128 < R + 1:
        H *= 2
    chunk = min(K, 1 << 15)

    def bit_test(cols, src):
        safe = jnp.minimum(cols, C - 1)
        w = jnp.take_along_axis(src, safe >> 5, axis=-1)
        return ((w >> (safe & 31).astype(jnp.uint32)) & 1).astype(
            jnp.int32) * (cols < C)

    @jax.jit
    def gather(cols, src):
        return jnp.sum(bit_test(cols, src))

    @jax.jit
    def gather_scatter(cols, rank, src):
        bit = bit_test(cols, src).reshape(-1)
        return jax.ops.segment_sum(bit, rank.reshape(-1), R + 1)[:R]

    @jax.jit
    def gather_cumsum(cols, ends, src):
        run = jnp.cumsum(bit_test(cols, src), axis=-1)
        run = jnp.concatenate([jnp.zeros((S, 1), jnp.int32), run], axis=-1)
        at = jnp.take_along_axis(run, ends, axis=-1)          # [S, R]
        per = at - jnp.concatenate(
            [jnp.zeros((S, 1), jnp.int32), at[:, :-1]], axis=-1)
        return jnp.sum(per, axis=0)

    def hist(rank, bit):
        """rank [n, chunk] int32, bit [n, chunk] bool -> int32[H * 128]."""
        hi_ids = jnp.arange(H, dtype=jnp.int32)
        lo_ids = jnp.arange(128, dtype=jnp.int32)

        def step(acc, rb):
            r, b = rb
            hi = ((r >> 7)[:, None] == hi_ids[None]) & b[:, None]
            lo = (r & 127)[:, None] == lo_ids[None]
            got = jnp.dot(hi.astype(jnp.bfloat16).T, lo.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
            return acc + got.astype(jnp.int32), None

        acc, _ = jax.lax.scan(step, jnp.zeros((H, 128), jnp.int32),
                              (rank, bit))
        return acc.reshape(-1)

    @jax.jit
    def gather_onehot(cols, rank, src):
        bit = bit_test(cols, src) != 0
        return hist(rank.reshape(-1, chunk), bit.reshape(-1, chunk))[:R]

    @jax.jit
    def bycolumn_onehot(bycol, src):
        bits = ((src[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
                ).reshape(S, C) != 0
        ok = bits & (bycol >= 0)
        return hist(jnp.maximum(bycol, 0).reshape(-1, chunk),
                    ok.reshape(-1, chunk))[:R]

    from pilosa_tpu.ops import bitvector as bv

    @jax.jit
    def slot_major(pairs, src):
        """PR 29's pairs_count_local, as it was."""
        by_column = pairs.ndim == 2
        k = pairs.shape[-1]
        step = min(1 << 15, k)
        per_shard = k // step
        hi_ids = jnp.arange(H, dtype=jnp.int32)
        lo_ids = jnp.arange(128, dtype=jnp.int32)
        bit_ids = jnp.arange(32, dtype=jnp.uint32)

        def one(acc, i):
            s, at = i // per_shard, (i % per_shard) * step
            if by_column:
                r = jax.lax.dynamic_slice(pairs, (s, at), (1, step))[0]
                words = jax.lax.dynamic_slice(src, (s, at // 32),
                                              (1, step // 32))[0]
                b = ((((words[:, None] >> bit_ids[None]) & 1) != 0)
                     .reshape(step) & (r >= 0))
            else:
                blk = jax.lax.dynamic_slice(pairs, (0, s, at), (2, 1, step))
                plane = jax.lax.dynamic_index_in_dim(src, s, axis=0,
                                                     keepdims=False)
                r = blk[1, 0]
                b = bv._dense_bit_test(blk[0, 0], plane)
            hi = ((r >> 7)[:, None] == hi_ids[None]) & b[:, None]
            lo = (r & 127)[:, None] == lo_ids[None]
            got = jnp.dot(hi.astype(jnp.bfloat16).T, lo.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
            return acc + got.astype(jnp.int32), None

        acc, _ = jax.lax.scan(one, jnp.zeros((H, 128), jnp.int32),
                              jnp.arange(S * per_shard, dtype=jnp.int32))
        return acc.reshape(-1)[:R]

    def lane_major_xla(step, n_slots):
        """ops/bitvector.py's body traced under another PAIRS_STEP or
        count vector (the global is read when the program is traced, at
        the first call)."""
        fn = jax.jit(lambda pairs, src: bv.pairs_count_local(
            pairs, src, n_slots)[:R])

        def call(pairs, src):
            was, bv.PAIRS_STEP = bv.PAIRS_STEP, step
            try:
                return fn(pairs, src)
            finally:
                bv.PAIRS_STEP = was

        return call

    n_slots = bv.pairs_count_slots(R)

    def shipped(pairs, src):
        return bv.pairs_count(pairs, src, n_slots)[:R]

    cols, rank, bycol, ends, src = make(29, S, K, R, 0.73, 0.05)
    want = oracle(cols, rank, src, R)
    kept = [(cols[s][cols[s] < C], rank[s][cols[s] < C]) for s in range(S)]
    dev = {k: jax.device_put(v) for k, v in dict(
        cols=cols, rank=rank, bycol=bycol, ends=ends, src=src,
        pairs=np.stack([cols, rank]),
        bitmajor=bv.pairs_by_column(kept)).items()}
    out = {"device": jax.devices()[0].device_kind, "shards": S, "slots": K,
           "rows": R, "bits": int((cols < C).sum())}
    runs = {
        "gather": (gather, ("cols", "src"), None),
        "gather+scatter": (gather_scatter, ("cols", "rank", "src"), want),
        "gather+cumsum": (gather_cumsum, ("cols", "ends", "src"), want),
        "gather+onehot": (gather_onehot, ("cols", "rank", "src"), want),
        "bycolumn+onehot": (bycolumn_onehot, ("bycol", "src"), want),
        "slot-major one-hot (PR 29), by pairs": (
            slot_major, ("pairs", "src"), want),
        "slot-major one-hot (PR 29), by column": (
            slot_major, ("bycol", "src"), want),
    }
    for step in args.steps:
        for layout in ("pairs", "column"):
            runs[f"lane-major one-hot, XLA, by {layout}, step {step}"] = (
                lane_major_xla(step, n_slots),
                ("pairs" if layout == "pairs" else "bitmajor", "src"), want)
    if args.explore:
        # H a multiple of 16 in place of a power of two
        h16 = -(-R // 2048) * 16
        runs[f"lane-major one-hot, XLA, by column, H = {h16}"] = (
            lane_major_xla(bv.PAIRS_STEP, h16 * 128), ("bitmajor", "src"),
            want)
    runs["shipped by pairs"] = (shipped, ("pairs", "src"), want)
    runs["shipped by column"] = (shipped, ("bitmajor", "src"), want)
    for name, (fn, keys, check) in runs.items():
        if args.only not in name:
            continue
        a = [dev[k] for k in keys]
        out[name] = timed(fn, a, check, args.reps)
        if fn is shipped:
            mem = bv.pairs_count.lower(*a, n_slots).compile(
                ).memory_analysis()
            out[name]["temp_bytes"] = mem.temp_size_in_bytes
        print(name, json.dumps(out[name]), flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
