#!/usr/bin/env python3
"""Timings that chose the list-to-plane bridge (PERF.md section 6): a
sparse row leaf int32[S, K] (sorted shard-local columns, sentinel-padded)
meeting a plane uint32[S, 32768]. Variants timed at each (S, K):

  scatter           the bridge as it was: a per-entry scatter-add of every
                    column's bit into a zeroed plane (vmap over shards)
  gather+sort ∩ / − sparse ∩ / − dense as they were: every entry's word
                    gathered from the plane and its bit tested, then the
                    list re-sorted with the misses masked to the sentinel
  product B=256 / B=128
                    ops/bitvector.py _bridge under either factoring of a
                    shard's 32,768 words, 128 rows of 256 or 256 of 128:
                    onehot(row)ᵀ @ half-values on the matrix unit
  fused ∩ / −       the product and one AND or AND-NOT in one program,
                    under the factoring BRIDGE_WORDS ships
  shipped           ops/bitvector.py sparse_to_dense and
                    sparse_intersect_dense as the executor calls them

A time is the host's wall over `--launches` launches back to back, ended
by blocking on every result, over the launches (the device's time a launch
once the queue is full), the median of `--reps`; no result is fetched but
the one that checks every variant against the scatter. Each variant also
reports its compiled program's temporary bytes (`memory_analysis()`): a
one-hot operand that was not fused into the product shows there.

    python3 benches/sparse_kernels.py --out sparse_kernels.json  # a TPU
    python3 benches/sparse_kernels.py --shapes 2x512,1x64 --reps 2  # CPU
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
SHAPES = "64x512,64x4096,64x8192,64x16384,16x4096,1x64"


def make(seed, S, K):
    """A leaf of S shards with 3/4 of its K slots holding columns (a
    segmentation row of 2,400-4,096 bits in 4,096 slots), and a plane."""
    rng = np.random.default_rng(seed)
    sp = np.full((S, K), C, np.int32)
    n = max(1, K * 3 // 4)
    for s in range(S):
        sp[s, :n] = np.sort(rng.choice(C, n, replace=False))
    dense = rng.integers(0, 1 << 32, size=(S, W), dtype=np.uint32)
    return sp, dense


def variants():
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.ops import bitvector as bv

    def scatter(sp):
        def one(idx):
            bit = jnp.uint32(1) << (idx & 31).astype(jnp.uint32)
            return jnp.zeros((W,), jnp.uint32).at[idx >> 5].add(
                bit, mode="drop")
        return jax.vmap(one)(sp)

    def bit_test(sp, dense):
        safe = jnp.minimum(sp, C - 1)
        w = jnp.take_along_axis(dense, safe >> 5, axis=-1)
        bit = (w >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
        return (bit != 0) & (sp < C)

    def gather_and(sp, dense):
        return jnp.sort(jnp.where(bit_test(sp, dense), sp, C), axis=-1)

    def gather_andnot(sp, dense):
        keep = ~bit_test(sp, dense) & (sp < C)
        return jnp.sort(jnp.where(keep, sp, C), axis=-1)

    def product(b_words):
        def fn(sp):
            was, bv.BRIDGE_WORDS = bv.BRIDGE_WORDS, b_words
            try:          # read while the program is traced
                return bv._bridge(sp, W)
            finally:
                bv.BRIDGE_WORDS = was
        return fn

    return {
        "scatter": (scatter, False),
        "gather+sort ∩": (gather_and, True),
        "gather+sort −": (gather_andnot, True),
        "product B=256": (product(256), False),
        "product B=128": (product(128), False),
        "fused ∩": (lambda sp, d: bv._bridge(sp, W) & d, True),
        "fused −": (lambda sp, d: bv._bridge(sp, W) & ~d, True),
        "shipped sparse_to_dense": (
            lambda sp: bv.sparse_to_dense(sp, W), False),
        "shipped sparse_intersect_dense": (bv.sparse_intersect_dense, True),
    }


def timed(fn, args, launches, reps):
    """Two warm calls, then the median of `reps` runs of `launches`
    launches back to back, a launch's share."""
    import jax

    jax.block_until_ready([fn(*args) for _ in range(2)])
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(launches)])
        ts.append((time.perf_counter() - t0) * 1e3 / launches)
    return {"ms_median": sorted(ts)[len(ts) // 2], "ms_min": min(ts)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=SHAPES,
                    help="comma-separated SxK: shards x slots a shard")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--launches", type=int, default=20)
    ap.add_argument("--out", default="",
                    help="also write every row as JSON to this file")
    args = ap.parse_args()
    import jax

    from pilosa_tpu.ops import bitvector as bv
    peak = 197e12       # v5e bfloat16 peak, benchmarks/lib/peaks.json
    out = {"device": jax.devices()[0].device_kind,
           "bridge_words": bv.BRIDGE_WORDS, "rows": []}
    fns = variants()
    for shape in args.shapes.split(","):
        S, K = (int(x) for x in shape.split("x"))
        sp_h, dense_h = make(S * 131 + K, S, K)
        sp, dense = jax.device_put(sp_h), jax.device_put(dense_h)
        plane = None
        row = {"S": S, "K": K, "flop": 4 * S * K * W}
        for name, (fn, with_dense) in fns.items():
            jf = fn if name.startswith("shipped") else jax.jit(fn)
            a = (sp, dense) if with_dense else (sp,)
            got = np.asarray(jf(*a))
            if name == "scatter":
                plane = got
            elif got.shape == plane.shape:
                want = (plane & dense_h if "∩" in name or "intersect" in name
                        else plane & ~dense_h if "−" in name else plane)
                row.setdefault("agrees", True)
                row["agrees"] &= bool((got == want).all())
            r = timed(jf, a, args.launches, args.reps)
            low = jf.lower(*a) if not name.startswith("shipped") else (
                bv.sparse_to_dense.lower(sp, W) if "to_dense" in name
                else bv.sparse_intersect_dense.lower(sp, dense))
            r["temp_bytes"] = low.compile().memory_analysis(
                ).temp_size_in_bytes
            if "product" in name or "fused" in name or "shipped" in name:
                r["pct_of_bf16_peak"] = (100.0 * row["flop"] / peak
                                         / (r["ms_median"] * 1e-3))
            row[name] = r
            del got
        print(json.dumps(row, ensure_ascii=False), flush=True)
        out["rows"].append(row)
    names = list(fns)
    print("| S x K | " + " | ".join(names) + " | agrees |")
    print("|---" * (len(names) + 2) + "|")
    for r in out["rows"]:
        print(f"| {r['S']} x {r['K']} | " + " | ".join(
            f"{r[n]['ms_median']:.3f}" for n in names)
            + f" | {r.get('agrees')} |")
    print("temp bytes: " + json.dumps(
        {f"{r['S']}x{r['K']}": {n: r[n]["temp_bytes"] for n in names}
         for r in out["rows"]}, ensure_ascii=False))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(out, fh, ensure_ascii=False)


if __name__ == "__main__":
    main()
