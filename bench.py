"""Benchmarks: the REAL engine on TPU vs CPU-numpy baselines.

Seven measurements (BASELINE.md configs), all through production code paths:

1. kernel      — raw fused and+popcount query stream on a 1.07B-column
                 resident slab, K queries batched per dispatch (config 2's
                 kernel ceiling; regression metric).
2. executor    — Executor.execute("Count(Intersect(Row,Row))") end to end
                 under concurrent clients: parse -> compile -> HBM residency
                 (warm) -> continuous-batched device dispatch -> host merge
                 (executor.go:1208,1521 analog).
3. topn        — TopN(n=1000) over a ranked-cache field through the
                 executor's two-phase threshold walk (config 3;
                 fragment.go:1018-1150).
4. groupby     — GroupBy cross product via device-batched fused counts
                 (executor.go:897-1090).
5. bsi         — Sum(Range(v > x)) through the device-composed BSI plane
                 kernels (config 4; fragment.go:718-985, executor.go:363).
6. http        — end-to-end HTTP loopback QPS against a real Server under
                 concurrent clients (config 1: wire + parse + execute).
7. distributed — 2-node cluster mapReduce fan-out Count over 16 shards
                 (config 5; executor.go:2183 analog).

The CPU baseline for each is the same logical work in vectorized numpy —
an upper bound on the reference's single-node Go throughput for dense data
(no Go toolchain exists in this image; BASELINE.md publishes no absolute
numbers).

The measurement runs in a worker SUBPROCESS under a hard deadline (one
process per chip: the parent never initialises JAX). It measures a live
TPU or it fails: a backend that is not a TPU, a worker that dies or a
stage that records `*_error` exits non-zero and prints no result line —
there is no fallback to an older record. PILOSA_BENCH_PLATFORM=cpu is the
explicit tiny-size rehearsal; its result line says so and no artifact is
written.

Methodology (see .claude/skills/verify/SKILL.md):
- dispatch is asynchronous: a value fetch (int()/np.asarray) ends every
  timed region; kernel timings chain dispatches through a carry and fetch
  once at the end
- the kernel stream scans K *distinct* (i, j) row pairs per dispatch so XLA
  cannot hoist or CSE the per-query work
- executor/topn/bsi/http timings are wall-clock per query with warm HBM
  residency (steady-state serving), forcing results to Python values

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}
where detail.metrics carries every measurement.

Per-stage checkpointing: the worker appends each completed stage's JSON to
PILOSA_BENCH_CKPT (default benches/bench_ckpt.jsonl) the moment it finishes
— a progress log for a run killed at its deadline, never a source of
results. Stages can be filtered for reruns via
PILOSA_BENCH_STAGES=kernel,executor,...
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from pilosa_tpu.constants import SHARD_WIDTH, WORDS_PER_SHARD

# kernel-stream slab (config 2): 1024 shards x 2^20 = 1.07B columns/row
N_SHARDS = int(os.environ.get("PILOSA_BENCH_SHARDS", "1024"))
N_ROWS = 16          # resident rows: 16 x 134MB = 2.1GB HBM
# queries per dispatch: fixed dispatch overhead amortizes across the batch
K_BATCH = int(os.environ.get("PILOSA_BENCH_K", "512"))
N_DISPATCH = 4       # chained dispatches measured

# per-kernel representation A/B microbench (`kernels` stage)
KERNELS_SHARDS = int(os.environ.get("PILOSA_BENCH_KERNELS_SHARDS", "32"))
KERNELS_LOOPS = int(os.environ.get("PILOSA_BENCH_KERNELS_LOOPS", "20"))

# engine-path scales (kept moderate: fragment data is built on HOST and the
# leaves upload into HBM once at warmup)
EXEC_SHARDS = int(os.environ.get("PILOSA_BENCH_EXEC_SHARDS", "128"))
EXEC_ROWS = 8
EXEC_DENSITY = 0.01
TOPN_SHARDS = 8
TOPN_ROWS = 100_000
TOPN_N = 1000
BSI_SHARDS = 16
HTTP_QUERIES = 200
BSI_THREADS = 16
ENGINE_QUERIES = 100
# serving throughput is measured under concurrent clients (the reference's
# QPS numbers are concurrent server loads; a single-stream loop measures
# per-query latency, not serving capacity)
EXEC_THREADS = int(os.environ.get("PILOSA_BENCH_THREADS", "32"))
EXEC_THREADS_PEAK = int(os.environ.get("PILOSA_BENCH_THREADS_PEAK", "256"))
HTTP_THREADS = 16
HTTP_THREADS_PEAK = int(os.environ.get("PILOSA_BENCH_HTTP_THREADS_PEAK", "128"))
BSI_THREADS_PEAK = int(os.environ.get("PILOSA_BENCH_BSI_THREADS_PEAK", "128"))

METRIC = ("executor_intersect_count_qps" if EXEC_SHARDS == 128
          else f"executor_intersect_count_qps_{EXEC_SHARDS}shards")
CKPT_PATH = os.environ.get(
    "PILOSA_BENCH_CKPT",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "benches",
                 "bench_ckpt.jsonl"))
STAGES = [s for s in os.environ.get("PILOSA_BENCH_STAGES", "").split(",") if s]
# wall-clock budget for the whole run: the probe loop retries a backend
# that fails to initialise inside it; if none comes up the run FAILS
DEADLINE_S = float(os.environ.get("PILOSA_BENCH_DEADLINE_S", "1800"))
PROBE_TIMEOUT_S = 120.0
# The explicit CPU rehearsal ("cpu"): the only way to run off a TPU. Its
# result line carries "rehearsal" and no artifact is written.
PLATFORM = os.environ.get("PILOSA_BENCH_PLATFORM", "")


def _apply_platform() -> None:
    if PLATFORM:
        import jax

        jax.config.update("jax_platforms", PLATFORM)


def _go_proxy() -> dict:
    """Measured reference-proxy numbers (benches/refproxy.json — scalar
    C++ mirror of the Go reference's kernels; see BASELINE.md). {} if the
    file is absent."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benches", "refproxy.json")
    try:
        with open(path) as f:
            return json.load(f).get("results", {})
    except (OSError, ValueError):
        return {}


def _attach_go_ref(m: dict, bench_name: str, tpu_s: float) -> None:
    """Add vs_go_reference = proxy_seconds / tpu_seconds to a stage dict."""
    entry = _go_proxy().get(bench_name)
    if entry and tpu_s > 0:
        go_s = entry["ns_per_op"] / 1e9
        m["go_proxy_ms_per_query"] = round(go_s * 1e3, 4)
        m["vs_go_reference"] = round(go_s / tpu_s, 2)


def _concurrent_seconds_per_query(n_threads: int, per_thread: int,
                                  run_query, latencies: list = None) -> float:
    """Aggregate serving rate under concurrent clients: n_threads each
    issue per_thread queries via run_query(thread_id, i); returns wall
    seconds per query. When `latencies` is given, per-query wall times
    (seconds) are appended to it. First client error re-raises."""
    import threading

    errors = []
    lat_lock = threading.Lock()

    def client(tid):
        try:
            if latencies is None:
                for i in range(per_thread):
                    run_query(tid, i)
                return
            mine = []
            for i in range(per_thread):
                q0 = time.perf_counter()
                run_query(tid, i)
                mine.append(time.perf_counter() - q0)
            with lat_lock:
                latencies.extend(mine)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return wall / (n_threads * per_thread)


def _lat_ms(latencies: list) -> dict:
    """{p50, p99} in ms from collected per-query latencies."""
    if not latencies:
        return {}
    s = sorted(latencies)
    return {"p50_ms": round(s[len(s) // 2] * 1e3, 2),
            "p99_ms": round(s[min(len(s) - 1, int(len(s) * 0.99))] * 1e3, 2)}


def _measure_base_peak(base_threads: int, peak_threads: int,
                       per_thread_base: int, per_thread_peak: int,
                       run_query, on_base_done=None,
                       latencies: list = None) -> tuple:
    """Closed-loop serving at a base concurrency (continuity with earlier
    rounds) and — when peak_threads > base_threads — at a saturating one:
    a closed loop caps at in_flight/latency, so peak serving needs enough
    clients to cover the per-query latency (the reference's Go server is
    benchmarked the same way: throughput at saturating concurrency). Returns (headline_s, headline_threads, base_s, peak_s)
    where peak_s is None when the peak run was skipped; headline = the
    better of the two runs. `on_base_done` fires between the runs
    (stage-local instrumentation snapshots)."""
    base_s = _concurrent_seconds_per_query(base_threads, per_thread_base,
                                           run_query)
    if on_base_done is not None:
        on_base_done()
    if peak_threads <= base_threads:
        return base_s, base_threads, base_s, None
    peak_s = _concurrent_seconds_per_query(peak_threads, per_thread_peak,
                                           run_query, latencies=latencies)
    if peak_s < base_s:
        return peak_s, peak_threads, base_s, peak_s
    return base_s, base_threads, base_s, peak_s


def _conc_path(base_threads: int, peak_threads: int, peak_ran: bool) -> str:
    """Provenance fragment naming exactly the concurrencies measured."""
    return (f"closed-loop clients at {base_threads}"
            + (f" and {peak_threads} (headline = better)"
               if peak_ran else ""))


def _init_backend_with_retry(deadline: float):
    """jax.devices() with bounded retry/backoff on transient init errors.
    A *hang* here is handled by the parent's subprocess timeout, not by us."""
    import jax

    _apply_platform()
    backoff = 10.0
    while True:
        try:
            return jax.devices()
        except RuntimeError as e:
            if time.monotonic() + backoff >= deadline:
                raise
            print(f"backend init failed ({e}); retrying in {backoff:.0f}s",
                  file=sys.stderr)
            time.sleep(backoff)
            backoff = min(backoff * 2, 60.0)


# --------------------------------------------------------------- 1) kernel


def bench_kernel() -> dict:
    import jax
    import jax.numpy as jnp

    from pilosa_tpu.parallel.mesh import count_pair_stream, eval_count_total

    prng = np.random.default_rng(23)
    pairs = [tuple(prng.choice(N_ROWS, size=2, replace=False))
             for _ in range(K_BATCH)]
    ii = jnp.array([p[0] for p in pairs], dtype=jnp.int32)
    jj = jnp.array([p[1] for p in pairs], dtype=jnp.int32)

    # generate the slab ON DEVICE: no GBs of host->device upload in set-up
    rows = jax.random.bits(
        jax.random.key(7), (N_ROWS, N_SHARDS, WORDS_PER_SHARD),
        dtype=jnp.uint32)
    int(rows[0, 0, 0])  # force materialization before timing

    int(count_pair_stream(rows, ii, jj, jnp.uint32(0)))  # compile + warm
    t0 = time.perf_counter()
    carry = jnp.uint32(1)
    for _ in range(N_DISPATCH):
        carry = count_pair_stream(rows, ii, jj, carry)
    int(carry)  # forces the whole chain
    tpu_s = (time.perf_counter() - t0) / (N_DISPATCH * K_BATCH)

    # CPU baseline: same dense AND+popcount in numpy, scaled from a slice
    # (full 2.1GB x 3 passes would eat the deadline)
    small = min(64, N_SHARDS)
    rng = np.random.default_rng(7)
    a = rng.integers(0, 2**32, size=(small, WORDS_PER_SHARD), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(small, WORDS_PER_SHARD), dtype=np.uint32)
    np.bitwise_count(a & b).sum()  # warm
    t0 = time.perf_counter()
    for _ in range(3):
        np.bitwise_count(a & b).sum()
    cpu_s = (time.perf_counter() - t0) / 3 * (N_SHARDS / small)

    # correctness cross-check on a small slice (keeps the device->host
    # fetch small): numpy vs the engine kernel vs the stream kernel
    i0, j0 = pairs[0]
    sm = rows[:, :4, :]
    expect = int(np.bitwise_count(np.asarray(sm[i0]) & np.asarray(sm[j0])).sum())
    got = int(eval_count_total(
        jnp.stack([sm[i0], sm[j0]]), ("and", ("leaf", 0), ("leaf", 1))))
    got_stream = int(count_pair_stream(sm, ii[:1], jj[:1], jnp.uint32(0)))
    assert got == expect, (got, expect)
    assert got_stream == expect, (got_stream, expect)

    cols = N_SHARDS * SHARD_WIDTH
    out = {
        "metric": "kernel_intersect_count_qps_1Bcol",
        "value": round(1.0 / tpu_s, 2),
        "unit": "queries/s/chip",
        "vs_baseline": round(cpu_s / tpu_s, 2),
        "tpu_ms_per_query": round(tpu_s * 1e3, 4),
        "cpu_numpy_ms_per_query": round(cpu_s * 1e3, 4),
        "columns_per_operand": cols,
        "tpu_gcols_per_s": round(cols / tpu_s / 1e9, 2),
        "hbm_gb_per_s": round(2 * cols / 8 / tpu_s / 1e9, 1),
    }
    if N_SHARDS == 1024:  # proxy measured at this exact shape
        _attach_go_ref(out, "kernel_2rows_dense_1024shard", tpu_s)

    # Pallas scalar-prefetch stream: explicitly double-buffered DMA of the
    # data-dependent row blocks (real TPU only — interpret mode would time
    # the emulator). Reported alongside; correctness asserted vs the scan
    # kernel's chain.
    if jax.default_backend() == "tpu":
        try:
            from pilosa_tpu.ops.pallas_kernels import (
                pair_stream_counts as pallas_stream,
            )

            ref = np.asarray(pallas_stream(rows[:, :4, :], ii[:1], jj[:1]))
            assert int(ref[0]) == expect, (int(ref[0]), expect)
            # warm TWICE: the first execution of a fresh pallas binary runs
            # ~4x slow (observed r3); steady state starts at the second
            int(pallas_stream(rows, ii, jj).sum())
            int(pallas_stream(rows, ii, jj).sum())
            t0 = time.perf_counter()
            acc = jnp.int32(0)
            for _ in range(N_DISPATCH):
                acc = acc + pallas_stream(rows, ii, jj).sum()
            int(acc)
            pl_s = (time.perf_counter() - t0) / (N_DISPATCH * K_BATCH)
            out["pallas_ms_per_query"] = round(pl_s * 1e3, 4)
            out["pallas_hbm_gb_per_s"] = round(2 * cols / 8 / pl_s / 1e9, 1)
        except Exception as e:  # noqa: BLE001 — optional measurement
            out["pallas_error"] = f"{type(e).__name__}: {e}"
    return out


def bench_kernels() -> dict:
    """Representation A/B microbench (run-container PR): the SAME logical
    row timed as a dense plane, a sorted-index sparse array and padded
    [start, last] run intervals, plus the TopN-counts / BSI kernels with
    their Pallas twins off/on. Go-proxy rows are container-level numbers
    (65536 columns/op); device seconds are normalized to per-container
    (or per-shard for the fragment-level bench) before the ratio so
    vs_go_reference stays apples-to-apples."""
    import jax
    import jax.numpy as jnp

    import pilosa_tpu.ops.bitvector as bv
    from pilosa_tpu.ops import bsi as bsiops
    from pilosa_tpu.ops import pallas_kernels
    from pilosa_tpu.ops import topn as topnops

    S = KERNELS_SHARDS
    W = WORDS_PER_SHARD
    containers = S * (SHARD_WIDTH // 65536)
    on_tpu = jax.default_backend() == "tpu"

    # runny twins: 64 runs x 2048 bits per shard; operand b shifted half a
    # run so every overlap is partial (the merge kernel's general case)
    R = 256
    n_runs, run_len, stride = 64, 2048, 8192
    starts = np.arange(n_runs, dtype=np.int64) * stride

    def run_row(shift):
        iv = np.stack([starts + shift, starts + shift + run_len - 1], 1)
        return np.broadcast_to(
            bv.runs_from_intervals(iv, R), (S, 2, R)).copy()

    ra = jnp.asarray(run_row(0))
    rb = jnp.asarray(run_row(run_len // 2))
    da = bv.run_to_dense(ra, W)
    db = bv.run_to_dense(rb, W)

    # sparse twins (their own regime: 2048 set bits per shard)
    K = 4096

    def sparse_row(seed):
        cols = np.sort(np.random.default_rng(seed).choice(
            SHARD_WIDTH, size=2048, replace=False)).astype(np.int32)
        sp = np.full((S, K), bv.SPARSE_SENTINEL, np.int32)
        sp[:, :2048] = cols
        return jnp.asarray(sp)

    sa, sb = sparse_row(1), sparse_row(2)

    # compose count pipelines under ONE jit each so the A/B times one
    # fused program per representation, not a chain of dispatch overheads
    f_dense = jax.jit(lambda a, b: jnp.sum(bv.intersect_count(a, b)))
    f_run = jax.jit(lambda a, b: jnp.sum(bv.run_intersect_count(a, b)))
    f_run_2step = jax.jit(
        lambda a, b: jnp.sum(bv.run_count(bv.run_intersect(a, b))))
    f_run_dense = jax.jit(
        lambda r, d: jnp.sum(bv.run_dense_count(r, d, W)), static_argnums=())
    f_sparse = jax.jit(
        lambda a, b: jnp.sum(bv.sparse_count(bv.sparse_intersect(a, b))))
    f_sparse_dense = jax.jit(
        lambda s, d: jnp.sum(bv.sparse_dense_count(s, d)))
    f_sparse_run = jax.jit(
        lambda s, r: jnp.sum(bv.sparse_count(bv.sparse_intersect_run(s, r))))

    # cross-representation parity before timing anything
    expect = int(f_dense(da, db))
    assert int(f_run(ra, rb)) == expect, (int(f_run(ra, rb)), expect)
    assert int(f_run_2step(ra, rb)) == expect
    assert int(f_run_dense(ra, db)) == expect
    sp_expect = int(f_sparse(sa, sb))
    assert int(f_sparse_dense(sa, db)) == int(f_sparse_run(sa, rb))
    assert sp_expect >= 0

    def us(fn, *a):
        jax.block_until_ready(fn(*a))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(KERNELS_LOOPS):
            r = fn(*a)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / KERNELS_LOOPS * 1e6

    kernels = []

    def row(name, t_us, proxy=None, per_shard=False, note=""):
        e = {"kernel": name, "us_per_op": round(t_us, 1)}
        if note:
            e["note"] = note
        if proxy:
            _attach_go_ref(e, proxy,
                           t_us / 1e6 / (S if per_shard else containers))
            e["go_ref_normalization"] = ("per-shard" if per_shard
                                         else "per-container")
        kernels.append(e)
        return e

    t_dense = us(f_dense, da, db)
    t_run = us(f_run, ra, rb)
    row("count_dense_dense", t_dense, "Fragment_IntersectionCount",
        per_shard=True)
    row("count_run_run", t_run,
        note="fused run_intersect_count; no container-level run-by-run "
             "proxy bench published")
    row("count_run_run_2step", us(f_run_2step, ra, rb),
        note="run_count(run_intersect(...)) — pays the argsort the fused "
             "count skips")
    row("count_run_dense", us(f_run_dense, ra, db),
        "IntersectionCount_BitmapRun")
    row("count_sparse_sparse", us(f_sparse, sa, sb),
        "IntersectionCount_ArrayArray")
    row("count_sparse_dense", us(f_sparse_dense, sa, db),
        "IntersectionCount_ArrayBitmap")
    row("count_sparse_run", us(f_sparse_run, sa, rb),
        "IntersectionCount_ArrayRun")

    out = {
        "metric": "kernels_run_vs_dense_count_speedup",
        "value": round(t_dense / t_run, 2),
        "unit": "x (dense us / run us, same logical row)",
        "vs_baseline": round(t_dense / t_run, 2),
        "run_capacity_ratio": round(da.nbytes / ra.nbytes, 2),
        "shards": S,
        "run_slots": R,
        "runs_per_shard": n_runs,
    }

    # TopN fused-counts kernel, XLA vs Pallas. Parity always (interpret
    # mode); timing only on a real chip — a CPU emulation number would
    # masquerade as a kernel measurement.
    TR, TS = 64, 4
    flat = jax.random.bits(jax.random.key(5), (TR, TS * W), dtype=jnp.uint32)
    src = jax.random.bits(jax.random.key(6), (TS * W,), dtype=jnp.uint32)
    small, ssrc = flat[:8, :2048], src[:2048]
    assert np.array_equal(
        np.asarray(topnops.tanimoto_counts_packed(small, ssrc)),
        np.asarray(pallas_kernels.topn_counts_packed(small, ssrc)))
    t_topn = us(topnops.tanimoto_counts_packed, flat, src)
    row("topn_counts_packed[xla]", t_topn)
    if on_tpu:
        t_topn_pl = us(pallas_kernels.topn_counts_packed, flat, src)
        row("topn_counts_packed[pallas]", t_topn_pl)
        out["topn_pallas_speedup"] = round(t_topn / t_topn_pl, 2)

    # BSI compare + sum sweeps, XLA vs Pallas
    depth = 16
    planes = jax.random.bits(jax.random.key(8), (depth, S, W),
                             dtype=jnp.uint32)
    exists = jnp.asarray(np.full((S, W), 0xFFFFFFFF, dtype=np.uint32))
    pred = jnp.asarray(bsiops.value_to_bits(23456, depth))
    sm_p, sm_e = planes[:, :8, :512], exists[:8, :512]
    assert np.array_equal(
        np.asarray(bsiops.compare(sm_p, sm_e, pred, "lt")),
        np.asarray(pallas_kernels.bsi_compare(sm_p, sm_e, pred, "lt")))
    assert np.array_equal(
        np.asarray(bsiops.sum_counts(sm_p, sm_e)),
        np.asarray(pallas_kernels.bsi_sum_counts(sm_p, sm_e)))
    t_cmp = us(lambda: bsiops.compare(planes, exists, pred, "lt"))
    row("bsi_compare_lt[xla]", t_cmp)
    t_sum = us(bsiops.sum_counts, planes, exists)
    row("bsi_sum_counts[xla]", t_sum)
    if on_tpu:
        t_cmp_pl = us(
            lambda: pallas_kernels.bsi_compare(planes, exists, pred, "lt"))
        row("bsi_compare_lt[pallas]", t_cmp_pl)
        out["bsi_compare_pallas_speedup"] = round(t_cmp / t_cmp_pl, 2)
        t_sum_pl = us(pallas_kernels.bsi_sum_counts, planes, exists)
        row("bsi_sum_counts[pallas]", t_sum_pl)
        out["bsi_sum_pallas_speedup"] = round(t_sum / t_sum_pl, 2)

    out["pallas"] = ("timed" if on_tpu else
                     "parity-only: interpret mode off-TPU — timing the "
                     "emulator is not a kernel number")
    out["kernels"] = kernels
    return out


# ------------------------------------------------------- engine test data


def build_exec_index(holder):
    """Index 'b' / field 'f': EXEC_ROWS rows x EXEC_SHARDS shards at
    EXEC_DENSITY — imported through the real roaring bulk path."""
    from pilosa_tpu.storage.roaring import Bitmap

    rng = np.random.default_rng(3)
    idx = holder.create_index("b", track_existence=False)
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    row_bits = {}
    n_per_shard = int(SHARD_WIDTH * EXEC_DENSITY)
    for shard in range(EXEC_SHARDS):
        positions = []
        for row in range(EXEC_ROWS):
            cols = rng.choice(SHARD_WIDTH, size=n_per_shard,
                              replace=False).astype(np.uint64)
            row_bits[(row, shard)] = cols
            positions.append(np.uint64(row) * np.uint64(SHARD_WIDTH) + cols)
        frag = view.create_fragment_if_not_exists(shard)
        frag.import_roaring(Bitmap(np.concatenate(positions)).to_bytes())
        f.add_available_shard(shard)
    return row_bits


def bench_executor(ex, row_bits) -> dict:
    qs = [f"Count(Intersect(Row(f={i % EXEC_ROWS}), Row(f={(i * 3 + 1) % EXEC_ROWS})))"
          for i in range(ENGINE_QUERIES)]
    # warmup: residency fill (host->HBM, one-time) + XLA compile; correctness asserted against the generator's sets
    (got,) = ex.execute("b", "Count(Intersect(Row(f=0), Row(f=1)))")
    expect = sum(
        np.intersect1d(row_bits[(0, s)], row_bits[(1, s)]).size
        for s in range(EXEC_SHARDS))
    assert got == expect, (got, expect)
    for q in qs[:4]:
        ex.execute("b", q)

    # single-stream latency (each query = dispatch + scalar fetch;
    # reported as p50 in detail)
    t0 = time.perf_counter()
    for q in qs[:20]:
        ex.execute("b", q)
    single_s = (time.perf_counter() - t0) / 20

    # concurrent throughput: closed-loop client threads, the serving QPS
    # analog of the reference's concurrent query benchmarks (dispatches
    # and fetches from different queries overlap on the link); see
    # _measure_base_peak for the base-vs-saturating protocol
    peak_lat: list = []
    tpu_s, headline_threads, tpu_s_base, tpu_s_peak = _measure_base_peak(
        EXEC_THREADS, EXEC_THREADS_PEAK,
        max(8, ENGINE_QUERIES // 4), max(8, ENGINE_QUERIES // 8),
        lambda tid, i: ex.execute("b", qs[(tid * 7 + i) % len(qs)]),
        latencies=peak_lat)

    # CPU baseline: the same dense AND+popcount work in numpy (per query:
    # two [S, W] operands), scaled from a slice. Measured BOTH single-core
    # and under the HEADLINE's client concurrency (numpy ufuncs release
    # the GIL, so this is the all-cores Go-server analog); the stronger
    # one is the baseline.
    small = min(16, EXEC_SHARDS)
    rng = np.random.default_rng(5)
    a = rng.integers(0, 2**32, size=(small, WORDS_PER_SHARD), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(small, WORDS_PER_SHARD), dtype=np.uint32)
    np.bitwise_count(a & b).sum()
    t0 = time.perf_counter()
    for _ in range(5):
        np.bitwise_count(a & b).sum()
    cpu_s = (time.perf_counter() - t0) / 5 * (EXEC_SHARDS / small)
    cpu_conc_s = _concurrent_seconds_per_query(
        headline_threads, 3,
        lambda tid, i: np.bitwise_count(a & b).sum(),
    ) * (EXEC_SHARDS / small)
    cpu_best_s = min(cpu_s, cpu_conc_s)

    out = {
        "metric": METRIC,
        "value": round(1.0 / tpu_s, 2),
        "unit": "queries/s/chip",
        "vs_baseline": round(cpu_best_s / tpu_s, 2),
        "tpu_ms_per_query": round(tpu_s * 1e3, 4),
        "single_stream_ms_per_query": round(single_s * 1e3, 4),
        "concurrency": headline_threads,
        "qps_at_base_concurrency": {"clients": EXEC_THREADS,
                                    "qps": round(1.0 / tpu_s_base, 2)},
        "cpu_numpy_ms_per_query": round(cpu_s * 1e3, 4),
        "cpu_numpy_concurrent_ms_per_query": round(cpu_conc_s * 1e3, 4),
        "columns_per_operand": EXEC_SHARDS * SHARD_WIDTH,
        "path": "Executor.execute (parse+compile+residency+device+merge), "
                + _conc_path(EXEC_THREADS, EXEC_THREADS_PEAK,
                             tpu_s_peak is not None)
                + "; baseline is the BEST of single-core and "
                "headline-concurrency numpy on the same dense work",
    }
    if tpu_s_peak is not None:
        out["qps_at_peak_concurrency"] = {
            "clients": EXEC_THREADS_PEAK,
            "qps": round(1.0 / tpu_s_peak, 2),
            **_lat_ms(peak_lat)}  # per-query latency under saturating load
    if EXEC_SHARDS == 128:  # proxy measured at this exact shape (1% rows)
        _attach_go_ref(out, "exec_128shard_1pct", tpu_s)
    return out


def build_topn_index(holder):
    """Index 'b' / field 't': TOPN_ROWS rows with a heavy-tailed size
    distribution over TOPN_SHARDS shards (the ranked-cache showcase,
    docs/examples.md:320-331)."""
    idx = holder.index("b") or holder.create_index("b")
    t = idx.create_field("t")
    rng = np.random.default_rng(11)
    rows, cols = [], []
    # zipf-ish: row r gets ~ TOPN_ROWS/(r+1) bits, capped; tail rows get 1
    for r in range(TOPN_ROWS):
        n = max(1, min(2000, TOPN_ROWS // (10 * (r + 1))))
        c = rng.integers(0, TOPN_SHARDS * SHARD_WIDTH, size=n, dtype=np.uint64)
        rows.append(np.full(n, r, dtype=np.uint64))
        cols.append(c)
    t.import_bits(np.concatenate(rows), np.concatenate(cols))
    return t


def bench_topn(ex) -> dict:
    (pairs,) = ex.execute("b", f"TopN(t, n={TOPN_N})")  # warm + compile
    assert len(pairs) == TOPN_N, len(pairs)
    lat = []
    for _ in range(10):
        t0 = time.perf_counter()
        ex.execute("b", f"TopN(t, n={TOPN_N})")
        lat.append(time.perf_counter() - t0)
    p50 = sorted(lat)[len(lat) // 2]

    # CPU baseline: the same two-phase merge in numpy over the per-shard
    # candidate pair lists (what the reference's rank-cache walk merges)
    idx = ex.holder.index("b")
    t = idx.field("t")
    shard_pairs = []
    for s in range(TOPN_SHARDS):
        cache = t.view("standard").rank_caches.get(s)
        if cache is not None and len(cache):
            arr = np.array(cache.top(), dtype=np.int64)
            if arr.size:
                shard_pairs.append(arr)
    t0 = time.perf_counter()
    for _ in range(3):
        allp = np.concatenate(shard_pairs)
        ids, inv = np.unique(allp[:, 0], return_inverse=True)
        counts = np.zeros(ids.size, dtype=np.int64)
        np.add.at(counts, inv, allp[:, 1])
        order = np.argsort(-counts, kind="stable")[:TOPN_N]
        _ = ids[order]
    cpu_s = (time.perf_counter() - t0) / 3

    return {
        "metric": "topn1000_p50_ms",
        "value": round(p50 * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_s / p50, 2),
        "rows": TOPN_ROWS,
        "recount_rows_total": ex.topn_recount_rows,
        "path": "Executor TopN two-phase threshold walk",
    }


# per axis; 100x100 = 10k combinations on TPU. CPU smoke runs scale this
# down — the dense cross product is ~5 GB of fused and+popcount per query,
# which the CPU backend emulates at ~0.3 GB/s.
GROUPBY_ROWS = int(os.environ.get("PILOSA_BENCH_GROUPBY_ROWS", "100"))
GROUPBY_SHARDS = 4
# bits per row: matches the refproxy groupby_100x100_4shard workload shape.
# 2000 bits over 4M columns is still sparse (5e-4); it sizes the stage so
# the chip-side cross-count advantage is visible over the link RTT instead
# of both sides racing to a sub-RTT no-op (r5: 400-bit rows made the whole
# contest an RTT measurement, vs_baseline 0.86)
GROUPBY_BITS = int(os.environ.get("PILOSA_BENCH_GROUPBY_BITS", "2000"))
GROUPBY_WARM_ITERS = 5


def build_groupby_index(holder):
    """Index 'gb', fields 'g1'/'g2': GROUPBY_ROWS rows each with random
    bits over GROUPBY_SHARDS shards — the 100x100 cross product the GroupBy
    redesign is sized against. A separate index: GroupBy fans out over the
    INDEX's shard union, and sharing index 'b' would drag the 128
    executor-bench shards (32x the device work and upload) into every
    GroupBy query."""
    idx = holder.create_index("gb", track_existence=False)
    rng = np.random.default_rng(19)
    n_cols = GROUPBY_SHARDS * SHARD_WIDTH
    sets = {}
    for fname in ("g1", "g2"):
        fld = idx.create_field(fname)
        rows, cols = [], []
        for r in range(GROUPBY_ROWS):
            c = rng.integers(0, n_cols, size=GROUPBY_BITS, dtype=np.uint64)
            sets[(fname, r)] = np.unique(c)
            rows.append(np.full(c.size, r, dtype=np.uint64))
            cols.append(c)
        fld.import_bits(np.concatenate(rows), np.concatenate(cols))
    return sets


def bench_groupby(ex, sets) -> dict:
    """GroupBy 100x100 through the single-program cross-count path: every
    level is one pipelined batch of fused counts[P, R] dispatches with
    on-device zero-pruning and ONE host sync (executor.py
    _execute_group_by). Cold = first query (slab build + upload); warm =
    residency-cached axis slabs, the steady serving state. The headline value is the WARM p50 — cold rides alongside."""
    q = "GroupBy(Rows(field=g1), Rows(field=g2))"
    syncs0 = ex.groupby_host_syncs
    t0 = time.perf_counter()
    (groups,) = ex.execute("gb", q)
    cold_s = time.perf_counter() - t0
    # spot-check a handful of combos against the generator's sets
    got = {(d["group"][0]["rowID"], d["group"][1]["rowID"]): d["count"]
           for d in groups}
    for a in (0, GROUPBY_ROWS // 2, GROUPBY_ROWS - 1):
        for b in (GROUPBY_ROWS // 3, GROUPBY_ROWS - 1):
            expect = np.intersect1d(sets[("g1", a)], sets[("g2", b)],
                                    assume_unique=True).size
            assert got.get((a, b), 0) == expect, (a, b)
    lat = []
    for _ in range(GROUPBY_WARM_ITERS):
        t0 = time.perf_counter()
        ex.execute("gb", q)
        lat.append(time.perf_counter() - t0)
    p50 = sorted(lat)[len(lat) // 2]
    # a fraction (not floor division): overflow-induced extra syncs must
    # surface here, not round away — it's the signal operations.md tells
    # operators to watch
    syncs_per_query = round((ex.groupby_host_syncs - syncs0)
                            / (GROUPBY_WARM_ITERS + 1), 2)

    # CPU baseline: the same cross product as vectorized numpy set
    # intersections over the sorted column arrays
    t0 = time.perf_counter()
    n = 0
    for a in range(GROUPBY_ROWS):
        sa = sets[("g1", a)]
        for b in range(GROUPBY_ROWS):
            if np.intersect1d(sa, sets[("g2", b)],
                              assume_unique=True).size:
                n += 1
    cpu_s = time.perf_counter() - t0
    assert n == len(got)

    out = {
        "metric": f"groupby_{GROUPBY_ROWS}x{GROUPBY_ROWS}_p50_ms",
        "value": round(p50 * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_s / p50, 2),
        "warm_p50_ms": round(p50 * 1e3, 3),
        "cold_ms": round(cold_s * 1e3, 3),
        "tpu_ms_per_query": round(p50 * 1e3, 3),
        "host_syncs_per_query": syncs_per_query,
        "cpu_numpy_ms_per_query": round(cpu_s * 1e3, 3),
        "combinations": GROUPBY_ROWS * GROUPBY_ROWS,
        "bits_per_row": GROUPBY_BITS,
        "path": "Executor GroupBy single-program cross-count levels "
                "(pipelined dispatches, on-device pruning, one host sync "
                "per level); headline = warm p50 over residency-cached "
                "axis slabs, cold first query alongside",
    }
    if GROUPBY_ROWS == 100 and GROUPBY_SHARDS == 4 and GROUPBY_BITS == 2000:
        _attach_go_ref(out, "groupby_100x100_4shard", p50)
    return out


def build_bsi_index(holder):
    """Index 'b' / field 'v': BSI int values on every column of
    BSI_SHARDS shards."""
    from pilosa_tpu.models import FieldOptions, FieldType

    idx = holder.index("b") or holder.create_index("b")
    v = idx.create_field("v", FieldOptions(type=FieldType.INT,
                                           min=0, max=1023))
    rng = np.random.default_rng(13)
    n = BSI_SHARDS * SHARD_WIDTH
    vals = rng.integers(0, 1024, size=n, dtype=np.int64)
    v.import_values(np.arange(n, dtype=np.uint64), vals)
    return vals


def bench_bsi(ex, vals) -> dict:
    (vc,) = ex.execute("b", "Sum(Range(v > 511), field=v)")  # warm + compile
    mask = vals > 511
    assert vc.val == int(vals[mask].sum()) and vc.count == int(mask.sum()), \
        (vc, int(vals[mask].sum()), int(mask.sum()))
    lat = []
    for i in range(10):
        thr = 256 + 32 * i  # vary the threshold: no caching shortcuts
        t0 = time.perf_counter()
        ex.execute("b", f"Sum(Range(v > {thr}), field=v)")
        lat.append(time.perf_counter() - t0)
    p50 = sorted(lat)[len(lat) // 2]

    # concurrent aggregation throughput: varying thresholds coalesce via
    # the PlaneSumBatcher (each query still pays its own compare sweep);
    # see _measure_base_peak for the base-vs-saturating protocol. Batch
    # counts are snapshotted per run so concurrent_batches describes the
    # HEADLINE run only.
    marks = [ex.sum_batcher.snapshot()["batches"] if ex.sum_batcher else 0]
    snap = lambda: marks.append(  # noqa: E731 — boundary instrumentation
        ex.sum_batcher.snapshot()["batches"] if ex.sum_batcher else 0)
    conc_s, conc_threads, conc_s_base, conc_s_peak = _measure_base_peak(
        BSI_THREADS, BSI_THREADS_PEAK, 6, 6,
        lambda tid, i: ex.execute(
            "b", f"Sum(Range(v > {128 + 8 * ((tid * 6 + i) % 96)}), field=v)"),
        on_base_done=snap)
    snap()
    batches = (marks[2] - marks[1] if conc_threads != BSI_THREADS
               else marks[1] - marks[0])

    t0 = time.perf_counter()
    for i in range(3):
        thr = 256 + 32 * i
        m = vals > thr
        _ = vals[m].sum(), m.sum()
    cpu_s = (time.perf_counter() - t0) / 3

    out = {
        "metric": "bsi_range_sum_p50_ms",
        "value": round(p50 * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(cpu_s / p50, 2),
        "columns": BSI_SHARDS * SHARD_WIDTH,
        "concurrent_qps": round(1.0 / conc_s, 2),
        "concurrent_clients": conc_threads,
        "concurrent_qps_at_base": {"clients": BSI_THREADS,
                                   "qps": round(1.0 / conc_s_base, 2)},
        "concurrent_batches": batches,
        "path": "Executor Sum(Range) BSI plane kernels; concurrent_qps = "
                + _conc_path(BSI_THREADS, BSI_THREADS_PEAK,
                             conc_s_peak is not None)
                + ", varying thresholds, PlaneSumBatcher coalesced",
    }
    if BSI_SHARDS == 16:  # proxy measured at this exact shape
        _attach_go_ref(out, "bsi_sum_range_16shard", conc_s)
        out["go_ref_compared_against"] = "concurrent (serving throughput)"
    return out


def bench_http(tmpdir) -> dict:
    """End-to-end HTTP loopback: a real Server, Count(Intersect) stream.

    Clients hold persistent HTTP/1.1 connections (the server speaks
    keep-alive): a fresh urllib connection per request would measure TCP
    setup, not the serving path — the reference's benchmarking clients
    reuse connections too."""
    import http.client
    import threading
    import urllib.request

    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "http"), port=0).open()
    try:
        u = srv.uri
        hostport = u.split("//", 1)[1]
        _local = threading.local()

        def post(path, body):
            conn = getattr(_local, "conn", None)
            if conn is None:
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=30)
            try:
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()  # stale keep-alive: one reconnect retry
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=30)
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return json.loads(out)

        post("/index/h", b"{}")
        post("/index/h/field/f", b"{}")
        rng = np.random.default_rng(17)
        cols = rng.choice(8 * SHARD_WIDTH, size=200_000, replace=False)
        half = len(cols) // 2
        post("/index/h/field/f/import", json.dumps({
            "rowIDs": [0] * half + [1] * (len(cols) - half),
            "columnIDs": cols.tolist()}).encode())
        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        out = post("/index/h/query", q)  # warm residency + compile
        assert isinstance(out["results"][0], int)
        t0 = time.perf_counter()
        for _ in range(10):
            post("/index/h/query", q)
        single_s = (time.perf_counter() - t0) / 10

        # concurrent clients (the threaded server's actual serving mode);
        # see _measure_base_peak for the base-vs-saturating protocol
        peak_lat: list = []
        per_q, conc, per_q_base, per_q_peak = _measure_base_peak(
            HTTP_THREADS, HTTP_THREADS_PEAK,
            HTTP_QUERIES // HTTP_THREADS,
            max(2, HTTP_QUERIES // HTTP_THREADS_PEAK),
            lambda tid, i: post("/index/h/query", q),
            latencies=peak_lat)
        out = {
            **({"peak_latency": _lat_ms(peak_lat)} if peak_lat else {}),
            "metric": "http_count_qps",
            "value": round(1.0 / per_q, 2),
            "unit": "queries/s",
            "tpu_ms_per_query": round(per_q * 1e3, 4),
            "single_stream_ms_per_query": round(single_s * 1e3, 4),
            "concurrency": conc,
            "qps_at_base_concurrency": {"clients": HTTP_THREADS,
                                        "qps": round(1.0 / per_q_base, 2)},
            "path": "HTTP loopback: wire + parse + execute, "
                    + _conc_path(HTTP_THREADS, HTTP_THREADS_PEAK,
                                 per_q_peak is not None)
                    + "; baseline is the Go-proxy kernel time for the "
                    "same query shape (no numpy HTTP path exists)",
        }
        # no HTTP-path numpy equivalent exists; the honest comparison is
        # the Go proxy's kernel time for the same query shape (its wire
        # overhead would only add to it) — never a hardcoded 0.0
        _attach_go_ref(out, "http_count_8shard", per_q)
        out["vs_baseline"] = out.get("vs_go_reference", 0.0)
        return out
    finally:
        srv.close()


PROFILER_ROUNDS = int(os.environ.get("PILOSA_BENCH_PROFILER_ROUNDS", "5"))
PROFILER_QUERIES = int(os.environ.get("PILOSA_BENCH_PROFILER_QUERIES", "60"))
TELEMETRY_ROUNDS = int(os.environ.get("PILOSA_BENCH_TELEMETRY_ROUNDS", "5"))
TELEMETRY_QUERIES = int(os.environ.get(
    "PILOSA_BENCH_TELEMETRY_QUERIES", "60"))


def bench_profiler(tmpdir) -> dict:
    """Profiler overhead A/B: the distributed query profiler must add
    ~zero overhead when disabled (the nop fast path: one ContextVar.get
    per instrumentation site) and bounded overhead when on. Protocol:
    one server, warm residency, interleaved off/on rounds of keep-alive
    Count queries (the shared host drifts; per-round ratios are the
    honest signal, the median ratio the headline). `profile_mode=off`
    takes the identical code path a pre-profiler binary took minus the
    per-site None-checks, so `median_ms_profile_off` vs the http stage's
    single-stream number (same query shape, same protocol) bounds the
    disabled-path cost; `overhead_on_vs_off_pct` is the full cost of
    recording a profile."""
    import http.client
    import statistics

    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "prof"), port=0).open()
    try:
        host = srv.uri.split("//", 1)[1]
        conn = http.client.HTTPConnection(host, timeout=60)

        def post(path, body):
            conn.request("POST", path, body=body)
            resp = conn.getresponse()
            out = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return json.loads(out)

        post("/index/p", b"{}")
        post("/index/p/field/f", b"{}")
        rng = np.random.default_rng(23)
        cols = rng.choice(4 * SHARD_WIDTH, size=100_000, replace=False)
        half = len(cols) // 2
        post("/index/p/field/f/import", json.dumps({
            "rowIDs": [0] * half + [1] * (len(cols) - half),
            "columnIDs": cols.tolist()}).encode())
        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        for _ in range(5):
            post("/index/p/query", q)  # warm residency + compile

        def median_ms(mode: str) -> float:
            srv.api.profile_mode = mode
            lats = []
            for _ in range(PROFILER_QUERIES):
                t0 = time.perf_counter()
                post("/index/p/query", q)
                lats.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(lats)

        rounds = []
        for _ in range(PROFILER_ROUNDS):
            rnd = {"ms_off": round(median_ms("off"), 4),
                   "ms_on": round(median_ms("on"), 4)}
            rnd["overhead_pct"] = round(
                100.0 * (rnd["ms_on"] / rnd["ms_off"] - 1.0), 2) \
                if rnd["ms_off"] else 0.0
            rounds.append(rnd)
        srv.api.profile_mode = "auto"
        med_off = statistics.median(r["ms_off"] for r in rounds)
        med_on = statistics.median(r["ms_on"] for r in rounds)
        overheads = sorted(r["overhead_pct"] for r in rounds)
        return {
            "metric": "profiler_overhead_pct",
            "value": overheads[len(overheads) // 2],
            "unit": "% (profile on vs off, median latency)",
            "median_ms_profile_off": round(med_off, 4),
            "median_ms_profile_on": round(med_on, 4),
            "rounds": rounds,
            "vs_baseline": 0.0,
            "path": "single-stream keep-alive Count(Intersect) loopback, "
                    "interleaved profile_mode=off/on rounds; off = the nop "
                    "fast path (one ContextVar.get per site), on = full "
                    "QueryProfile recording incl. dispatch attribution",
        }
    finally:
        srv.close()


def bench_telemetry(tmpdir) -> dict:
    """Telemetry sampler overhead A/B (budget: <= 1%): one server,
    interleaved sampler-stopped/running rounds of keep-alive Count
    queries, sampler at a punishing 10 ms interval (50-500x the
    production default) so the measured number is a worst-case bound.
    The sampler tick walks fragments and snapshots residency/batcher/
    pool gauges on a background thread — the A/B answers whether that
    walk steals latency from the serving path."""
    import http.client
    import statistics

    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "telem"), port=0,
                 telemetry_interval=0.01, telemetry_ring=720).open()
    try:
        host = srv.uri.split("//", 1)[1]
        conn = http.client.HTTPConnection(host, timeout=60)

        def post(path, body):
            conn.request("POST", path, body=body)
            resp = conn.getresponse()
            out = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return json.loads(out)

        post("/index/tm", b"{}")
        post("/index/tm/field/f", b"{}")
        rng = np.random.default_rng(29)
        cols = rng.choice(4 * SHARD_WIDTH, size=100_000, replace=False)
        half = len(cols) // 2
        post("/index/tm/field/f/import", json.dumps({
            "rowIDs": [0] * half + [1] * (len(cols) - half),
            "columnIDs": cols.tolist()}).encode())
        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        for _ in range(5):
            post("/index/tm/query", q)  # warm residency + compile

        def median_ms(sampler_on: bool) -> float:
            if sampler_on:
                srv.telemetry.start()
            else:
                srv.telemetry.stop()
            lats = []
            for _ in range(TELEMETRY_QUERIES):
                t0 = time.perf_counter()
                post("/index/tm/query", q)
                lats.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(lats)

        rounds = []
        for _ in range(TELEMETRY_ROUNDS):
            rnd = {"ms_off": round(median_ms(False), 4),
                   "ms_on": round(median_ms(True), 4)}
            rnd["overhead_pct"] = round(
                100.0 * (rnd["ms_on"] / rnd["ms_off"] - 1.0), 2) \
                if rnd["ms_off"] else 0.0
            rounds.append(rnd)
        ring_len = len(srv.telemetry.ring)
        overheads = sorted(r["overhead_pct"] for r in rounds)
        return {
            "metric": "telemetry_overhead_pct",
            "value": overheads[len(overheads) // 2],
            "unit": "% (sampler on vs off, median latency; budget <= 1%)",
            "rounds": rounds,
            "ring_samples": ring_len,
            "sampler_interval_s": 0.01,
            "vs_baseline": 0.0,
            "path": "single-stream keep-alive Count(Intersect) loopback, "
                    "interleaved sampler stopped/running rounds at a 10 ms "
                    "interval (worst case; production default is 5 s)",
        }
    finally:
        srv.close()


ACCOUNTING_CLIENTS = int(os.environ.get(
    "PILOSA_BENCH_ACCOUNTING_CLIENTS", "256"))
ACCOUNTING_ROUNDS = int(os.environ.get(
    "PILOSA_BENCH_ACCOUNTING_ROUNDS", "3"))
ACCOUNTING_QPC = int(os.environ.get("PILOSA_BENCH_ACCOUNTING_QPC", "4"))


def bench_accounting(tmpdir) -> dict:
    """Per-principal accounting overhead A/B (budget: <= 1%, the PR 5
    telemetry methodology): one server, ACCOUNTING_CLIENTS keep-alive
    clients each carrying its own DISTINCT X-API-Key (the worst case for
    the ledger — every request resolves a principal, charges several
    sites, and the key space saturates the tracked-principal bound so the
    spill path also runs), interleaved ledger-disabled/enabled rounds.
    The headline is the median-latency delta of enabling accounting."""
    import http.client
    import statistics
    import threading

    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "acct"), port=0).open()
    try:
        hostport = srv.uri.split("//", 1)[1]
        _local = threading.local()

        def post(path, body, key):
            conn = getattr(_local, "conn", None)
            if conn is None:
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
            headers = {"X-API-Key": key}
            try:
                conn.request("POST", path, body=body, headers=headers)
                resp = conn.getresponse()
                out = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
                conn.request("POST", path, body=body, headers=headers)
                resp = conn.getresponse()
                out = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return out

        post("/index/ac", b"{}", "setup")
        post("/index/ac/field/f", b"{}", "setup")
        rng = np.random.default_rng(31)
        cols = rng.choice(4 * SHARD_WIDTH, size=100_000, replace=False)
        half = len(cols) // 2
        post("/index/ac/field/f/import", json.dumps({
            "rowIDs": [0] * half + [1] * (len(cols) - half),
            "columnIDs": cols.tolist()}).encode(), "setup")
        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        for _ in range(5):
            post("/index/ac/query", q, "warm")  # warm residency + compile

        def run_round(accounting_on: bool) -> float:
            srv.usage.enabled = accounting_on
            lats: list[float] = []
            lat_lock = threading.Lock()
            barrier = threading.Barrier(ACCOUNTING_CLIENTS)

            def client(i):
                mine = []
                barrier.wait()
                for _ in range(ACCOUNTING_QPC):
                    t0 = time.perf_counter()
                    post("/index/ac/query", q, f"bench-key-{i}")
                    mine.append((time.perf_counter() - t0) * 1e3)
                with lat_lock:
                    lats.extend(mine)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(ACCOUNTING_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return statistics.median(lats)

        rounds = []
        for _ in range(ACCOUNTING_ROUNDS):
            rnd = {"ms_off": round(run_round(False), 4),
                   "ms_on": round(run_round(True), 4)}
            rnd["overhead_pct"] = round(
                100.0 * (rnd["ms_on"] / rnd["ms_off"] - 1.0), 2) \
                if rnd["ms_off"] else 0.0
            rounds.append(rnd)
        srv.usage.enabled = True
        snap = srv.usage.snapshot()
        overheads = sorted(r["overhead_pct"] for r in rounds)
        return {
            "metric": "accounting_overhead_pct",
            "value": overheads[len(overheads) // 2],
            "unit": "% (ledger on vs off, median latency at "
                    f"{ACCOUNTING_CLIENTS} keyed clients; budget <= 1%)",
            "rounds": rounds,
            "tracked_principals": snap["trackedPrincipals"],
            "spilled_principals": snap["spilledPrincipals"],
            "total_queries_accounted": snap["totals"]["queries"],
            "vs_baseline": 0.0,
            "path": f"{ACCOUNTING_CLIENTS} keep-alive clients x "
                    f"{ACCOUNTING_QPC} Count(Intersect) each, one distinct "
                    "X-API-Key per client (ledger bound + spill exercised), "
                    "interleaved usage.enabled=False/True rounds",
        }
    finally:
        srv.close()


EVENTS_CLIENTS = int(os.environ.get("PILOSA_BENCH_EVENTS_CLIENTS", "256"))
EVENTS_QPC = int(os.environ.get("PILOSA_BENCH_EVENTS_QPC", "4"))
EVENTS_ROUNDS = int(os.environ.get("PILOSA_BENCH_EVENTS_ROUNDS", "3"))


def bench_events(tmpdir) -> dict:
    """Flight-recorder overhead A/B (budget: <= 1%): one server,
    EVENTS_CLIENTS keep-alive clients of warm Counts, interleaved
    PILOSA_TPU_EVENTS=0/1 rounds (the documented kill switch, read per
    emit). The off side still stamps the HLC response header — a mixed
    on/off cluster must stay causally ordered — so the measured delta is
    the recording path itself: the enabled() checks at every choke
    point, context auto-attach, and journal appends for whatever state
    transitions the workload trips."""
    import http.client
    import statistics
    import threading

    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "events"), port=0).open()
    prev_env = os.environ.get("PILOSA_TPU_EVENTS")
    try:
        hostport = srv.uri.split("//", 1)[1]
        _local = threading.local()

        def post(path, body):
            conn = getattr(_local, "conn", None)
            if conn is None:
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
            try:
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return out

        post("/index/ev", b"{}")
        post("/index/ev/field/f", b"{}")
        rng = np.random.default_rng(37)
        cols = rng.choice(4 * SHARD_WIDTH, size=100_000, replace=False)
        half = len(cols) // 2
        post("/index/ev/field/f/import", json.dumps({
            "rowIDs": [0] * half + [1] * (len(cols) - half),
            "columnIDs": cols.tolist()}).encode())
        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        for _ in range(5):
            post("/index/ev/query", q)  # warm residency + compile

        def run_round(recorder_on: bool) -> list:
            os.environ["PILOSA_TPU_EVENTS"] = "1" if recorder_on else "0"
            lats: list[float] = []
            lat_lock = threading.Lock()
            barrier = threading.Barrier(EVENTS_CLIENTS)

            def client(i):
                mine = []
                barrier.wait()
                for _ in range(EVENTS_QPC):
                    t0 = time.perf_counter()
                    post("/index/ev/query", q)
                    mine.append((time.perf_counter() - t0) * 1e3)
                with lat_lock:
                    lats.extend(mine)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(EVENTS_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return lats

        rounds = []
        all_off: list[float] = []
        all_on: list[float] = []
        for i in range(EVENTS_ROUNDS):
            # alternate which side runs first: within-round warmup drift
            # (thread spawn, connection setup, frequency scaling) is
            # bigger than the effect measured, and a fixed order would
            # book all of it to one side
            if i % 2 == 0:
                off, on = run_round(False), run_round(True)
            else:
                on, off = run_round(True), run_round(False)
            all_off.extend(off)
            all_on.extend(on)
            rnd = {"ms_off": round(statistics.median(off), 4),
                   "ms_on": round(statistics.median(on), 4)}
            rnd["overhead_pct"] = round(
                100.0 * (rnd["ms_on"] / rnd["ms_off"] - 1.0), 2) \
                if rnd["ms_off"] else 0.0
            rounds.append(rnd)
        snap = srv.events.snapshot()
        # headline = POOLED medians across every round: per-round medians
        # at this sample count swing ±15% on a shared host while the true
        # delta is ~0 (the hot read path contains no emit site — on/off
        # run identical per-request code), and the interleaved pooled
        # estimator averages the scheduler noise out
        med_off = statistics.median(all_off)
        med_on = statistics.median(all_on)
        pooled = round(100.0 * (med_on / med_off - 1.0), 2) \
            if med_off else 0.0
        return {
            "metric": "events_overhead_pct",
            "value": pooled,
            "unit": "% (flight recorder on vs PILOSA_TPU_EVENTS=0, "
                    f"pooled median latency at {EVENTS_CLIENTS} clients; "
                    "budget <= 1%)",
            "rounds": rounds,
            "pooled_ms_off": round(med_off, 4),
            "pooled_ms_on": round(med_on, 4),
            "samples_per_side": len(all_off),
            "events_emitted": snap["emitted"],
            "events_dropped_disabled": snap["droppedDisabled"],
            "vs_baseline": 0.0,
            "path": f"{EVENTS_CLIENTS} keep-alive clients x "
                    f"{EVENTS_QPC} Count(Intersect) each, interleaved "
                    "recorder off/on rounds via the env kill switch "
                    "(HLC response stamping identical on both sides)",
        }
    finally:
        if prev_env is None:
            os.environ.pop("PILOSA_TPU_EVENTS", None)
        else:
            os.environ["PILOSA_TPU_EVENTS"] = prev_env
        srv.close()


HEAT_CLIENTS = int(os.environ.get("PILOSA_BENCH_HEAT_CLIENTS", "16"))
HEAT_QPC = int(os.environ.get("PILOSA_BENCH_HEAT_QPC", "6"))
HEAT_ROUNDS = int(os.environ.get("PILOSA_BENCH_HEAT_ROUNDS", "3"))
HEAT_ROWS = int(os.environ.get("PILOSA_BENCH_HEAT_ROWS", "96"))
HEAT_ACCESSES = int(os.environ.get("PILOSA_BENCH_HEAT_ACCESSES", "900"))


def bench_heat(tmpdir) -> dict:
    """Fragment heat map A/B (utils/heat.py; docs/operations.md "Data
    temperature and placement advice").

    (a) tracking overhead: one server, HEAT_CLIENTS keep-alive clients
        on the residency-hot Count(Intersect) workload, interleaved
        tracker-disabled/enabled rounds. Headline = median-latency delta
        of enabling heat tracking (budget <= 1%, the accounting-stage
        methodology — the charge sites must be invisible).
    (b) eviction steering: a local executor with a deliberately
        constrained HBM residency budget (a quarter of the row working
        set) serving a skewed zipfian row-read sequence; the SAME
        sequence replays under eviction=lru and eviction=heat and the
        stage reports the warm residency hit-rate delta — heat keeps the
        zipf head resident through the long-tail scans that rotate it
        out of LRU."""
    import http.client
    import statistics
    import threading

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models import Holder
    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "heat"), port=0).open()
    try:
        hostport = srv.uri.split("//", 1)[1]
        _local = threading.local()

        def post(path, body):
            conn = getattr(_local, "conn", None)
            if conn is None:
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
            try:
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return out

        post("/index/ht", b"{}")
        post("/index/ht/field/f", b"{}")
        rng = np.random.default_rng(47)
        cols = rng.choice(4 * SHARD_WIDTH, size=100_000, replace=False)
        half = len(cols) // 2
        post("/index/ht/field/f/import", json.dumps({
            "rowIDs": [0] * half + [1] * (len(cols) - half),
            "columnIDs": cols.tolist()}).encode())
        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        for _ in range(5):
            post("/index/ht/query", q)  # warm residency + compile

        tracker = srv.executor.heat

        def run_round(heat_on: bool) -> float:
            if tracker is not None:
                tracker.enabled = heat_on
            lats: list[float] = []
            lat_lock = threading.Lock()
            barrier = threading.Barrier(HEAT_CLIENTS)

            def client(i):
                mine = []
                barrier.wait()
                for _ in range(HEAT_QPC):
                    t0 = time.perf_counter()
                    post("/index/ht/query", q)
                    mine.append((time.perf_counter() - t0) * 1e3)
                with lat_lock:
                    lats.extend(mine)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(HEAT_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return statistics.median(lats)

        rounds = []
        for _ in range(HEAT_ROUNDS):
            rnd = {"ms_off": round(run_round(False), 4),
                   "ms_on": round(run_round(True), 4)}
            rnd["overhead_pct"] = round(
                100.0 * (rnd["ms_on"] / rnd["ms_off"] - 1.0), 2) \
                if rnd["ms_off"] else 0.0
            rounds.append(rnd)
        if tracker is not None:
            tracker.enabled = True
        overheads = sorted(r["overhead_pct"] for r in rounds)
        overhead_med = overheads[len(overheads) // 2]
    finally:
        srv.close()

    # (b) heat-vs-LRU eviction under a skewed zipfian read workload at a
    # constrained HBM budget — a local executor, no HTTP in the loop
    holder = Holder(os.path.join(tmpdir, "heat-ev")).open()
    try:
        ex = Executor(holder)
        # the measurement target is RESIDENCY eviction: the plan cache
        # would absorb repeat Counts before they ever touch a leaf
        ex.plan_cache = None
        idx = holder.create_index("z")
        # heat is FRAGMENT-granular (index, field, view, shard): the skew
        # must live across fragments for the signal to differentiate
        # occupants — one field per fragment, zipf-weighted access (hot
        # dashboard fields vs a long tail), matching how placement will
        # consume the same signal
        for k in range(HEAT_ROWS):
            idx.create_field(f"f{k}").import_bits(
                [0] * 4, [k, k + 7, k + 101, k + 1013])
        # one probe query sizes a row leaf on this backend
        ex.execute("z", "Count(Row(f0=0))")
        leaf_bytes = max(1, ex.residency.bytes)
        res = ex.residency
        res.budget = leaf_bytes * max(2, HEAT_ROWS // 4)
        # skewed zipfian reads interleaved with sequential scan traffic
        # (the dashboard + batch-export mix), fixed seed: identical under
        # both modes. The scans are what separate the policies — a full
        # sweep rotates the zipf head out of a 1/4-working-set LRU, while
        # heat remembers the head's standing across the sweep.
        weights = 1.0 / np.arange(1, HEAT_ROWS + 1) ** 1.3
        weights /= weights.sum()
        zipf = rng.choice(HEAT_ROWS, size=HEAT_ACCESSES, p=weights)
        seq = []
        scan_pos = 0
        for i, r in enumerate(zipf):
            if i % 3 == 0:
                seq.append(scan_pos % HEAT_ROWS)
                scan_pos += 1
            else:
                seq.append(int(r))

        def run_eviction(mode: str) -> float:
            res.eviction = mode
            res.clear()
            h0, m0 = res.hits, res.misses
            for r in seq:
                ex.execute("z", f"Count(Row(f{int(r)}=0))")
            dh, dm = res.hits - h0, res.misses - m0
            return dh / max(1, dh + dm)

        # teach the tracker the skew once (also warms compiles), then
        # replay the identical sequence under each policy
        run_eviction("lru")
        hit_lru = run_eviction("lru")
        hit_heat = run_eviction("heat")
        heat_evictions = res.heat_evictions
    finally:
        holder.close()

    return {
        "metric": "heat_overhead_pct",
        "value": overhead_med,
        "unit": "% (tracking on vs off, median latency at "
                f"{HEAT_CLIENTS} clients; budget <= 1%)",
        "rounds": rounds,
        "eviction_ab": {
            "rows": HEAT_ROWS,
            "accesses": HEAT_ACCESSES,
            "budget_leaves": max(2, HEAT_ROWS // 4),
            "warm_hit_rate_lru": round(hit_lru, 4),
            "warm_hit_rate_heat": round(hit_heat, 4),
            "hit_rate_delta_pp": round(100 * (hit_heat - hit_lru), 2),
            "heat_evictions": heat_evictions,
        },
        "vs_baseline": 0.0,
        "path": f"{HEAT_CLIENTS} keep-alive clients x {HEAT_QPC} "
                "Count(Intersect) each, interleaved tracker off/on "
                f"rounds; then {HEAT_ACCESSES} zipf(1.3) row reads over "
                f"{HEAT_ROWS} rows at a quarter-working-set HBM budget, "
                "same sequence under eviction=lru and eviction=heat",
    }


QOS_CLIENTS = int(os.environ.get("PILOSA_BENCH_QOS_CLIENTS", "64"))
QOS_QPC = int(os.environ.get("PILOSA_BENCH_QOS_QPC", "8"))
QOS_ROUNDS = int(os.environ.get("PILOSA_BENCH_QOS_ROUNDS", "3"))
QOS_ABUSERS = int(os.environ.get("PILOSA_BENCH_QOS_ABUSERS", "8"))


def bench_qos(tmpdir) -> dict:
    """Multi-tenant QoS chaos-storm A/B (pilosa_tpu/qos.py).

    (a) idle-path admission overhead: interleaved mode=off/enforce rounds
        with no quota pressure — the admission check runs and admits
        every query. Budget: <= 1% on the median latency.
    (b) abusive-tenant isolation: QOS_CLIENTS well-behaved interactive
        clients measured alone (baseline p99), then again while
        QOS_ABUSERS threads flood batch-priority queries under a
        quota'd principal. Acceptance: the well-behaved p99 moves
        <= 15%, and the abuser's rejections are EARLY 429s carrying
        Retry-After (median rejection latency far below a query's own
        service time), not late timeouts."""
    import http.client
    import statistics
    import threading

    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "qos"), port=0, qos_mode="enforce",
                 qos_principals={
                     "key:abuser": {"priority": "batch",
                                    "queries-per-s": 50.0}}).open()
    try:
        hostport = srv.uri.split("//", 1)[1]
        _local = threading.local()

        def post(path, body, key, priority=None):
            conn = getattr(_local, "conn", None)
            if conn is None:
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
            headers = {"X-API-Key": key}
            if priority:
                headers["X-Pilosa-Priority"] = priority
            try:
                conn.request("POST", path, body=body, headers=headers)
                resp = conn.getresponse()
                out = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
                conn.request("POST", path, body=body, headers=headers)
                resp = conn.getresponse()
                out = resp.read()
            return resp, out

        def must(path, body, key):
            resp, out = post(path, body, key)
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return out

        must("/index/qs", b"{}", "setup")
        must("/index/qs/field/f", b"{}", "setup")
        rng = np.random.default_rng(47)
        cols = rng.choice(4 * SHARD_WIDTH, size=100_000, replace=False)
        half = len(cols) // 2
        must("/index/qs/field/f/import", json.dumps({
            "rowIDs": [0] * half + [1] * (len(cols) - half),
            "columnIDs": cols.tolist()}).encode(), "setup")
        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        for _ in range(5):
            must("/index/qs/query", q, "warm")

        # -- (a) admission-check overhead A/B (no pressure) --------------
        def overhead_round(mode: str) -> float:
            srv.qos.mode = mode
            lats: list[float] = []
            lock = threading.Lock()
            barrier = threading.Barrier(QOS_CLIENTS)

            def client(i):
                mine = []
                barrier.wait()
                for _ in range(QOS_QPC):
                    t0 = time.perf_counter()
                    must("/index/qs/query", q, f"good-{i}")
                    mine.append((time.perf_counter() - t0) * 1e3)
                with lock:
                    lats.extend(mine)

            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(QOS_CLIENTS)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return statistics.median(lats)

        overhead_rounds = []
        for _ in range(QOS_ROUNDS):
            rnd = {"ms_off": round(overhead_round("off"), 4),
                   "ms_on": round(overhead_round("enforce"), 4)}
            rnd["overhead_pct"] = round(
                100.0 * (rnd["ms_on"] / rnd["ms_off"] - 1.0), 2) \
                if rnd["ms_off"] else 0.0
            overhead_rounds.append(rnd)
        overheads = sorted(r["overhead_pct"] for r in overhead_rounds)

        # -- (b) abusive tenant vs well-behaved p99 ----------------------
        def p99(vals):
            vals = sorted(vals)
            return vals[min(len(vals) - 1, int(0.99 * len(vals)))]

        def storm_round(with_abuser: bool):
            srv.qos.mode = "enforce"
            lats: list[float] = []
            shed_lats: list[float] = []
            abuser_codes = {"200": 0, "429": 0, "other": 0}
            retry_after_present = 0
            lock = threading.Lock()
            stop = threading.Event()

            def good(i):
                mine = []
                for _ in range(QOS_QPC):
                    t0 = time.perf_counter()
                    must("/index/qs/query", q, f"good-{i}")
                    mine.append((time.perf_counter() - t0) * 1e3)
                with lock:
                    lats.extend(mine)

            def abuser():
                nonlocal retry_after_present
                while not stop.is_set():
                    t0 = time.perf_counter()
                    resp, _out = post("/index/qs/query", q, "abuser",
                                      priority="batch")
                    dt = (time.perf_counter() - t0) * 1e3
                    with lock:
                        if resp.status == 429:
                            abuser_codes["429"] += 1
                            shed_lats.append(dt)
                            if resp.getheader("Retry-After"):
                                retry_after_present += 1
                        elif resp.status == 200:
                            abuser_codes["200"] += 1
                        else:
                            abuser_codes["other"] += 1

            abuser_threads = []
            if with_abuser:
                for _ in range(QOS_ABUSERS):
                    t = threading.Thread(target=abuser, daemon=True)
                    t.start()
                    abuser_threads.append(t)
                # warm the storm to steady state: the abuser's token
                # bucket opens with a full burst, and measuring during
                # that window would compare against an unthrottled
                # flood the quota has not engaged on yet
                deadline = time.monotonic() + 5.0
                while time.monotonic() < deadline:
                    with lock:
                        if abuser_codes["429"] >= 1:
                            break
                    time.sleep(0.05)
                with lock:
                    shed_lats.clear()
            ts = [threading.Thread(target=good, args=(i,))
                  for i in range(QOS_CLIENTS)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            stop.set()
            for t in abuser_threads:
                t.join(timeout=5)
            out = {"p99_ms": round(p99(lats), 3),
                   "p50_ms": round(statistics.median(lats), 3)}
            if with_abuser:
                out["abuser"] = dict(abuser_codes)
                out["abuserRetryAfterPresent"] = retry_after_present
                if shed_lats:
                    out["shed_p50_ms"] = round(
                        statistics.median(shed_lats), 3)
            return out

        storm_rounds = []
        for _ in range(QOS_ROUNDS):
            base = storm_round(False)
            storm = storm_round(True)
            delta = (100.0 * (storm["p99_ms"] / base["p99_ms"] - 1.0)
                     if base["p99_ms"] else 0.0)
            storm_rounds.append({"baseline": base, "storm": storm,
                                 "p99_delta_pct": round(delta, 2)})
        deltas = sorted(r["p99_delta_pct"] for r in storm_rounds)
        snap = srv.qos.snapshot()
        last = storm_rounds[-1]["storm"]
        return {
            "metric": "qos_p99_delta_pct",
            "value": deltas[len(deltas) // 2],
            "unit": "% (well-behaved p99, abuser storm vs baseline, "
                    "enforce; budget <= 15%)",
            "admission_overhead_pct": overheads[len(overheads) // 2],
            "admission_overhead_rounds": overhead_rounds,
            "storm_rounds": storm_rounds,
            "abuser_throttled_429": last.get("abuser", {}).get("429", 0),
            "abuser_retry_after_present":
                last.get("abuserRetryAfterPresent", 0),
            "shed_p50_ms": last.get("shed_p50_ms", 0.0),
            "sheds_counted": snap["throttled"],
            "vs_baseline": 0.0,
            "path": f"{QOS_CLIENTS} interactive keep-alive clients x "
                    f"{QOS_QPC} Count(Intersect) vs {QOS_ABUSERS} "
                    "batch-priority abuser threads under a 50 q/s quota; "
                    "interleaved baseline/storm rounds + mode off/enforce "
                    "idle-path A/B",
        }
    finally:
        srv.close()


INGEST_WRITERS = int(os.environ.get("PILOSA_BENCH_INGEST_WRITERS", "8"))
INGEST_ENVELOPE = int(os.environ.get("PILOSA_BENCH_INGEST_ENVELOPE", "500"))
INGEST_READERS = int(os.environ.get("PILOSA_BENCH_INGEST_READERS", "256"))
INGEST_READ_QPC = int(os.environ.get("PILOSA_BENCH_INGEST_READ_QPC", "4"))


def bench_ingest(tmpdir) -> dict:
    """Streaming-ingest throughput concurrent with serving (ISSUE 16).

    INGEST_WRITERS keep-alive writer threads flood mixed Set/Clear
    envelopes (80/20, INGEST_ENVELOPE mutations each) through the
    coalesced write path while INGEST_READERS interactive clients run
    the warm dense-read workload. Headline: acked mutations/s during the
    concurrent window (acceptance >= 100k/s). Gates: the readers' warm
    p50 moves <= 15% vs a writer-free baseline round; every acked write
    is immediately readable (read-your-writes spot check); and the WAL
    group-commit ratio — per-bit-equivalent WAL writes (one per mutation
    plus one per Set for existence marking) over actual fsync-able
    appends — is >= 10x."""
    import http.client
    import statistics
    import threading

    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "ingest"), port=0).open()
    try:
        hostport = srv.uri.split("//", 1)[1]
        _local = threading.local()

        def post(path, body, batch_priority=False):
            # bulk writers self-declare the QoS batch class, the
            # documented practice for ingest clients (docs/operations.md
            # "Streaming ingest"): under admission pressure reads order
            # ahead of the flood
            headers = ({"X-Pilosa-Priority": "batch"} if batch_priority
                       else {})
            conn = getattr(_local, "conn", None)
            if conn is None:
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
            try:
                conn.request("POST", path, body=body, headers=headers)
                resp = conn.getresponse()
                out = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
                conn.request("POST", path, body=body, headers=headers)
                resp = conn.getresponse()
                out = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return out

        post("/index/in", b"{}")
        post("/index/in/field/f", b"{}")
        post("/index/in/field/w", b"{}")
        rng = np.random.default_rng(16)
        cols = rng.choice(4 * SHARD_WIDTH, size=100_000, replace=False)
        half = len(cols) // 2
        post("/index/in/field/f/import", json.dumps({
            "rowIDs": [0] * half + [1] * (len(cols) - half),
            "columnIDs": cols.tolist()}).encode())
        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        for _ in range(5):
            post("/index/in/query", q)

        def read_round(stop_writers=None):
            lats: list[float] = []
            lock = threading.Lock()
            barrier = threading.Barrier(INGEST_READERS)

            def reader(i):
                mine = []
                barrier.wait()
                for _ in range(INGEST_READ_QPC):
                    t0 = time.perf_counter()
                    post("/index/in/query", q)
                    mine.append((time.perf_counter() - t0) * 1e3)
                with lock:
                    lats.extend(mine)

            ts = [threading.Thread(target=reader, args=(i,))
                  for i in range(INGEST_READERS)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            if stop_writers is not None:
                stop_writers.set()
            lats.sort()
            return (statistics.median(lats),
                    lats[min(len(lats) - 1, int(0.99 * len(lats)))])

        base_p50, base_p99 = read_round()

        # -- concurrent writers: mixed 80/20 Set/Clear envelopes ---------
        acked = [0] * INGEST_WRITERS
        write_errors: list = []
        stop = threading.Event()

        def writer(tid):
            wrng = np.random.default_rng(1000 + tid)
            lane = tid * 50_000_000  # disjoint columns per writer
            seq = 0
            try:
                while not stop.is_set():
                    calls = []
                    for _ in range(INGEST_ENVELOPE):
                        if seq and wrng.random() < 0.2:
                            c = lane + int(wrng.integers(0, seq))
                            calls.append(f"Clear({c}, w={tid % 4})")
                        else:
                            calls.append(f"Set({lane + seq}, w={tid % 4})")
                            seq += 1
                    post("/index/in/query", "".join(calls).encode(),
                         batch_priority=True)
                    acked[tid] += INGEST_ENVELOPE
            except BaseException as e:  # noqa: BLE001
                write_errors.append(repr(e))

        writers = [threading.Thread(target=writer, args=(t,), daemon=True)
                   for t in range(INGEST_WRITERS)]
        t0 = time.perf_counter()
        for t in writers:
            t.start()
        conc_p50, conc_p99 = read_round(stop_writers=stop)
        for t in writers:
            t.join(timeout=60)
        elapsed = time.perf_counter() - t0
        total_acked = sum(acked)
        sets_per_s = total_acked / elapsed if elapsed else 0.0

        # read-your-writes: acked mutations are immediately visible
        ryw = json.loads(post(
            "/index/in/query", b"Count(Row(w=0))").decode())
        ryw_count = ryw["results"][0]

        dv = json.loads(urlopen_json(srv.uri + "/debug/vars"))
        ing = dv["ingest"]
        perbit_equiv = ing["mutations"] + ing["setMutations"]
        fsync_reduction = (perbit_equiv / ing["walAppends"]
                           if ing["walAppends"] else float("inf"))
        p50_delta = (100.0 * (conc_p50 / base_p50 - 1.0)
                     if base_p50 else 0.0)
        return {
            "metric": "ingest_sets_per_s",
            "value": round(sets_per_s, 1),
            "unit": "acked mutations/s concurrent with "
                    f"{INGEST_READERS}-client reads (acceptance >= 100k)",
            "mutations_acked": total_acked,
            "write_errors": write_errors[:3],
            "read_p50_ms_baseline": round(base_p50, 3),
            "read_p99_ms_baseline": round(base_p99, 3),
            "read_p50_ms_concurrent": round(conc_p50, 3),
            "read_p99_ms_concurrent": round(conc_p99, 3),
            "read_p50_delta_pct": round(p50_delta, 2),
            "read_your_writes_count": ryw_count,
            "fsync_reduction_x": round(fsync_reduction, 1),
            "wal_appends": ing["walAppends"],
            "applied_batches": ing["appliedBatches"],
            "max_batch_seen": ing["max_batch_seen"],
            "patched_leaves": ing["patchedDense"] + ing["patchedSparse"],
            "vs_baseline": 0.0,
            "path": f"{INGEST_WRITERS} keep-alive writers x "
                    f"{INGEST_ENVELOPE}-mutation 80/20 Set/Clear "
                    f"envelopes vs {INGEST_READERS} readers x "
                    f"{INGEST_READ_QPC} warm Count(Intersect); "
                    "baseline/concurrent read rounds",
        }
    finally:
        srv.close()


def urlopen_json(url: str):
    import urllib.request
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.read()


PLANNER_SHARDS = 8
PLANNER_CLIENTS = int(os.environ.get("PILOSA_BENCH_PLANNER_CLIENTS", "256"))
PLANNER_ROUNDS = int(os.environ.get("PILOSA_BENCH_PLANNER_ROUNDS", "3"))
PLANNER_QUERIES_PER_CLIENT = int(os.environ.get(
    "PILOSA_BENCH_PLANNER_QPC", "4"))
PLANNER_CHAIN_QUERIES = int(os.environ.get(
    "PILOSA_BENCH_PLANNER_CHAIN_QUERIES", "40"))


def bench_planner(tmpdir) -> dict:
    """Cost-based planner + plan-cache A/B (interleaved rounds).

    (a) skewed-cardinality intersect chains, plan cache DISABLED on both
        sides: planner on vs off isolates the planning pass itself. On
        the dense engine a reorder does not change kernel cost, so the
        honest claim here is bounded overhead (acceptance: regression
        within noise, <= 3%).
    (b) repeated-dashboard workload: PLANNER_CLIENTS keep-alive clients
        issuing queries with ~80% overlapping subexpressions (the shared
        dashboard panels, in per-client permuted operand order — the
        canonicalizing reorder is what makes permutations share one
        cache key) and ~20% ad-hoc uniques. Cache on vs off interleaved;
        the headline is the p50 speedup of the cache-hit path
        (acceptance: >= 1.3x) plus the measured cache hit rate.
    """
    import http.client
    import statistics
    import threading

    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "plan"), port=0).open()
    try:
        hostport = srv.uri.split("//", 1)[1]
        _local = threading.local()

        def post(path, body):
            conn = getattr(_local, "conn", None)
            if conn is None:
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
            try:
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return json.loads(out)

        post("/index/pl", b"{}")
        post("/index/pl/field/d", b"{}")
        rng = np.random.default_rng(29)
        # 32 rows, skewed cardinalities: row r holds ~200k >> ... >> ~50
        # bits (the regime where cardinality ordering matters on CPU
        # engines, and where dashboards mix broad and narrow filters)
        rows_l, cols_l = [], []
        for r in range(32):
            n = max(50, 200_000 >> (r % 12))
            cols = rng.choice(PLANNER_SHARDS * SHARD_WIDTH,
                              size=n, replace=False)
            rows_l += [r] * len(cols)
            cols_l += cols.tolist()
        post("/index/pl/field/d/import", json.dumps({
            "rowIDs": rows_l, "columnIDs": cols_l}).encode())
        ex = srv.api.executor

        # ---- (a) skewed chain: planner on/off, cache off both sides ----
        chain_q = (b"Count(Intersect(Row(d=0), Row(d=11), Row(d=5), "
                   b"Row(d=2)))")
        ex.plan_cache.enabled = False
        for _ in range(5):
            post("/index/pl/query", chain_q)  # warm compile + residency

        def chain_p50(planner_on: bool) -> float:
            ex.planner.enabled = planner_on
            lats = []
            for _ in range(PLANNER_CHAIN_QUERIES):
                t0 = time.perf_counter()
                post("/index/pl/query", chain_q)
                lats.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(lats)

        chain_rounds = []
        for _ in range(PLANNER_ROUNDS):
            off = chain_p50(False)
            on = chain_p50(True)
            chain_rounds.append({
                "p50_ms_off": round(off, 4), "p50_ms_on": round(on, 4),
                "overhead_pct": round(100.0 * (on / off - 1.0), 2)
                if off else 0.0})
        ex.planner.enabled = True
        chain_overhead = statistics.median(
            r["overhead_pct"] for r in chain_rounds)

        # ---- (b) repeated dashboard: cache on/off, planner on ----------
        # 10 shared "dashboard panels"; every client issues each in its
        # OWN operand permutation (the canonical reorder dedups them)
        shared = []
        for k in range(10):
            a, b, c = (k % 8), 8 + (k % 6), 14 + (k % 9)
            shared.append([f"Row(d={a})", f"Row(d={b})", f"Row(d={c})"])

        def dashboard_query(tid: int, i: int) -> bytes:
            r = np.random.default_rng((tid << 20) | i)
            if r.random() < 0.8:
                panel = list(shared[int(r.integers(len(shared)))])
                r.shuffle(panel)  # permuted phrasing of the same panel
                return f"Count(Intersect({', '.join(panel)}))".encode()
            picks = r.choice(32, size=3, replace=False)  # ad-hoc unique
            ops = ", ".join(f"Row(d={int(p)})" for p in picks)
            return f"Count(Union({ops}))".encode()

        lat_lock = threading.Lock()

        def run_clients(round_no: int) -> list:
            lats: list = []

            def client(tid: int):
                mine = []
                for i in range(PLANNER_QUERIES_PER_CLIENT):
                    q = dashboard_query(tid, (round_no << 8) | i)
                    t0 = time.perf_counter()
                    post("/index/pl/query", q)
                    mine.append((time.perf_counter() - t0) * 1e3)
                with lat_lock:
                    lats.extend(mine)

            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(PLANNER_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return lats

        dash_rounds = []
        hit_rates = []
        for rnd in range(PLANNER_ROUNDS):
            ex.plan_cache.enabled = False
            ex.plan_cache.clear()
            p50_off = statistics.median(run_clients(rnd))
            ex.plan_cache.enabled = True
            s0 = ex.plan_cache.snapshot()
            # same round twice cache-on: first warms, second measures the
            # steady repeated-dashboard state (clients re-issue panels)
            run_clients(rnd)
            p50_on = statistics.median(run_clients(rnd))
            s1 = ex.plan_cache.snapshot()
            look = (s1["hits"] - s0["hits"]) + (s1["misses"] - s0["misses"])
            hit_rates.append((s1["hits"] - s0["hits"]) / look
                             if look else 0.0)
            dash_rounds.append({
                "p50_ms_cache_off": round(p50_off, 4),
                "p50_ms_cache_on": round(p50_on, 4),
                "speedup": round(p50_off / p50_on, 3) if p50_on else 0.0})
        p50_on_med = statistics.median(
            r["p50_ms_cache_on"] for r in dash_rounds)
        p50_off_med = statistics.median(
            r["p50_ms_cache_off"] for r in dash_rounds)
        speedup = round(p50_off_med / p50_on_med, 3) if p50_on_med else 0.0
        hit_rate = round(statistics.median(hit_rates), 4)

        out = {
            "metric": "planner_dashboard_speedup",
            "value": speedup,
            "unit": "x (p50, plan cache on vs off; acceptance >= 1.3)",
            "cache_hit_rate": hit_rate,
            "planner_overhead_pct": chain_overhead,
            "skewed_chain_rounds": chain_rounds,
            "dashboard_rounds": dash_rounds,
            "dashboard_p50_ms_on": round(p50_on_med, 4),
            "dashboard_p50_ms_off": round(p50_off_med, 4),
            "clients": PLANNER_CLIENTS,
            "vs_baseline": 0.0,
            "path": f"{PLANNER_CLIENTS} keep-alive clients, 80% shared "
                    "panels in permuted operand order / 20% ad-hoc, "
                    "interleaved plan-cache off/on rounds; skewed-chain "
                    "A/B isolates planning overhead with the cache off "
                    "(go ref: kernel time of the same Count shape)",
        }
        # the honest external anchor: the Go proxy's kernel time for a
        # Count over the same shard count (its wire overhead would only
        # add) against the cache-hit serving path
        _attach_go_ref(out, "http_count_8shard", p50_on_med / 1e3)
        return out
    finally:
        srv.close()


DIST_SHARDS = 16
DIST_NODES = int(os.environ.get("PILOSA_BENCH_DIST_NODES", "3"))
DIST_THREADS = 8
DIST_THREADS_PEAK = int(os.environ.get("PILOSA_BENCH_DIST_THREADS_PEAK", "64"))
DIST_QUERIES = 96
# coalescing A/B: fixed concurrency + interleaved on/off rounds (the
# shared bench host drifts; per-round ratios are the honest signal)
DIST_AB_THREADS = int(os.environ.get("PILOSA_BENCH_DIST_AB_THREADS", "32"))
DIST_AB_ROUNDS = int(os.environ.get("PILOSA_BENCH_DIST_AB_ROUNDS", "5"))
DIST_SWEEP = [1, 4, 8, 16, 32, 64]


def _keepalive_qps(host: str, path: str, body: bytes, check,
                   clients: int, per_thread: int) -> float:
    """Closed-loop QPS with one persistent HTTP connection per client —
    measures the server, not urllib's per-request reconnect churn (the
    sweep/A-B companion to the urllib-based headline, whose methodology
    is kept for round-over-round continuity)."""
    import http.client
    import threading

    errors = []

    def client(tid):
        conn = http.client.HTTPConnection(host, timeout=60)
        try:
            for _ in range(per_thread):
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = json.loads(resp.read())
                check(out)
        except Exception as e:  # noqa: BLE001 — surface the first error
            errors.append(e)
        finally:
            conn.close()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return clients * per_thread / elapsed


DEVOBS_CLIENTS = int(os.environ.get("PILOSA_BENCH_DEVOBS_CLIENTS", "64"))
DEVOBS_QPC = int(os.environ.get("PILOSA_BENCH_DEVOBS_QPC", "8"))
DEVOBS_ROUNDS = int(os.environ.get("PILOSA_BENCH_DEVOBS_ROUNDS", "3"))
DEVOBS_EXPLAINS = int(os.environ.get("PILOSA_BENCH_DEVOBS_EXPLAINS", "64"))
DEVOBS_MICRO_N = int(os.environ.get("PILOSA_BENCH_DEVOBS_MICRO_N", "2000"))


def _devobs_dispatch_micro() -> dict:
    """Sequential per-dispatch attribution cost: the SAME counted_jit
    kernel called DEVOBS_MICRO_N times with kernel stats off, then on,
    in interleaved blocks. The A/B under concurrent serving is the
    headline (that is the configuration operators run), but on a noisy
    shared host its medians carry scheduler jitter orders of magnitude
    above the effect; this sequential delta is the stable lower-level
    number: nanoseconds added to one dispatch by the perf_counter pair,
    the arity walk and the histogram booking."""
    import statistics

    import jax.numpy as jnp

    from pilosa_tpu.utils import telemetry as _telemetry

    @_telemetry.counted_jit("bitwise")
    def _k(a, b):
        return a & b

    x = jnp.zeros((8, 128), dtype=jnp.uint32)
    _k(x, x)  # compile outside the measurement
    blocks = {"0": [], "1": []}
    for rep in range(6):
        side = "01"[rep % 2]
        os.environ["PILOSA_TPU_KERNEL_STATS"] = side
        t0 = time.perf_counter()
        for _ in range(DEVOBS_MICRO_N // 6 + 1):
            _k(x, x)
        blocks[side].append(
            (time.perf_counter() - t0) / (DEVOBS_MICRO_N // 6 + 1))
    off = statistics.median(blocks["0"]) * 1e9
    on = statistics.median(blocks["1"]) * 1e9
    return {"dispatch_ns_off": round(off, 1),
            "dispatch_ns_on": round(on, 1),
            "dispatch_overhead_ns": round(on - off, 1)}


def bench_device_obs(tmpdir) -> dict:
    """Kernel-stats attribution overhead A/B (budget: <= 1%): one
    server, DEVOBS_CLIENTS keep-alive clients of warm Counts,
    interleaved PILOSA_TPU_KERNEL_STATS=0/1 rounds (the documented kill
    switch, read per dispatch). Both sides pay the XLA compile/cached
    accounting — that predates this stage — so the measured delta is the
    attribution path itself: the perf_counter pair around each dispatch,
    the arity walk over flattened leaves, and the histogram booking.
    Same interleaved pooled-median estimator as the events stage (the
    per-round medians swing more than the effect measured). The detail
    carries the EXPLAIN round trip: p50 of ?explain=true on the warm
    query — the plan-without-dispatch path operators will point
    dashboards at."""
    import http.client
    import statistics
    import threading

    from pilosa_tpu.server import Server

    srv = Server(os.path.join(tmpdir, "devobs"), port=0).open()
    prev_env = os.environ.get("PILOSA_TPU_KERNEL_STATS")
    try:
        hostport = srv.uri.split("//", 1)[1]
        _local = threading.local()

        def post(path, body):
            conn = getattr(_local, "conn", None)
            if conn is None:
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
            try:
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            except (http.client.HTTPException, OSError):
                conn.close()
                conn = _local.conn = http.client.HTTPConnection(
                    hostport, timeout=60)
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            if resp.status != 200:
                raise RuntimeError(f"{path}: {resp.status}: {out[:200]}")
            return out

        post("/index/dv", b"{}")
        post("/index/dv/field/f", b"{}")
        rng = np.random.default_rng(41)
        n_rows = 16
        cols = rng.choice(4 * SHARD_WIDTH, size=100_000, replace=False)
        per = len(cols) // n_rows
        post("/index/dv/field/f/import", json.dumps({
            "rowIDs": [r for r in range(n_rows) for _ in range(per)],
            "columnIDs": cols[: per * n_rows].tolist()}).encode())
        # DISTINCT query strings per request: a repeated query is served
        # from the result cache without touching the device, which would
        # A/B an empty dispatch path. Distinct 4-row unions miss the
        # result cache every time while hitting the SAME jit signature,
        # so every request crosses the attribution choke point.
        import itertools
        need = (2 * DEVOBS_ROUNDS + 2) * DEVOBS_CLIENTS * DEVOBS_QPC + 64
        queries = []
        for combo in itertools.permutations(range(n_rows), 4):
            queries.append(
                "Count(Union(%s))" % ", ".join(
                    f"Row(f={r})" for r in combo))
            if len(queries) >= need:
                break
        for r in range(n_rows):
            post("/index/dv/query",
                 f"Count(Row(f={r}))".encode())  # warm residency
        post("/index/dv/query", queries[-1].encode())  # warm the compile
        q_next = itertools.count()

        def run_round(stats_on: bool) -> list:
            os.environ["PILOSA_TPU_KERNEL_STATS"] = \
                "1" if stats_on else "0"
            lats: list[float] = []
            lat_lock = threading.Lock()
            barrier = threading.Barrier(DEVOBS_CLIENTS)

            def client(i):
                mine = []
                barrier.wait()
                for _ in range(DEVOBS_QPC):
                    q = queries[next(q_next) % len(queries)]
                    t0 = time.perf_counter()
                    post("/index/dv/query", q.encode())
                    mine.append((time.perf_counter() - t0) * 1e3)
                with lat_lock:
                    lats.extend(mine)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(DEVOBS_CLIENTS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return lats

        # discarded warmup rounds: the first concurrent rounds ride the
        # executor pool / plan cache / connection warmup curve (median
        # drops ~10x before steady state), which would swamp any A/B no
        # matter how the sides interleave
        run_round(False)
        run_round(True)
        rounds = []
        all_off: list[float] = []
        all_on: list[float] = []
        for i in range(DEVOBS_ROUNDS):
            # alternate first-runner per round — see bench_events: the
            # within-round warmup drift exceeds the effect measured
            if i % 2 == 0:
                off, on = run_round(False), run_round(True)
            else:
                on, off = run_round(True), run_round(False)
            all_off.extend(off)
            all_on.extend(on)
            rnd = {"ms_off": round(statistics.median(off), 4),
                   "ms_on": round(statistics.median(on), 4)}
            rnd["overhead_pct"] = round(
                100.0 * (rnd["ms_on"] / rnd["ms_off"] - 1.0), 2) \
                if rnd["ms_off"] else 0.0
            rounds.append(rnd)
        med_off = statistics.median(all_off)
        med_on = statistics.median(all_on)
        pooled = round(100.0 * (med_on / med_off - 1.0), 2) \
            if med_off else 0.0
        # EXPLAIN round trip: sequential p50 of the zero-dispatch path
        os.environ["PILOSA_TPU_KERNEL_STATS"] = "1"
        ex_lats: list[float] = []
        for _ in range(DEVOBS_EXPLAINS):
            t0 = time.perf_counter()
            post("/index/dv/query?explain=true", queries[0].encode())
            ex_lats.append((time.perf_counter() - t0) * 1e3)
        from pilosa_tpu.utils import telemetry as _telemetry
        ks = _telemetry.kernels.totals()
        micro = _devobs_dispatch_micro()
        return {
            "metric": "device_obs_overhead_pct",
            "value": pooled,
            **micro,
            "unit": "% (kernel attribution on vs "
                    "PILOSA_TPU_KERNEL_STATS=0, pooled median latency "
                    f"at {DEVOBS_CLIENTS} clients; budget <= 1%)",
            "rounds": rounds,
            "pooled_ms_off": round(med_off, 4),
            "pooled_ms_on": round(med_on, 4),
            "samples_per_side": len(all_off),
            "explain_p50_ms": round(statistics.median(ex_lats), 4),
            "explain_samples": len(ex_lats),
            "kernel_dispatches_attributed": ks["dispatches"],
            "vs_baseline": 0.0,
            "path": f"{DEVOBS_CLIENTS} keep-alive clients x "
                    f"{DEVOBS_QPC} distinct Count(Union(4 rows)) each "
                    "(result-cache misses, jit-cache hits), interleaved "
                    "kernel-stats off/on rounds via the env kill "
                    "switch; then ?explain=true round trips",
        }
    finally:
        if prev_env is None:
            os.environ.pop("PILOSA_TPU_KERNEL_STATS", None)
        else:
            os.environ["PILOSA_TPU_KERNEL_STATS"] = prev_env
        srv.close()


def bench_hybrid(tmpdir) -> dict:
    """Hybrid sparse/dense containers (ISSUE 15): two interleaved A/Bs.

    (a) equal-HBM-budget capacity on a zipf-sparse dataset: a budget
        sized for only ~6 dense planes, swept twice over a 160-row
        working set whose cardinalities follow a zipf tail (a few rows
        above the sparse threshold, most far below — the realistic
        sparsity regime of the motivation). Reported: resident row
        leaves and warm-pass hit rate, hybrid vs pure dense. Acceptance:
        >= 4x resident sparse rows at equal budget.
    (b) dense-headline guard: the executor-bench query shape over rows
        ABOVE the threshold, hybrid on vs off interleaved on one
        executor — enabling hybrid must not touch the dense path
        (acceptance: warm p50 delta <= 15%, the --compare gate's bound).
    """
    import statistics

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models import Holder

    shards = 2
    n_rows = 160
    holder = Holder(os.path.join(tmpdir, "hybrid")).open()
    try:
        idx = holder.create_index("hy", track_existence=False)
        f = idx.create_field("f")
        rng = np.random.default_rng(47)
        sets = {}
        for r in range(n_rows):
            # zipf tail: row 0 ~ 30k bits per shard (dense), the bulk of
            # the tail far below the 4096 sparse threshold
            per_shard = max(16, int(30000 / (1 + r)))
            cols = np.concatenate([
                rng.choice(SHARD_WIDTH, size=per_shard, replace=False)
                .astype(np.int64) + s * SHARD_WIDTH
                for s in range(shards)])
            f.import_bits([r] * cols.size, cols.tolist())
            sets[r] = cols
        # budget = 12 planes: the zipf head (7 above-threshold rows)
        # plus the whole sparse tail fits as hybrid (~7.2 planes), while
        # the all-dense arm needs 160 planes and scan-thrashes — the
        # regime the motivation describes (sparse rows wasting the
        # budget ROADMAP items 2-4 fight over)
        plane_bytes = shards * (SHARD_WIDTH // 8)
        budget = 12 * plane_bytes

        def sweep(threshold: int):
            ex = Executor(holder)
            ex.plan_cache.enabled = False  # the residency LRU is under test
            ex.hybrid.threshold = threshold
            ex.residency.budget = budget
            for r in range(n_rows):  # cold pass: fill
                (n,) = ex.execute("hy", f"Count(Row(f={r}))")
                assert n == sets[r].size
            before = ex.residency.snapshot()
            for r in range(n_rows):  # warm pass: who stayed resident?
                ex.execute("hy", f"Count(Row(f={r}))")
            after = ex.residency.snapshot()
            lookups = (after["hits"] + after["misses"]
                       - before["hits"] - before["misses"])
            hit_rate = (after["hits"] - before["hits"]) / max(1, lookups)
            bk = after["by_kind"]
            resident = (bk.get("sparse", {}).get("entries", 0)
                        + bk.get("row", {}).get("entries", 0))
            return resident, round(hit_rate, 4)

        res_hybrid, warm_hybrid = sweep(4096)
        res_dense, warm_dense = sweep(0)
        ratio = res_hybrid / max(1, res_dense)

        # (b) dense-headline guard: rows 0..3 are all above the threshold
        ex = Executor(holder)
        ex.plan_cache.enabled = False
        qs = [f"Count(Intersect(Row(f={a}), Row(f={b})))"
              for a, b in ((0, 1), (1, 2), (2, 3), (0, 3))]
        for q in qs:  # warm both representations' residency
            ex.execute("hy", q)

        def round_p50():
            lat = []
            for _ in range(6):
                for q in qs:
                    t0 = time.perf_counter()
                    ex.execute("hy", q)
                    lat.append((time.perf_counter() - t0) * 1e3)
            return statistics.median(lat)

        on_p50, off_p50 = [], []
        for _ in range(4):  # interleaved: drift hits both arms alike
            ex.hybrid.threshold = 4096
            on_p50.append(round_p50())
            ex.hybrid.threshold = 0
            off_p50.append(round_p50())
        on_med = statistics.median(on_p50)
        off_med = statistics.median(off_p50)
        overhead = (on_med / off_med - 1.0) * 100.0
        return {
            "metric": "hybrid_capacity_ratio",
            "value": round(ratio, 2),
            "unit": "x resident rows at equal HBM budget",
            "vs_baseline": 0.0,
            "resident_rows_hybrid": res_hybrid,
            "resident_rows_dense": res_dense,
            "warm_hit_rate_hybrid": warm_hybrid,
            "warm_hit_rate_dense": warm_dense,
            "budget_planes": 12,
            "rows": n_rows,
            "dense_overhead_pct": round(overhead, 2),
            "dense_on_p50_ms": round(on_med, 3),
            "dense_off_p50_ms": round(off_med, 3),
            "path": "zipf-sparse capacity sweep (2 passes x 160 rows, "
                    "budget = 12 dense planes) hybrid vs dense; dense "
                    "headline Count(Intersect) interleaved hybrid "
                    "on/off on above-threshold rows",
        }
    finally:
        holder.close()


def bench_distributed(tmpdir) -> dict:
    """Config 5: distributed Intersect+Count over a 3-node cluster — the
    mapReduce fan-out path (executor.go:2183 analog): node 0 executes its
    own shards locally (device) and scatter-gathers the rest from nodes
    1..N over the coalesced /internal/query-batch envelope (net/coalesce),
    merging per-shard counts. All in-process nodes share the one real
    chip; the measured delta vs the single-node executor number is the
    fan-out + wire + remote-re-parse overhead. Grew from 2 to 3 nodes in
    the coalescing round: with one remote node the coordinator's own
    HTTP+execute cost dominates and the A/B understates the wire effect
    every additional node multiplies."""
    import urllib.request

    from pilosa_tpu.server import Server

    servers = [Server(os.path.join(tmpdir, f"dn{i}"), port=0).open()
               for i in range(DIST_NODES)]
    try:
        uris = [s.uri for s in servers]
        for s in servers:
            s.cluster_hosts = uris
            s.refresh_membership()

        def post(uri, path, body):
            req = urllib.request.Request(uri + path, data=body,
                                         method="POST")
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())

        post(uris[0], "/index/d", b"{}")
        post(uris[0], "/index/d/field/f", b"{}")
        rng = np.random.default_rng(29)
        n_per = int(SHARD_WIDTH * 0.005)
        sets = {}
        row_ids, col_ids = [], []
        for shard in range(DIST_SHARDS):
            for row in (0, 1):
                cols = (rng.choice(SHARD_WIDTH, size=n_per, replace=False)
                        .astype(np.int64) + shard * SHARD_WIDTH)
                sets[(row, shard)] = cols
                row_ids += [row] * n_per
                col_ids += cols.tolist()
        # one import POST: the API splits by shard and forwards each batch
        # to its owning node (api.py forward_import_fn)
        post(uris[0], "/index/d/field/f/import", json.dumps({
            "rowIDs": row_ids, "columnIDs": col_ids}).encode())

        q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        out = post(uris[0], "/index/d/query", q)  # warm + correctness
        expect = sum(
            np.intersect1d(sets[(0, s)], sets[(1, s)]).size
            for s in range(DIST_SHARDS))
        assert out["results"][0] == expect, (out, expect)
        # every node must answer identically (remote re-parse path). Peers
        # learn of shards they don't host via the async create-shard
        # announcements, so poll briefly for convergence (the same eventual
        # visibility the cluster tests assert; the import coordinator —
        # node 0, asserted above — is always immediately correct)
        deadline = time.monotonic() + 30
        for u in uris[1:]:
            while True:
                out1 = post(u, "/index/d/query", q)
                if out1["results"][0] == expect:
                    break
                assert time.monotonic() < deadline, (u, out1, expect)
                time.sleep(0.25)

        per_q, conc, per_q_base, per_q_peak = _measure_base_peak(
            DIST_THREADS, DIST_THREADS_PEAK,
            DIST_QUERIES // DIST_THREADS,
            max(2, DIST_QUERIES // DIST_THREADS_PEAK),
            lambda tid, i: post(uris[0], "/index/d/query", q))

        host = uris[0].split("//", 1)[1]

        def check(o):
            assert o["results"][0] == expect, (o, expect)

        def qps_at(clients: int, per_thread: int) -> float:
            return _keepalive_qps(host, "/index/d/query", q, check,
                                  clients, per_thread)

        # saturating-concurrency sweep (keep-alive clients): where does
        # the coordinator stop converting clients into throughput? The
        # knee was never captured in earlier rounds (VERDICT r5: 43 q/s @8
        # clients, "no saturation point")
        sweep = []
        for c in DIST_SWEEP:
            sweep.append({"clients": c,
                          "qps": round(qps_at(c, max(4, 192 // c)), 2)})
        # saturation = smallest client count reaching >=90% of the sweep's
        # peak rate (robust to non-monotone noise on a shared host, where
        # a first-gain-below-10% walk stops at the first dip)
        peak = max(p["qps"] for p in sweep)
        saturation = next(p["clients"] for p in sweep
                          if p["qps"] >= 0.9 * peak)

        # coalescing A/B at fixed concurrency: same cluster, same warm
        # residency, interleaved off/on rounds (the shared host drifts —
        # per-round ratios are the honest signal, the median ratio the
        # headline). Factor/dedup deltas come from the coordinator's
        # NodeCoalescer counters.
        coal = servers[0].executor.coalescer
        ab_rounds = []
        for _ in range(DIST_AB_ROUNDS):
            rnd = {}
            for mode in ("off", "on"):
                if coal is not None:
                    coal.enabled = mode == "on"
                snap0 = coal.snapshot() if coal is not None else {}
                rnd[f"qps_{mode}"] = round(qps_at(DIST_AB_THREADS, 8), 2)
                snap1 = coal.snapshot() if coal is not None else {}
                if mode == "on" and coal is not None:
                    nb = snap1["batches"] - snap0["batches"]
                    nq = (snap1["batched_queries"]
                          - snap0["batched_queries"])
                    rnd["coalesce_factor"] = round(nq / nb, 2) if nb else 0.0
                    rnd["deduped"] = (snap1["deduped_queries"]
                                      - snap0["deduped_queries"])
            rnd["speedup"] = (round(rnd["qps_on"] / rnd["qps_off"], 2)
                              if rnd["qps_off"] else 0.0)
            ab_rounds.append(rnd)
        if coal is not None:
            coal.enabled = True
        speedups = sorted(r["speedup"] for r in ab_rounds)
        factors = [r.get("coalesce_factor", 0.0) for r in ab_rounds]

        out = {
            "metric": f"distributed_count_qps_16shard_{DIST_NODES}node",
            "value": round(1.0 / per_q, 2),
            "unit": "queries/s",
            "tpu_ms_per_query": round(per_q * 1e3, 4),
            "concurrency": conc,
            "qps_at_base_concurrency": {"clients": DIST_THREADS,
                                        "qps": round(1.0 / per_q_base, 2)},
            "concurrency_sweep": sweep,
            "saturation_clients": saturation,
            "coalesce_ab": {
                "clients": DIST_AB_THREADS,
                "rounds": ab_rounds,
                "median_speedup_on_vs_off": speedups[len(speedups) // 2],
                "mean_coalesce_factor": round(
                    sum(factors) / len(factors), 2) if factors else 0.0,
                "note": "interleaved off/on keep-alive rounds on the same "
                        "warm cluster; coalescing = /internal/query-batch "
                        "envelopes + singleflight dedup (net/coalesce.py)",
            },
            "path": f"{DIST_NODES}-node mapReduce fan-out: local device "
                    "shards + coalesced HTTP scatter-gather "
                    "(executor.go:2183 analog; net/coalesce.py); "
                    + _conc_path(DIST_THREADS, DIST_THREADS_PEAK,
                                 per_q_peak is not None)
                    + " via per-request urllib (continuity); sweep and "
                    "A/B use keep-alive clients; baseline is the Go-proxy "
                    "kernel time for the same query shape (fan-out "
                    "overhead metric)",
        }
        # fan-out overhead metric with no numpy equivalent: compare the
        # Go proxy's kernel time for the same 16-shard query shape (the
        # reference pays its own scatter-gather on top) — never a bare 0.0
        _attach_go_ref(out, "dist_count_16shard", per_q)
        out["vs_baseline"] = out.get("vs_go_reference", 0.0)
        return out
    finally:
        for s in servers:
            s.close()


ICI_NODES = int(os.environ.get("PILOSA_BENCH_ICI_NODES", "3"))
ICI_SHARDS = int(os.environ.get("PILOSA_BENCH_ICI_SHARDS", "8"))
ICI_QUERIES = int(os.environ.get("PILOSA_BENCH_ICI_QUERIES", "48"))
ICI_AB_ROUNDS = int(os.environ.get("PILOSA_BENCH_ICI_AB_ROUNDS", "3"))


def bench_ici(tmpdir) -> dict:
    """ICI-native slice-local serving A/B (docs "ICI-native serving"): a
    3-node replica-3 cluster — every node co-resides the full shard set —
    serving the distributed Count and GroupBy workloads with ici-serving
    interleaved on/off. With routing ON the coordinator answers each query
    as ONE local sharded program (zero /internal/query-batch envelopes,
    asserted from the netCoalesce counters); OFF is the coalesced HTTP
    scatter-gather plane. Reported: warm p50/p99 per mode, the RTTs
    removed per query (envelopes the off-path needed), and whether the
    slice-local warm p50 beat the HTTP path's observed 1-RTT floor (the
    best single off-mode sample — the bound BENCH_NOTES_r06 showed warm
    GroupBy parked at). Single closed-loop client: per-query latency is
    the honest RTT comparison, not a queueing artifact."""
    import http.client
    import urllib.request

    from pilosa_tpu.server import Server

    servers = [Server(os.path.join(tmpdir, f"ici{i}"), port=0,
                      replica_n=ICI_NODES, ici_serving="on").open()
               for i in range(ICI_NODES)]
    try:
        uris = [s.uri for s in servers]
        for s in servers:
            s.cluster_hosts = uris
            s.refresh_membership()

        def post(uri, path, body):
            req = urllib.request.Request(uri + path, data=body,
                                         method="POST")
            with urllib.request.urlopen(req, timeout=60) as r:
                return json.loads(r.read())

        post(uris[0], "/index/ici", b"{}")
        post(uris[0], "/index/ici/field/f", b"{}")
        post(uris[0], "/index/ici/field/g", b"{}")
        rng = np.random.default_rng(31)
        n_per = int(SHARD_WIDTH * 0.005)
        sets = {}
        row_ids, col_ids = [], []
        g_rows, g_cols = [], []
        for shard in range(ICI_SHARDS):
            for row in (0, 1):
                cols = (rng.choice(SHARD_WIDTH, size=n_per, replace=False)
                        .astype(np.int64) + shard * SHARD_WIDTH)
                sets[(row, shard)] = cols
                row_ids += [row] * n_per
                col_ids += cols.tolist()
            for row in range(4):
                cols = (rng.choice(SHARD_WIDTH, size=n_per // 2,
                                   replace=False)
                        .astype(np.int64) + shard * SHARD_WIDTH)
                g_rows += [row] * len(cols)
                g_cols += cols.tolist()
        post(uris[0], "/index/ici/field/f/import", json.dumps({
            "rowIDs": row_ids, "columnIDs": col_ids}).encode())
        post(uris[0], "/index/ici/field/g/import", json.dumps({
            "rowIDs": g_rows, "columnIDs": g_cols}).encode())

        count_q = b"Count(Intersect(Row(f=0), Row(f=1)))"
        groupby_q = b"GroupBy(Rows(field=g))"
        expect = sum(np.intersect1d(sets[(0, s)], sets[(1, s)]).size
                     for s in range(ICI_SHARDS))
        out = post(uris[0], "/index/ici/query", count_q)
        assert out["results"][0] == expect, (out, expect)

        ex = servers[0].executor
        coal = ex.coalescer
        host = uris[0].split("//", 1)[1]

        def lat_series(q: bytes, n: int) -> list:
            """Per-query wall seconds over one keep-alive connection."""
            conn = http.client.HTTPConnection(host, timeout=60)
            lats = []
            try:
                for _ in range(n):
                    t0 = time.perf_counter()
                    conn.request("POST", "/index/ici/query", body=q)
                    resp = conn.getresponse()
                    out = json.loads(resp.read())
                    lats.append(time.perf_counter() - t0)
                    assert "results" in out, out
            finally:
                conn.close()
            return sorted(lats)

        def pctl(lats: list, p: float) -> float:
            return lats[min(len(lats) - 1, int(p * len(lats)))]

        # warm both modes: compile caches, residency, coalescer routes
        for mode in ("off", "on"):
            ex.ici_mode = mode
            lat_series(count_q, 4)
            lat_series(groupby_q, 4)

        rounds = []
        floor_off = float("inf")
        for _ in range(ICI_AB_ROUNDS):
            rnd = {}
            for mode in ("off", "on"):
                ex.ici_mode = mode
                snap0 = coal.snapshot() if coal is not None else {}
                local0 = ex.ici_slice_local
                for name, q in (("count", count_q), ("groupby", groupby_q)):
                    lats = lat_series(q, ICI_QUERIES)
                    rnd[f"{name}_p50_ms_{mode}"] = round(
                        pctl(lats, 0.5) * 1e3, 3)
                    rnd[f"{name}_p99_ms_{mode}"] = round(
                        pctl(lats, 0.99) * 1e3, 3)
                    if mode == "off":
                        floor_off = min(floor_off, lats[0])
                snap1 = coal.snapshot() if coal is not None else {}
                env = (snap1.get("batches", 0) - snap0.get("batches", 0)
                       + snap1.get("fallback_queries", 0)
                       - snap0.get("fallback_queries", 0))
                rnd[f"envelopes_{mode}"] = env
                if mode == "on":
                    rnd["slice_local"] = ex.ici_slice_local - local0
            rnd["count_speedup"] = (
                round(rnd["count_p50_ms_off"] / rnd["count_p50_ms_on"], 2)
                if rnd["count_p50_ms_on"] else 0.0)
            rnd["groupby_speedup"] = (
                round(rnd["groupby_p50_ms_off"]
                      / rnd["groupby_p50_ms_on"], 2)
                if rnd["groupby_p50_ms_on"] else 0.0)
            rounds.append(rnd)
        ex.ici_mode = "on"
        n_q = 2 * ICI_QUERIES  # count + groupby per mode per round
        env_off = sum(r["envelopes_off"] for r in rounds)
        env_on = sum(r["envelopes_on"] for r in rounds)
        speedups = sorted(r["count_speedup"] for r in rounds)
        g_speedups = sorted(r["groupby_speedup"] for r in rounds)
        p50_on = sorted(r["count_p50_ms_on"] for r in rounds)[
            len(rounds) // 2]
        out = {
            "metric": f"ici_slice_local_count_p50_speedup_{ICI_NODES}node",
            "value": speedups[len(speedups) // 2],
            "unit": "x vs http scatter-gather",
            "rounds": rounds,
            "median_count_speedup": speedups[len(speedups) // 2],
            "median_groupby_speedup": g_speedups[len(g_speedups) // 2],
            "envelopes_per_query_off": round(
                env_off / (len(rounds) * n_q), 3),
            "envelopes_per_query_on": round(
                env_on / (len(rounds) * n_q), 3),
            "rtts_removed_per_query": round(
                (env_off - env_on) / (len(rounds) * n_q), 3),
            "http_1rtt_floor_ms": round(floor_off * 1e3, 3),
            "slice_local_warm_p50_ms": p50_on,
            "slice_local_below_http_floor": bool(
                p50_on < floor_off * 1e3),
            "path": f"{ICI_NODES}-node replica-{ICI_NODES} cluster, every "
                    "shard co-resident on the coordinator: ici-serving=on "
                    "answers as ONE local sharded program (zero internal "
                    "envelopes), off rides the coalesced HTTP plane; "
                    "interleaved keep-alive single-client rounds",
        }
        if env_on != 0:
            out["note"] = ("WARNING: slice-local rounds produced internal "
                           "envelopes — routing did not fully engage")
        out["vs_baseline"] = out["value"]
        return out
    finally:
        for s in servers:
            s.close()


ROLLING_CLIENTS = int(os.environ.get("PILOSA_BENCH_ROLLING_CLIENTS", "256"))
ROLLING_STEADY_S = float(os.environ.get("PILOSA_BENCH_ROLLING_STEADY_S",
                                        "3.0"))
ROLLING_SHARDS = int(os.environ.get("PILOSA_BENCH_ROLLING_SHARDS", "6"))


def bench_rolling_restart(tmpdir) -> dict:
    """Zero-downtime operations acceptance: restart all 3 nodes of a
    replica-2 cluster IN SEQUENCE (graceful drain → process-close →
    rejoin with hint replay + read fence) under a 256-client mixed
    read/write keep-alive load. Criteria: ZERO failed well-formed
    requests (clients fail over across replicas, exactly as the drain's
    503 + X-Pilosa-Shed-Reason tells them to), ZERO acked-write loss
    (every acked Set present on every owning replica afterward), and the
    p99 delta of the restart window vs steady state as the headline."""
    import http.client
    import threading

    from pilosa_tpu.constants import SHARD_WIDTH as SW
    from pilosa_tpu.server import Server

    servers = [Server(os.path.join(tmpdir, f"rr{i}"), port=0,
                      replica_n=2).open() for i in range(3)]
    uris = [s.uri for s in servers]
    ports = [s.http.port for s in servers]
    for s in servers:
        s.cluster_hosts = uris
        s.refresh_membership()
    hosts = [u.split("//", 1)[1] for u in uris]
    _local = threading.local()

    def post(path, body, prefer):
        """One request with replica failover: try every node starting at
        `prefer`, two passes (the restart window can race a socket
        teardown). Returns (status, body) of the first 200, or the last
        answer. Connection-level failures move on like 5xx rejections."""
        last = (0, b"")
        for attempt in range(2 * len(hosts)):
            hp = hosts[(prefer + attempt) % len(hosts)]
            conns = getattr(_local, "conns", None)
            if conns is None:
                conns = _local.conns = {}
            conn = conns.get(hp)
            try:
                if conn is None:
                    conn = conns[hp] = http.client.HTTPConnection(
                        hp, timeout=60)
                conn.request("POST", path, body=body)
                resp = conn.getresponse()
                out = resp.read()
            except (http.client.HTTPException, OSError):
                c = conns.pop(hp, None)
                if c is not None:
                    c.close()
                # one in-place reconnect for a stale keep-alive, then on
                # to the next replica
                try:
                    conn = conns[hp] = http.client.HTTPConnection(
                        hp, timeout=60)
                    conn.request("POST", path, body=body)
                    resp = conn.getresponse()
                    out = resp.read()
                except (http.client.HTTPException, OSError):
                    conns.pop(hp, None)
                    last = (0, b"connection failed")
                    continue
            if resp.status == 200:
                return 200, out
            last = (resp.status, out)
            if resp.will_close:
                conns.pop(hp, None)
                conn.close()
        return last

    st, _ = post("/index/rr", b"{}", 0)
    assert st == 200
    st, _ = post("/index/rr/field/f", b"{}", 0)
    assert st == 200
    rng = np.random.default_rng(47)
    row_ids, col_ids = [], []
    for shard in range(ROLLING_SHARDS):
        cols = (rng.choice(SW, size=int(SW * 0.002), replace=False)
                .astype(np.int64) + shard * SW)
        row_ids += [1] * len(cols)
        col_ids += cols.tolist()
    st, _ = post("/index/rr/field/f/import", json.dumps(
        {"rowIDs": row_ids, "columnIDs": col_ids}).encode(), 0)
    assert st == 200
    read_q = b"Count(Row(f=1))"
    for _ in range(5):
        post("/index/rr/query", read_q, 0)  # warm residency + compile

    stop = threading.Event()
    phase = {"name": "steady"}
    lat_lock = threading.Lock()
    lats = {"steady": [], "restart": []}
    failed: list = []
    acked: list[int] = []
    wcount = [0]

    def client(tid):
        my_acked, my_ops = [], 0
        while not stop.is_set():
            my_ops += 1
            # a quarter of the clients alternate Set/Count; the rest read
            is_write = tid % 4 == 0 and my_ops % 2 == 0
            if is_write:
                with lat_lock:
                    wcount[0] += 1
                    wid = wcount[0]
                col = (wid % ROLLING_SHARDS) * SW + 300_000 + wid
                body = f"Set({col}, f=9)".encode()
            else:
                body = read_q
            t0 = time.perf_counter()
            st, out = post("/index/rr/query", body, tid % len(hosts))
            ms = (time.perf_counter() - t0) * 1e3
            ph = phase["name"]
            with lat_lock:
                lats[ph].append(ms)
            if st != 200:
                with lat_lock:
                    failed.append((ph, st,
                                   out[:120].decode(errors="replace")))
            elif is_write:
                my_acked.append(col)
        with lat_lock:
            acked.extend(my_acked)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(ROLLING_CLIENTS)]
    for t in threads:
        t.start()
    time.sleep(ROLLING_STEADY_S)  # steady-state window

    phase["name"] = "restart"
    t_restart = time.perf_counter()
    for i in range(3):
        post("/cluster/drain", b"{}", i)  # lands on node i (prefer=i)
        deadline = time.monotonic() + 30
        while not servers[i].drained and time.monotonic() < deadline:
            time.sleep(0.02)
        servers[i].close()
        time.sleep(0.3)  # the window writes must survive via hints
        s = Server(os.path.join(tmpdir, f"rr{i}"), port=ports[i],
                   replica_n=2)
        s.cluster_hosts = uris
        s.open()
        servers[i] = s
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if (s.executor.fence_snapshot()["fencedShards"] == 0
                    and all(not o.cluster.is_unavailable(s.node_id)
                            for o in servers if o is not s)):
                break
            time.sleep(0.05)
    restart_wall = time.perf_counter() - t_restart
    phase["name"] = "steady2"
    lats["steady2"] = []
    time.sleep(1.0)
    stop.set()
    for t in threads:
        t.join(timeout=10)

    # settle: retry any pending hint replays, then check every acked
    # write on every owning replica
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        for s in servers:
            s._retry_pending_hints()
        if all(not s.hints.snapshot()["pendingBytes"] for s in servers):
            break
        time.sleep(0.2)
    lost = 0
    for s in servers:
        idx = s.holder.index("rr")
        v = idx.field("f").view("standard") if idx else None
        for col in acked:
            shard = col // SW
            if not s.cluster.owns_shard(s.node_id, "rr", shard):
                continue
            frag = v.fragment(shard) if v else None
            if frag is None or not frag.contains(9, col % SW):
                lost += 1
    for s in servers:
        s.close()

    def p99(xs):
        return round(sorted(xs)[int(0.99 * (len(xs) - 1))], 2) if xs \
            else 0.0

    p99_steady = p99(lats["steady"])
    p99_restart = p99(lats["restart"])
    delta_pct = round(100.0 * (p99_restart / p99_steady - 1.0), 1) \
        if p99_steady else 0.0
    return {
        "metric": "rolling_restart_failed_requests",
        "value": float(len(failed)),
        "unit": "failed requests (criterion: 0) across a full 3-node "
                f"rolling restart under {ROLLING_CLIENTS} mixed clients",
        "acked_write_loss": lost,
        "acked_writes": len(acked),
        "requests_steady": len(lats["steady"]),
        "requests_during_restart": len(lats["restart"]),
        "p99_steady_ms": p99_steady,
        "p99_restart_ms": p99_restart,
        "p99_delta_pct": delta_pct,
        "restart_wall_s": round(restart_wall, 2),
        "failures_sample": failed[:5],
        "vs_baseline": 0.0,
        "path": "3-node replica-2 cluster; per node: POST /cluster/drain "
                "→ wait drained → close → reopen same port → wait fence "
                "lift + peer rejoin; clients fail over across replicas "
                "on 503-draining/connection errors (the documented "
                "client contract); acked Sets verified present on every "
                "owning replica after hint replay",
    }


def worker() -> None:
    """Full measurement (runs in a subprocess; may hang — parent enforces
    the deadline). Prints the final JSON line on success."""
    import shutil
    import tempfile

    from pilosa_tpu.parallel.mesh import configure_compile_cache

    configure_compile_cache()  # before backend init
    deadline = time.monotonic() + DEADLINE_S * 0.9
    devices = _init_backend_with_retry(deadline)
    if devices[0].platform != (PLATFORM or "tpu"):
        raise SystemExit(
            f"[bench] backend is {devices[0].platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}), not "
            f"{PLATFORM or 'tpu'!r} — refusing to measure")

    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models import Holder

    metrics = []
    try:  # fresh checkpoint per worker run
        os.makedirs(os.path.dirname(CKPT_PATH), exist_ok=True)
        with open(CKPT_PATH, "w") as f:
            f.write(json.dumps({
                "ckpt_start": True, "device": str(devices[0]),
                "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                             time.gmtime())}) + "\n")
    except OSError as e:  # pragma: no cover
        print(f"[bench] checkpoint disabled: {e}", file=sys.stderr)

    def record(m):
        metrics.append(m)
        try:
            with open(CKPT_PATH, "a") as f:
                f.write(json.dumps(m) + "\n")
                f.flush()
                os.fsync(f.fileno())
        except OSError:
            pass

    def stage(name, fn, *a):
        if STAGES and name not in STAGES:
            return
        t0 = time.perf_counter()
        try:
            m = fn(*a)
        except Exception as e:  # noqa: BLE001 — keep measuring so one
            # run names EVERY broken stage; the worker still exits non-zero
            record({"metric": f"{name}_error", "value": 0.0,
                    "unit": "error", "vs_baseline": 0.0,
                    "error": f"{type(e).__name__}: {e}"[:300]})
            print(f"[bench] {name} FAILED: {e}", file=sys.stderr)
            return
        m["stage_s"] = round(time.perf_counter() - t0, 1)
        record(m)
        print(f"[bench] {name}: {m['value']} {m['unit']} "
              f"(x{m['vs_baseline']} vs cpu, {m['stage_s']}s)",
              file=sys.stderr)

    stage("kernel", bench_kernel)
    stage("kernels", bench_kernels)

    tmp = tempfile.mkdtemp(prefix="pilosa-bench-")
    try:
        holder = Holder(tmp).open()
        ex = Executor(holder)

        def staged(name, build, bench):
            """Index build + measurement under one fault barrier: a build
            failure must cost only its own stage, like a bench failure."""
            if STAGES and name not in STAGES:
                return
            try:
                args = build()
            except Exception as e:  # noqa: BLE001
                record({"metric": f"{name}_error", "value": 0.0,
                        "unit": "error", "vs_baseline": 0.0,
                        "error": f"build: {type(e).__name__}: {e}"[:300]})
                print(f"[bench] {name} build FAILED: {e}", file=sys.stderr)
                return
            stage(name, bench, *args)

        def topn_build():
            build_topn_index(holder)
            return (ex,)

        staged("executor", lambda: (ex, build_exec_index(holder)),
               bench_executor)
        staged("topn", topn_build, bench_topn)
        staged("groupby", lambda: (ex, build_groupby_index(holder)),
               bench_groupby)
        staged("bsi", lambda: (ex, build_bsi_index(holder)), bench_bsi)
        holder.close()
        stage("http", bench_http, tmp)
        stage("profiler", bench_profiler, tmp)
        stage("telemetry", bench_telemetry, tmp)
        stage("accounting", bench_accounting, tmp)
        stage("events", bench_events, tmp)
        stage("heat", bench_heat, tmp)
        stage("qos", bench_qos, tmp)
        stage("planner", bench_planner, tmp)
        stage("hybrid", bench_hybrid, tmp)
        stage("distributed", bench_distributed, tmp)
        stage("ici", bench_ici, tmp)
        stage("rolling_restart", bench_rolling_restart, tmp)
        stage("ingest", bench_ingest, tmp)
        stage("device_obs", bench_device_obs, tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    failed = [m for m in metrics if m["metric"].endswith("_error")]
    if failed:
        raise SystemExit(
            "[bench] stages failed: "
            + "; ".join(f"{m['metric']}: {m['error']}" for m in failed))
    # a stage filter that skips the headline stage headlines the first
    # stage it did run; an empty run is a failure, never a 0.0
    head = next((m for m in metrics if m["metric"] == METRIC),
                metrics[0] if metrics else None)
    if head is None:
        raise SystemExit("[bench] no stage ran (PILOSA_BENCH_STAGES="
                         f"{','.join(STAGES)!r})")
    result = dict(head)
    result["detail"] = {
        "device": str(devices[0]),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "metrics": metrics,
    }
    if PLATFORM:
        result["rehearsal"] = PLATFORM
    print(json.dumps(result))


def _probe_backend(timeout_s: float):
    """(ok, error_string, platform): can jax.devices() return, within
    timeout_s? Cheap subprocess — avoids burning the full worker on a
    backend that never initialises. `platform` is the probed backend name
    ("tpu"/"cpu"/...) when ok, "" otherwise — main() refuses anything but
    a TPU (or the explicit rehearsal platform)."""
    code = (
        "import jax\n"
        + (f"jax.config.update('jax_platforms', {PLATFORM!r})\n" if PLATFORM
           else "")
        + "d = jax.devices(); print(d[0].platform)")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], timeout=timeout_s,
            capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        return False, "BackendInitTimeout: jax.devices() did not return", ""
    if proc.returncode == 0:
        out_lines = (proc.stdout or "").strip().splitlines()
        return True, "", (out_lines[-1].strip() if out_lines else "unknown")
    tail = (proc.stderr or "").strip().splitlines()
    return False, "BackendInitError: " + (tail[-1][:300] if tail else
                                          f"rc={proc.returncode}"), ""


# ---------------------------------------------------------------------------
# Machine-readable bench artifact + regression compare
# ---------------------------------------------------------------------------

BENCH_ROUND = os.environ.get("PILOSA_BENCH_ROUND", "r08")
ARTIFACT_PATH = os.environ.get("PILOSA_BENCH_ARTIFACT") or os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    f"BENCH_{BENCH_ROUND}.json")

# stage acceptance criteria (metric regex -> check): the prose "budget
# <= 1%" notes, machine-readable so the artifact can say pass/fail
_CRITERIA = [
    (r"^profiler_overhead_pct$",
     lambda m: (m["value"] <= 5.0, "median overhead <= 5%")),
    (r"^telemetry_overhead_pct$",
     lambda m: (m["value"] <= 1.0, "median overhead <= 1%")),
    (r"^accounting_overhead_pct$",
     lambda m: (m["value"] <= 1.0, "median overhead <= 1%")),
    (r"^events_overhead_pct$",
     lambda m: (m["value"] <= 1.0, "median overhead <= 1%")),
    (r"^heat_overhead_pct$",
     lambda m: (m["value"] <= 1.0, "median overhead <= 1%")),
    (r"^qos_p99_delta_pct$",
     lambda m: (m["value"] <= 15.0, "well-behaved p99 delta <= 15%")),
    (r"^planner_dashboard_speedup$",
     lambda m: (m["value"] >= 1.3, "cache-on p50 speedup >= 1.3x")),
    (r"^ici_slice_local_count_p50_speedup",
     lambda m: (m["value"] >= 1.0, "slice-local no slower than HTTP")),
    (r"^rolling_restart_failed_requests$",
     lambda m: (m["value"] == 0 and not m.get("acked_write_loss"),
                "0 failed requests and 0 lost acked writes")),
    (r"^hybrid_capacity_ratio$",
     lambda m: (m["value"] >= 4.0 and m["dense_overhead_pct"] <= 15.0,
                ">= 4x resident sparse rows at equal HBM budget AND "
                "dense headline within the 15% gate with hybrid on")),
    (r"^kernels_run_vs_dense_count_speedup$",
     lambda m: (m["value"] >= 1.0 and m["run_capacity_ratio"] >= 4.0,
                "run-by-run count no slower than dense on the same "
                "logical row AND run leaf >= 4x smaller than its dense "
                "plane (the runny-regime win)")),
    (r"^ingest_sets_per_s$",
     lambda m: (m["value"] >= 100_000.0
                and m["read_p50_delta_pct"] <= 15.0
                and m["fsync_reduction_x"] >= 10.0
                and not m["write_errors"],
                ">= 100k acked mutations/s concurrent with serving, "
                "warm read p50 delta <= 15%, WAL group-commit >= 10x "
                "fewer appends than per-bit, 0 write errors")),
]

# headline stages for `--compare` and the regression direction of their
# `value` ("lower" = a latency, "higher" = a rate/speedup); the warm-p50
# regression gate applies to whichever of these both artifacts carry
_HEADLINE_COMPARE = [
    (r"^kernel_intersect_count_qps", "higher"),
    (r"^executor_intersect_count_qps", "higher"),
    (r"^topn1000_p50_ms$", "lower"),
    (r"^groupby_\d+x\d+_p50_ms$", "lower"),
    (r"^bsi_range_sum_p50_ms$", "lower"),
    (r"^http_count_qps$", "higher"),
    (r"^distributed_count_qps_16shard", "higher"),
    (r"^hybrid_capacity_ratio$", "higher"),
    (r"^kernels_run_vs_dense_count_speedup$", "higher"),
    (r"^ingest_sets_per_s$", "higher"),
]

COMPARE_REGRESSION_PCT = float(os.environ.get(
    "PILOSA_BENCH_COMPARE_PCT", "15"))


def _stage_entry(m: dict) -> dict:
    """Normalize one stage's metric dict for the artifact: headline
    value/unit, every cold/warm/p50/p99 latency field it reported,
    criterion verdict when one applies, and the raw dict for everything
    else."""
    import re as _re

    entry = {"value": m.get("value"), "unit": m.get("unit", "")}
    lat = {k: v for k, v in m.items()
           if isinstance(v, (int, float))
           and _re.search(r"p50|p99|cold|warm", k)}
    if lat:
        entry["latency"] = lat
    for pat, check in _CRITERIA:
        if _re.match(pat, m.get("metric", "")):
            try:
                ok, text = check(m)
            except (KeyError, TypeError):
                ok, text = False, "criterion inputs missing"
            entry["criterion"] = {"pass": bool(ok), "text": text}
            break
    entry["raw"] = m
    return entry


def _write_bench_artifact(result: dict) -> None:
    """BENCH_<round>.json: the machine-readable bench trajectory record —
    stage -> value/latency/criterion, with the device it ran on. Written
    by the PARENT, only for a live on-chip run. Never raises: a broken
    artifact write must not fail the bench run."""
    try:
        detail = result.get("detail") or {}
        metrics = [m for m in (detail.get("metrics") or [])
                   if isinstance(m, dict) and m.get("metric")]
        stages = {m["metric"]: _stage_entry(m) for m in metrics}
        criteria = {name: e["criterion"] for name, e in stages.items()
                    if "criterion" in e}
        art = {
            "schema": "pilosa-tpu-bench/v1",
            "round": BENCH_ROUND,
            "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime()),
            "headline": {k: result.get(k) for k in
                         ("metric", "value", "unit", "vs_baseline")},
            "provenance": {
                k: detail.get(k) for k in
                ("device", "platform", "device_kind", "device_count")},
            "criteria": {
                "pass": all(c["pass"] for c in criteria.values()),
                "stages": criteria,
            },
            "stages": stages,
        }
        with open(ARTIFACT_PATH, "w") as f:
            json.dump(art, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[bench] artifact: {ARTIFACT_PATH} ({len(stages)} stages, "
              f"criteria {'PASS' if art['criteria']['pass'] else 'FAIL'})",
              file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — artifact is best-effort
        print(f"[bench] artifact write failed: {e}", file=sys.stderr)


def compare_artifacts(new: dict, prior: dict,
                      threshold_pct: float = COMPARE_REGRESSION_PCT
                      ) -> tuple[bool, list[str]]:
    """Regression gate between two BENCH_*.json artifacts: for every
    headline stage present in BOTH, a warm-p50-equivalent move worse
    than threshold_pct (latency up / rate down) is a regression.
    Returns (regressed, report lines)."""
    import re as _re

    lines: list[str] = []
    regressed = False
    new_stages = new.get("stages") or {}
    old_stages = prior.get("stages") or {}
    for pat, direction in _HEADLINE_COMPARE:
        for name, entry in sorted(new_stages.items()):
            if not _re.match(pat, name):
                continue
            old = old_stages.get(name)
            nv, ov = entry.get("value"), (old or {}).get("value")
            if not old or not nv or not ov:
                lines.append(f"  skip {name}: missing from one side")
                continue
            if direction == "lower":
                delta_pct = 100.0 * (nv / ov - 1.0)
            else:
                delta_pct = 100.0 * (ov / nv - 1.0)
            verdict = "ok"
            if delta_pct > threshold_pct:
                verdict = "REGRESSION"
                regressed = True
            lines.append(
                f"  {verdict:>10} {name}: {ov} -> {nv} "
                f"({'+' if delta_pct >= 0 else ''}{delta_pct:.1f}% "
                f"{'slower' if direction == 'lower' else 'rate change'}"
                f", gate {threshold_pct:.0f}%)")
    return regressed, lines


def _maybe_compare() -> None:
    """`--compare <prior.json>`: gate the artifact just written against
    a prior round's; exit 1 on any headline warm-p50 regression."""
    if "--compare" not in sys.argv:
        return
    prior_path = sys.argv[sys.argv.index("--compare") + 1]
    try:
        with open(ARTIFACT_PATH) as f:
            new = json.load(f)
        with open(prior_path) as f:
            prior = json.load(f)
    except (OSError, ValueError) as e:
        print(f"[bench] compare failed: {e}", file=sys.stderr)
        sys.exit(1)
    regressed, lines = compare_artifacts(new, prior)
    print(f"[bench] compare vs {prior_path} "
          f"(gate {COMPARE_REGRESSION_PCT:.0f}% on headline warm p50):",
          file=sys.stderr)
    for line in lines:
        print(line, file=sys.stderr)
    if regressed:
        print("[bench] REGRESSION detected — failing", file=sys.stderr)
        sys.exit(1)


def main() -> None:
    if "--worker" in sys.argv:
        worker()
        return

    # a CPU-backend number is not an on-chip capture: anything but a live
    # TPU measurement exits non-zero and prints no result line (the
    # explicit PILOSA_BENCH_PLATFORM rehearsal excepted — it says so)
    want = PLATFORM or "tpu"
    try:
        os.remove(CKPT_PATH)  # drop the prior run's progress log
    except OSError:
        pass
    t_end = time.monotonic() + DEADLINE_S
    last_err = "unknown"
    attempt = 0
    same_err_count = 0
    while time.monotonic() < t_end - 45:
        attempt += 1
        probe_budget = min(PROBE_TIMEOUT_S, t_end - time.monotonic() - 50)
        if probe_budget <= 5:
            break
        ok, err, platform = _probe_backend(probe_budget)
        if not ok:
            same_err_count = same_err_count + 1 if err == last_err else 1
            last_err = err
            print(f"[bench] probe attempt {attempt} failed ({err}); "
                  "backing off", file=sys.stderr)
            if same_err_count >= 3 and err.startswith("BackendInitError"):
                break  # deterministic crash — retrying won't help
            time.sleep(min(15, max(0, t_end - time.monotonic() - 45)))
            continue
        if platform != want:
            print(f"[bench] backend is {platform!r} (JAX_PLATFORMS="
                  f"{os.environ.get('JAX_PLATFORMS')!r}), not {want!r} — "
                  "refusing to measure", file=sys.stderr)
            sys.exit(3)
        budget = t_end - time.monotonic() - 45
        if budget <= 30:
            break
        try:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--worker"],
                timeout=budget, capture_output=True, text=True,
                cwd=os.path.dirname(os.path.abspath(__file__)))
        except subprocess.TimeoutExpired:
            last_err = f"WorkerTimeout: measurement exceeded {budget:.0f}s"
            break
        sys.stderr.write(proc.stderr[-3000:])
        lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
        if proc.returncode == 0 and lines:
            try:
                result = json.loads(lines[-1])
            except ValueError:
                last_err = f"WorkerBadOutput: {lines[-1][:200]}"
                break
            print(lines[-1])
            if not PLATFORM:
                _write_bench_artifact(result)
                _maybe_compare()
            return
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        last_err = f"WorkerFailed(rc={proc.returncode}): " + \
            (tail[-1][:300] if tail else "no output")
        break  # a worker that ran and failed is a result, not a flake
    print(f"[bench] no live measurement on {want!r} completed ({last_err})",
          file=sys.stderr)
    sys.exit(3)


if __name__ == "__main__":
    main()
