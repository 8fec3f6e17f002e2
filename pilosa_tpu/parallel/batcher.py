"""Continuous batching of concurrent device queries into single dispatches.

The dominant serving workloads — Count over a 1- or 2-leaf bitmap program
(executor.go:1521 executeCount of Row/Intersect/Union/...) and BSI plane
aggregations (executor.go:363 executeSum) — dispatch one tiny device
program per query. Each dispatch pays fixed launch overhead, so concurrent
serving throughput is launch-bound long before the chip is busy.

This is the TPU answer to the reference's goroutine-per-shard fan-out
(executor.go:2283): instead of more host threads, coalesce the queries
themselves. A leader thread grabs every compatible pending query, runs ONE
kernel computing all K results, and distributes them. Batches form *while
the previous dispatch executes* — continuous batching: a lone query pays
at most one admission tick (~0.5 ms, see _ADMISSION_S), and under
concurrency the batch size adapts to the arrival rate.

Leadership protocol (shared by all batchers): the first arrival for a
compatibility key becomes leader and serves exactly ONE batch — its own
request is the queue head — then promotes the next queued request to
leader (or releases leadership if the queue drained). One batch per leader
keeps tail latency fair: no thread serves strangers after its own query is
answered. Errors wake every waiter in the failed batch.

Pipelining: a batch's life is dispatch (enqueue the program on the device)
then finalize (a blocking device→host fetch of the results). Leadership
hands off BEFORE dispatch: the moment a leader cuts its batch from the
queue, the next queued request is promoted, so batch N+1's admission
window and dispatch overlap batch N's execution and result fetch.
Serializing them would cap the dispatch rate at one batch per
dispatch-plus-fetch regardless of chip speed; with overlap, throughput is
arrival-bound. A short admission window (see _ADMISSION_S) aggregates the
resubmit burst that follows each delivered batch into one dispatch.
In-flight depth is naturally bounded by the client thread count — every
finalize runs on the thread that led that batch. Subclasses implement
_dispatch/_finalize (or legacy one-shot _compute, which degrades to
dispatch-and-fetch in one step).
"""

from __future__ import annotations

import functools
import os
import threading
import time
from collections import defaultdict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu import qos
from pilosa_tpu.ops.bitvector import popcount
from pilosa_tpu.utils import accounting, tracing
from pilosa_tpu.utils import profile as qprofile
from pilosa_tpu.utils.telemetry import counted_jit

MAX_BATCH = 512
_LEGACY = object()  # _dispatch sentinel: subclass only implements _compute
_FAILED = object()  # dispatch raised; error already delivered to the batch
# follower wait poll: bounds the hang window if a leader thread dies for a
# non-exception reason (interpreter teardown, thread kill) — followers
# re-check leader liveness and reclaim leadership
_WAIT_POLL_S = 5.0
# admission window ceiling (seconds): how long a new leader will wait for
# the post-finalize resubmit burst to land before cutting its batch. The
# loop exits early on an arrival lull, so a lone query pays one ~0.5 ms
# tick, not the full window. 0 disables (cut immediately).
_ADMISSION_S = float(os.environ.get("PILOSA_TPU_BATCH_WINDOW_MS", "4")) / 1e3

# shard chunk for device-side partial count reductions: each chunk's total
# is < 2016 shards x 2^20 bits < 2^31, so int32 partials cannot wrap; the
# host finishes the reduction in int64 (the exactness invariant of the
# ops/bitvector.py "Numeric protocol", shared with the BSI batchers below)
_SUM_SHARD_CHUNK = 2016

_OPS = {
    "and": jnp.bitwise_and,
    "or": jnp.bitwise_or,
    "xor": jnp.bitwise_xor,
    "andnot": lambda a, b: jnp.bitwise_and(a, jnp.bitwise_not(b)),
    "id": lambda a, b: a,
}


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


class _Req:
    __slots__ = ("payload", "event", "result", "exc", "promoted", "done",
                 "server", "profile", "account", "t_submit", "priority")

    def __init__(self, payload):
        self.payload = payload
        self.t_submit = time.perf_counter()  # queue-wait telemetry anchor
        # the submitter's QoS priority level (pilosa_tpu/qos.py): when the
        # queue exceeds one batch, the cut is ordered by this — batch
        # traffic waits out interactive traffic instead of starving it
        self.priority = qos.current_level()
        self.event = threading.Event()
        self.result = None
        self.exc: Optional[BaseException] = None
        self.promoted = False  # woken to take over leadership, not served
        self.done = False  # result/exc actually delivered (event alone is
        # ambiguous: promotion also sets it)
        # the submitting query's QueryProfile (or None): dispatch
        # attribution must be recorded against the SUBMITTER — the batch
        # is served on a leader thread belonging to a different query
        self.profile = qprofile.current_profile.get()
        # likewise the submitter's usage account (utils/accounting.py):
        # the dispatch share is charged to whoever submitted the query,
        # not to the stranger whose thread led the batch
        self.account = accounting.current_account.get()
        self.server: Optional[threading.Thread] = None  # thread serving the
        # batch this request was popped into (set at the cut; liveness
        # checks must consult it, not the leadership slot — leadership
        # hands off at the cut, BEFORE dispatch, while this batch's
        # dispatch and finalize are still in flight on this thread)


class ContinuousBatcher:
    """Leadership/queue machinery; subclasses implement _compute."""

    # whether a dispatch's wall-time share is DEVICE time for accounting:
    # True for the device batchers; NodeCoalescer overrides to False (its
    # "dispatch" is an HTTP envelope — the waiters charge RPC bytes
    # instead, and double-charging network wall as device-ms would break
    # the per-principal device attribution admission control acts on)
    ACCOUNT_DEVICE_MS = True

    # kernel family this batcher's queue wait is attributed to in the
    # KernelStats dispatch-vs-wait split (utils/telemetry.py; must be a
    # registered family, constants.KERNEL_FAMILY_REPS). None = the
    # batches are not device dispatches (NodeCoalescer's HTTP envelopes)
    KERNEL_FAMILY: Optional[str] = "batcher"

    # whether leadership hands off at the CUT (before dispatch) or after
    # the batch completes. At-cut is right for read dispatches: the next
    # leader's admission overlaps this batch's device round trip. The
    # write-side IngestBatcher overrides to False — group commit only
    # coalesces if arrivals ACCUMULATE while the in-flight apply runs;
    # handing off at the cut would let every arrival lead its own
    # singleton batch concurrently and no batch would ever exceed one
    # payload (one fsync per client write, the exact cost the batcher
    # exists to amortize)
    HANDOFF_AT_CUT = True

    def __init__(self, max_batch: int = MAX_BATCH, runner=None):
        self.max_batch = max_batch
        self.runner = runner  # the device batchers' DeviceRunner, if any
        self.admission_s = _ADMISSION_S
        self._lock = threading.Lock()
        self._pending: dict[tuple, list[_Req]] = defaultdict(list)
        self._leaders: set[tuple] = set()
        self._leader_threads: dict[tuple, threading.Thread] = {}
        # observability (surfaced via /debug/vars through executor stats;
        # the telemetry sampler derives per-window queue depth and wait
        # rates from the cumulative wait totals)
        self.batches = 0
        self.batched_queries = 0
        self.max_batch_seen = 0
        self.wait_ms_total = 0.0  # submit -> result delivery, cumulative
        self.waited = 0  # requests the wait total covers

    def submit(self, key: tuple, payload):
        """Enqueue one query under compatibility `key`; blocks until a
        batch containing it executes; returns its result. The wait is
        the `<family>.wait` span (`batcher.wait` for the device read
        batchers): submit to delivery, the interval _run books as
        wait_ms_total; a leader's own launch and fetch nest under it."""
        if self.KERNEL_FAMILY is None:
            return self._submit(key, payload)
        with tracing.span(self.KERNEL_FAMILY + ".wait"):
            return self._submit(key, payload)

    def _submit(self, key: tuple, payload):
        req = _Req(payload)
        with self._lock:
            self._pending[key].append(req)
            lead = key not in self._leaders
            if lead:
                self._leaders.add(key)
                self._leader_threads[key] = threading.current_thread()
        if not lead:
            # bounded wait: poll leader liveness so a leader thread that
            # dies without raising (interpreter teardown, thread kill)
            # hangs followers for at most _WAIT_POLL_S before reclaim
            while not req.event.wait(_WAIT_POLL_S):
                with self._lock:
                    if req.done:
                        break  # delivered in the wait-timeout window
                    if req in self._pending.get(key, ()):
                        t = self._leader_threads.get(key)
                        if t is not None and t.is_alive():
                            continue  # leader healthy (maybe mid-dispatch)
                        # dead leader, our request still queued: take over
                        self._leaders.add(key)
                        self._leader_threads[key] = threading.current_thread()
                        req.promoted = True
                        req.event.set()
                    else:
                        # popped into a batch: its dispatch/results may
                        # still be in flight on the SERVING thread
                        # (leadership already handed off at the cut) —
                        # only that thread dying means the result is
                        # never coming
                        t = req.server
                        if t is not None and t.is_alive():
                            continue  # finalize in flight
                        req.exc = RuntimeError(
                            "batch leader died mid-compute")
                        req.event.set()
            if not req.promoted:
                if req.exc is not None:
                    raise req.exc
                return req.result
            # promoted: the previous leader finished its batch with this
            # request still queued — take over and serve the next batch
            # (which normally contains this request)
        self._serve_one_batch(key)
        # serving one batch usually delivers our own request (it was the
        # queue head), but not always: a reclaim behind a >max_batch
        # backlog serves the first max_batch strangers, and a double-
        # promote race can leave our request inside ANOTHER leader's
        # in-flight batch. Keep serving while it is queued; poll while it
        # is in someone else's hands (rare paths — see test_batcher).
        while not req.done:
            with self._lock:
                in_q = req in self._pending.get(key, ())
            if in_q:
                self._serve_one_batch(key)
                continue
            time.sleep(0.002)
            if req.done:
                break
            with self._lock:
                # in another leader's in-flight batch: that SERVING thread
                # (not the current leadership holder) owes us the result
                t = req.server if req.server is not None \
                    else self._leader_threads.get(key)
                if (t is None or not t.is_alive()) and not req.done:
                    req.exc = RuntimeError("batch leader died mid-compute")
                    break
        if req.exc is not None:
            raise req.exc
        return req.result

    def _serve_one_batch(self, key: tuple) -> None:
        with self._lock:
            self._leader_threads[key] = threading.current_thread()
        # admission window: when a finalize delivers K results, those K
        # clients resubmit near-simultaneously — wait out the burst (until
        # an arrival lull, one sleep tick with no growth) so it lands in
        # ONE dispatch instead of K tiny ones, each paying the fixed
        # dispatch cost. A lone query waits a single tick (~0.5 ms).
        if self.admission_s > 0:
            deadline = time.perf_counter() + self.admission_s
            last = -1
            while True:
                with self._lock:
                    n = len(self._pending.get(key, ()))
                # lull = no growth over one tick; `last` starts at -1 so a
                # lone query still waits exactly one tick, and a leader
                # whose queue was emptied by a concurrent cut (reclaim
                # races) exits after one tick instead of the full window
                if (n >= self.max_batch or n == last
                        or time.perf_counter() >= deadline):
                    break
                last = n
                time.sleep(0.0005)
        with self._lock:
            q = self._pending[key]
            if len(q) > self.max_batch:
                # QoS priority ordering at the cut — ONLY when the queue
                # overflows one batch (inside a batch everyone is served
                # together, so ordering is moot and the common case pays
                # nothing). Stable sort: FIFO within a priority class.
                q.sort(key=lambda r: r.priority)
            batch, q[:] = q[:self.max_batch], q[self.max_batch:]
            for r in batch:  # liveness anchor for followers (see _Req)
                r.server = threading.current_thread()
            # leadership hands off HERE — before dispatch — so the next
            # leader's admission+dispatch overlaps this batch's dispatch
            # AND its blocking result fetch (serializing them caps the
            # dispatch rate and with it the whole serving throughput).
            # Hold-through-apply batchers defer this to the finally below.
            if self.HANDOFF_AT_CUT:
                if q:
                    q[0].promoted = True
                    q[0].event.set()  # leadership stays marked; continue
                else:
                    self._leaders.discard(key)
                    self._leader_threads.pop(key, None)
                    # drop the drained queue entry: id()-based keys (plane
                    # slabs) are unbounded over a server's life, and a
                    # retired slab's key would otherwise linger forever
                    del self._pending[key]
        try:
            handle = _FAILED
            t_cut = time.perf_counter()  # dispatch+finalize wall
            if batch:
                try:
                    handle = self._dispatch(key,
                                            [r.payload for r in batch])
                except BaseException as e:  # noqa: BLE001 — waiters wake
                    self._deliver_exc(batch, e)
            if batch and handle is not _FAILED:
                self._run(key, batch, handle, t_cut)
        finally:
            if not self.HANDOFF_AT_CUT:
                # post-apply handoff: arrivals that queued during the
                # apply are cut as ONE batch by the promoted follower.
                # MUST run on every exit path — this thread stays marked
                # leader through the apply, and since it returns to
                # application code alive, followers' dead-leader reclaim
                # would never fire: skipping this release deadlocks them.
                with self._lock:
                    q = self._pending.get(key)
                    if q:
                        q[0].promoted = True
                        q[0].event.set()
                    else:
                        self._leaders.discard(key)
                        self._leader_threads.pop(key, None)
                        if q is not None:
                            del self._pending[key]

    def _run(self, key: tuple, batch: list[_Req], handle,
             t_cut: Optional[float] = None) -> None:
        try:
            results = self._finalize(key, handle,
                                     [r.payload for r in batch])
            if len(results) != len(batch):
                # a length bug must surface as an exception delivered to
                # EVERY waiter, not leave the unpaired ones blocked forever
                raise RuntimeError(
                    f"batcher _compute returned {len(results)} results "
                    f"for {len(batch)} payloads (key={key[:1]})")
            t_done = time.perf_counter()
            batch_wait_ms = sum(
                (t_done - r.t_submit) * 1e3 for r in batch)
            with self._lock:
                self.batches += 1
                self.batched_queries += len(batch)
                self.max_batch_seen = max(self.max_batch_seen, len(batch))
                self.wait_ms_total += batch_wait_ms
                self.waited += len(batch)
                seq = self.batches
            if self.KERNEL_FAMILY is not None:
                # per-family queue-wait attribution: the batcher-side
                # half of KernelStats' dispatch-vs-wait split (the
                # dispatch half is timed inside counted_jit)
                from pilosa_tpu.utils import telemetry as _telemetry
                if _telemetry.kernel_stats_enabled():
                    _telemetry.kernels.record_wait(
                        self.KERNEL_FAMILY, batch_wait_ms, len(batch))
            if t_cut is not None:
                wall_ms = (t_done - t_cut) * 1e3
                share_ms = wall_ms / max(1, len(batch))
                kind = type(self).__name__
                for r in batch:
                    # dispatch attribution: every profiled co-batched
                    # query learns which dispatch served it, the batch
                    # size it shared, and its wall-time share
                    # (utils/profile.py) — NodeCoalescer envelopes ride
                    # this same hook, so the envelope coalesce factor is
                    # the batchSize of a "NodeCoalescer" dispatch record
                    if r.profile is not None:
                        r.profile.record_dispatch(kind, seq, len(batch),
                                                  wall_ms)
                    # usage attribution rides the identical share
                    # convention (a query cannot be charged less than its
                    # seat): device-ms = wall share, queue-wait = time
                    # from submit to delivery minus the dispatch itself
                    if r.account is not None:
                        r.account.charge(
                            device_ms=share_ms if self.ACCOUNT_DEVICE_MS
                            else 0.0,
                            queue_ms=max(
                                0.0,
                                (t_done - r.t_submit) * 1e3 - wall_ms))
            for r, res in zip(batch, results):
                r.result = res
                r.done = True
                r.event.set()
        except BaseException as e:  # noqa: BLE001 — waiters must wake
            self._deliver_exc(batch, e)

    @staticmethod
    def _deliver_exc(batch: list[_Req], e: BaseException) -> None:
        for r in batch:
            r.exc = e
            r.done = True
            r.event.set()

    # -- compute hooks ----------------------------------------------------
    # Subclasses either implement the pipelined pair — _dispatch launches
    # device work and returns a handle WITHOUT fetching; _finalize blocks
    # on the handle and unpacks per-payload results — or just legacy
    # one-shot _compute (then dispatch is a no-op and finalize does all
    # the work inside the round trip, losing overlap but staying correct).

    def _dispatch(self, key: tuple, payloads: list):
        return _LEGACY

    def _finalize(self, key: tuple, handle, payloads: list) -> list:
        if handle is _LEGACY:
            return self._compute(key, payloads)
        raise NotImplementedError

    def _compute(self, key: tuple, payloads: list) -> list:
        raise NotImplementedError

    def _launch_cross_shard(self, fn, *args):
        """Launch a jitted function that sums over the shard axis: on a
        runner with a mesh through its one collective thread (the leader
        is a request thread), else in place."""
        if self.runner is None:
            return fn(*args)
        return self.runner.collective(fn, *args)

    def queue_depth(self) -> int:
        """Requests currently queued (pre-cut) across every compatibility
        key — the telemetry sampler's saturation gauge."""
        with self._lock:
            return sum(len(q) for q in self._pending.values())

    def snapshot(self) -> dict:
        with self._lock:
            depth = sum(len(q) for q in self._pending.values())
            return {"batches": self.batches,
                    "batched_queries": self.batched_queries,
                    "max_batch_seen": self.max_batch_seen,
                    "queue_depth": depth,
                    "wait_ms_total": round(self.wait_ms_total, 3),
                    "waited": self.waited,
                    "avg_wait_ms": round(
                        self.wait_ms_total / self.waited, 3)
                    if self.waited else 0.0}


# ------------------------------------------------------------------ counts


@counted_jit("batcher", cross_shard=True, static_argnames=("op",))
def _batched_counts(leaves: tuple, ii: jax.Array, jj: jax.Array,
                    op: str) -> jax.Array:
    """Shard-chunk count partials int32[K, C] for K queries
    op(leaves[ii[k]], leaves[jj[k]]), C = ceil(S / _SUM_SHARD_CHUNK).

    `leaves` is a tuple of [S, W] device arrays (pytree: its length is a
    static part of the jit key); the stack and the per-step dynamic gathers
    stay on device, so the only host traffic is ii/jj in and partials out.
    Each chunk's popcount total is < 2^31 so int32 cannot wrap; the caller
    finishes the reduction host-side in int64."""
    rows = jnp.stack(leaves)
    chunk = min(rows.shape[1], _SUM_SHARD_CHUNK)
    pad = (-rows.shape[1]) % chunk if chunk else 0
    if pad:  # zero shards count zero: padding never changes totals
        rows = jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))
    fn = _OPS[op]

    def body(carry, ij):
        i, j = ij
        a = jax.lax.dynamic_index_in_dim(rows, i, axis=0, keepdims=False)
        b = jax.lax.dynamic_index_in_dim(rows, j, axis=0, keepdims=False)
        pc = popcount(fn(a, b))  # per-shard counts [S'] (word axis reduced)
        part = pc.reshape(-1, chunk).sum(axis=-1)
        return carry, part

    _, counts = jax.lax.scan(body, jnp.int32(0), (ii, jj))
    return counts


@functools.lru_cache(maxsize=None)
def _replica_counts_fn(mesh, op: str):
    """Compiled replica-data-parallel count program for one (mesh, op):
    the query *stream* shards over the mesh's replica axis while the leaf
    data shards over the shard axis (replicated per replica slice), so R
    replica slices each serve K/R of the batch against a full data copy —
    the production form of SURVEY §2.9 strategy 3 (the reference fans
    queries across ReplicaN node groups, executor.go:2216-2231; here the
    fan-out is a shard_map and the per-query reduce is an ICI psum)."""
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.parallel.mesh import REPLICA_AXIS, SHARD_AXIS

    fn = _OPS[op]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, SHARD_AXIS, None), P(REPLICA_AXIS),
                  P(REPLICA_AXIS)),
        out_specs=P(REPLICA_AXIS, SHARD_AXIS),
        check_vma=False)
    def run(rows_blk, ii_blk, jj_blk):
        s_loc = rows_blk.shape[1]
        chunk = min(s_loc, _SUM_SHARD_CHUNK)
        pad = (-s_loc) % chunk
        if pad:  # zero shards count zero
            rows_blk = jnp.pad(rows_blk, ((0, 0), (0, pad), (0, 0)))

        def body(carry, ij):
            i, j = ij
            a = jax.lax.dynamic_index_in_dim(rows_blk, i, 0, keepdims=False)
            b = jax.lax.dynamic_index_in_dim(rows_blk, j, 0, keepdims=False)
            pc = popcount(fn(a, b))  # per-local-shard counts
            return carry, pc.reshape(-1, chunk).sum(axis=-1)

        _, parts = jax.lax.scan(body, jnp.int32(0), (ii_blk, jj_blk))
        return parts  # [K_loc, C_loc] int32-safe partials

    @jax.jit
    def outer(leaves: tuple, ii, jj):
        return run(jnp.stack(leaves), ii, jj)

    return outer


class CountBatcher(ContinuousBatcher):
    """Batches Count over 1-/2-leaf bitmap programs. Compatibility key =
    (op, leaf shape, dtype); K and the deduped leaf count pad to pow2
    buckets so the jit cache stays small.

    With a replica×shard mesh runner, the batch splits across replica
    slices (each slice computes its K/R queries against its full data
    copy) instead of every replica redundantly computing all K — batch
    throughput scales with the replica count."""

    def count(self, op: str, a: jax.Array, b: Optional[jax.Array]) -> int:
        if b is None:
            op, b = "id", a
        return self.submit((op, tuple(a.shape), str(a.dtype)), (a, b))

    def _dispatch(self, key: tuple, payloads: list):
        with tracing.span("dispatch", batch=len(payloads)):
            return self._launch(key, payloads)

    def _launch(self, key: tuple, payloads: list):
        op = key[0]
        slots: dict[int, int] = {}
        leaves: list = []

        def slot(arr) -> int:
            s = slots.get(id(arr))
            if s is None:
                s = len(leaves)
                slots[id(arr)] = s
                leaves.append(arr)
            return s

        ii = np.array([slot(a) for a, _ in payloads], dtype=np.int32)
        jj = np.array([slot(b) for _, b in payloads], dtype=np.int32)
        # pow2 buckets bound the jit cache: pad queries by repeating
        # query 0 (dropped on unpack) and leaves by repeating leaf 0
        # (never indexed by real queries)
        k = len(payloads)
        n_rep = 1 if self.runner is None else self.runner.n_replicas
        kp = _pow2(k)
        kp += (-kp) % n_rep  # replica scatter needs n_rep | K
        if kp > k:
            ii = np.concatenate([ii, np.zeros(kp - k, np.int32)])
            jj = np.concatenate([jj, np.zeros(kp - k, np.int32)])
        lp = _pow2(len(leaves))
        leaves = leaves + [leaves[0]] * (lp - len(leaves))
        if n_rep > 1:
            fn = _replica_counts_fn(self.runner.mesh, op)
            return fn(tuple(leaves), ii, jj)  # device array, not fetched
        # on a mesh the chunk sums cross devices (one all-reduce)
        return self._launch_cross_shard(
            _batched_counts, tuple(leaves), ii, jj, op)

    def _finalize(self, key: tuple, handle, payloads: list) -> list:
        with tracing.span("device.wait"):
            parts = np.asarray(handle)  # blocks: the batch's one round trip
        counts = parts.astype(np.int64).sum(axis=-1)  # exact int64 finish
        return [int(c) for c in counts[:len(payloads)]]


# -------------------------------------------------------------- BSI sums


def _dedup_masks(payloads: list) -> tuple[list, list[int]]:
    """Dedup identical mask objects (concurrent unfiltered Sums all pass
    the same residency-cached exists array) and pow2-pad by repeating mask
    0 so the jit cache stays small; returns (masks, per-payload index)."""
    slots: dict[int, int] = {}
    masks: list = []
    idx = []
    for _, m in payloads:
        s = slots.get(id(m))
        if s is None:
            s = len(masks)
            slots[id(m)] = s
            masks.append(m)
        idx.append(s)
    kp = _pow2(len(masks))
    return masks + [masks[0]] * (kp - len(masks)), idx


@counted_jit("batcher", cross_shard=True)
def _batched_plane_sums(planes: jax.Array, masks: tuple) -> jax.Array:
    """Per-query per-plane filtered popcounts with the mask's own count
    appended -> int32[K, depth + 1, C] shard-chunk partials (one dispatch,
    one small fetch for the whole batch; C = ceil(S' / 2016) is 1 for any
    realistic residency)."""
    ex = jnp.stack(masks)  # [K, S', W]
    pc = popcount(jnp.bitwise_and(planes[None], ex[:, None]))  # [K, D, S']
    n = popcount(ex)  # [K, S']
    both = jnp.concatenate([pc, n[:, None]], axis=1)  # [K, D+1, S']
    k, d1, s = both.shape
    pad = (-s) % _SUM_SHARD_CHUNK
    if pad:
        both = jnp.pad(both, ((0, 0), (0, 0), (0, pad)))
    return both.reshape(k, d1, -1, _SUM_SHARD_CHUNK).sum(axis=-1)


@counted_jit("batcher", static_argnames=("is_min",))
def _batched_min_max(planes: jax.Array, masks: tuple,
                     is_min: bool) -> jax.Array:
    """vmapped packed greedy bit descent: int32[K, depth + 1, S'] (bits
    rows 0..depth-1, attaining-count row depth; per-shard, the host picks
    the cross-shard winner exactly as the single-query path does)."""
    from pilosa_tpu.ops.bsi import bsi_max_packed, bsi_min_packed

    fn = bsi_min_packed if is_min else bsi_max_packed
    return jax.vmap(lambda m: fn(planes, m))(jnp.stack(masks))


class MinMaxBatcher(ContinuousBatcher):
    """Batches BSI Min/Max descents sharing a plane slab. Compatibility
    key = (slab identity, is_min)."""

    def packed(self, planes: jax.Array, mask: jax.Array,
               is_min: bool) -> np.ndarray:
        """[depth + 1, S'] int64 packed bits + count for one query."""
        return self.submit((id(planes), tuple(planes.shape), is_min),
                           (planes, mask))

    def _dispatch(self, key: tuple, payloads: list):
        planes, is_min = payloads[0][0], key[2]
        with tracing.span("dispatch", batch=len(payloads)):
            masks, idx = _dedup_masks(payloads)
            return _batched_min_max(planes, tuple(masks), is_min), idx

    def _finalize(self, key: tuple, handle, payloads: list) -> list:
        arrs, idx = handle
        with tracing.span("device.wait"):
            out = np.asarray(arrs)  # blocks: the round trip
        out = out.astype(np.int64)
        return [out[i] for i in idx]


class PlaneSumBatcher(ContinuousBatcher):
    """Batches BSI Sum aggregations that share a plane slab (same field +
    shard set): concurrent dashboards issuing Sum(Range(v > x)) with
    varying thresholds coalesce into one vmapped dispatch. Compatibility
    key = identity of the residency-cached plane slab."""

    def plane_sums(self, planes: jax.Array, mask: jax.Array) -> np.ndarray:
        """[depth + 1] int64 totals for popcount(planes & mask) + count."""
        return self.submit((id(planes), tuple(planes.shape)),
                           (planes, mask))

    def _dispatch(self, key: tuple, payloads: list):
        planes = payloads[0][0]
        with tracing.span("dispatch", batch=len(payloads)):
            masks, idx = _dedup_masks(payloads)
            return self._launch_cross_shard(
                _batched_plane_sums, planes, tuple(masks)), idx

    def _finalize(self, key: tuple, handle, payloads: list) -> list:
        arrs, idx = handle
        with tracing.span("device.wait"):
            out = np.asarray(arrs)  # blocks: the batch's one round trip
        # finish the shard-chunk reduction in int64 (exact)
        totals = out.astype(np.int64).sum(axis=-1)  # [kp, depth+1]
        return [totals[i] for i in idx]
