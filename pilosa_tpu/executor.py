"""Query executor: PQL call dispatch over device-evaluated shard slabs.

Reference: executor.go. The reference evaluates each call per shard inside a
goroutine fan-out, with roaring container kernels doing the bitwise work
(executor.go:2183-2321, 1173-1520). The TPU redesign batches instead of
threading: for a query the executor

  1. walks the bitmap call tree and collects *leaf* operands
     (Row / BSI-compare results / existence rows),
  2. materializes each leaf as a dense bitvector for every shard in the
     query's shard set — through a generation-keyed device cache, so repeat
     queries touch HBM-resident slabs without host transfers,
  3. compiles the call tree to a static nested-tuple program and evaluates
     it on device in one fused XLA program over the [leaves, shards, words]
     slab (pilosa_tpu.parallel.mesh),
  4. reduces: per-shard popcounts / dense rows come back int32/uint32; the
     host assembles exact Python ints and Row segments — the associative
     reduceFn role (executor.go:2209-2242).

Writes (Set/Clear/Store/attrs) stay host-side against the WAL-backed
fragments, invalidating cached slabs by generation, exactly as the
reference's rowCache is invalidated on mutation (fragment.go:435-440).
"""

from __future__ import annotations

import os
from datetime import datetime
from typing import Optional

import numpy as np

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.models import timequantum
from pilosa_tpu.models.cache import merge_pairs
from pilosa_tpu.models.field import FieldType
from pilosa_tpu.models.index import Index
from pilosa_tpu.models.row import Row
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.ops import bsi as bsi_ops
from pilosa_tpu.ops.bitvector import columns_from_dense
from pilosa_tpu.parallel.mesh import DeviceRunner
from pilosa_tpu.pql import (
    Call,
    Condition,
    Query,
    parse_mutations_fast,
    parse_string_cached,
)
from pilosa_tpu.pql.ast import BETWEEN, EQ, GT, GTE, LT, LTE, NEQ
from pilosa_tpu.utils import qctx, tracing
from pilosa_tpu.utils import profile as qprofile

WORDS = SHARD_WIDTH // 32

BITMAP_CALLS = {"Row", "Union", "Intersect", "Difference", "Xor", "Not", "Range"}

# TopN recount slabs are [rows, S, W]: bounded by BYTES, not rows. A fixed
# 256-row block at 128 shards is a 4 GiB slab — 12 GiB live while jnp.stack
# assembles it from its leaves — which fits beside nothing on a 16 GB chip.
_RECOUNT_SLAB_BYTES = 1 << 30


def _recount_chunk(n_shards: int) -> int:
    """Rows per TopN recount block: 256 for small indexes, fewer as the
    shard count grows, never under the 8-row sublane tile."""
    return max(8, min(256, _RECOUNT_SLAB_BYTES // (max(n_shards, 1)
                                                  * WORDS * 4)))


class ExecutionError(ValueError):
    pass


class PairsEntry:
    """The small rows of one field, resident: `dev` int32[2, S', K] or, by
    column, int32[1, S', 32, W] on the device (ops/bitvector.py
    pairs_count), `ids` the rows in rank order
    (ascending), `stored` the bits each holds over all shards. Residency
    charges it `nbytes`, like any leaf."""

    def __init__(self, dev, ids: np.ndarray, stored: np.ndarray):
        self.dev, self.ids, self.stored = dev, ids, stored
        self.nbytes = dev.nbytes
        self.by_column = dev.ndim == 4


class Pairs(list):
    """TopN result: [(row_id, count)] (reference Pairs, cache.go:317).
    `row_keys` holds the translated row keys, index-aligned with the
    pairs, when the field is keyed (Pair.Key, cache.go:319). NOT named
    `keys`: a `keys` attribute makes dict() treat the list as a mapping
    and call it (the mapping protocol) — dict(pairs) must keep working."""

    row_keys: Optional[list] = None


class RowIdentifiers(list):
    """Rows result: sorted row ids (reference RowIdentifiers,
    executor.go:858-861). `row_keys` holds translated row keys on keyed
    fields (RowIdentifiers.Keys); see Pairs for why it isn't `keys`."""

    row_keys: Optional[list] = None


class GroupCounts(list):
    """GroupBy result: [{"group": [...], "count": n}] (reference GroupCounts)."""


class ValCount:
    """Sum/Min/Max result (reference ValCount, executor.go:363)."""

    __slots__ = ("val", "count")

    def __init__(self, val: int = 0, count: int = 0):
        self.val = val
        self.count = count

    def to_json_dict(self):
        return {"value": self.val, "count": self.count}

    def __eq__(self, other):
        return isinstance(other, ValCount) and (self.val, self.count) == (other.val, other.count)

    def __repr__(self):
        return f"ValCount(val={self.val}, count={self.count})"


class Executor:
    def __init__(self, holder, runner: Optional[DeviceRunner] = None,
                 translator=None, cluster=None, client=None):
        self.holder = holder
        self.runner = runner or DeviceRunner()
        self.translator = translator
        # multi-node fan-out (None -> purely local execution)
        self.cluster = cluster
        self.client = client
        # observability (nop defaults; reference: executor per-call counters
        # executor.go:258-293, spans executor.go:85)
        from pilosa_tpu.utils.stats import NopStatsClient
        self.stats = NopStatsClient()
        # host row cache: (index, field, view, shard, row, generation) ->
        # dense numpy row (the reference's fragment rowCache analog,
        # fragment.go:112)
        self._row_cache: dict[tuple, np.ndarray] = {}
        self._row_cache_epoch = 0  # bumped by clear_caches(); fences misses
        # rows materialized for TopN recounts — observability for the
        # threshold-pruning walk (tests assert ≪ total rows; /debug/vars)
        self.topn_recount_rows = 0
        # the recount from sorted columns (_pairs_entry, _pairs_recount):
        # launches of the pairs kernel; the bytes they had to read, from
        # the data and the request (4 a stored bit of the rows recounted,
        # 128 KiB a shard for the filter plane), not from what was
        # uploaded; of the launches, those over an entry laid by column
        # (nothing gathered); entries built and the bytes they hold on
        # the device
        self.topn_pairs_recounts = 0
        self.topn_pairs_recounts_by_column = 0
        self.topn_pairs_bytes = 0
        self.pairs_entries_built = 0
        self.pairs_entry_bytes = 0
        # a TopN under tanimotoThreshold: candidates that entered the
        # Tanimoto band and those it kept for the recount (/debug/vars)
        self.topn_band_in = 0
        self.topn_band_kept = 0
        # host syncs performed by GroupBy's device path — the pipelined
        # level loop promises at most ONE blocking fetch per cross-product
        # level (tests assert it, like topn_recount_rows; /debug/vars)
        self.groupby_host_syncs = 0
        # static size bound of the on-device zero-prune transfer: a level
        # chunk whose live combinations exceed it falls back to a full
        # count-matrix fetch (counted as an extra sync)
        self._groupby_live_cap = int(os.environ.get(
            "PILOSA_TPU_GROUPBY_LIVE_BOUND", str(1 << 16)))
        # (index, field, shards) -> (cache versions, merged ids, counts):
        # the cross-shard TopN candidate merge memo, LRU-bounded so a
        # server alternating many ad-hoc shard subsets evicts the coldest
        # entry instead of dropping every memo at once (see
        # _topn_candidate_arrays)
        import collections
        import threading as _threading
        self._topn_merge_memo: collections.OrderedDict = \
            collections.OrderedDict()
        self._topn_memo_lock = _threading.Lock()
        # HBM residency manager: query leaves cached as device arrays keyed
        # by content generation; repeat queries run without host->HBM
        # transfers (parallel/residency.py)
        from pilosa_tpu.parallel.residency import DeviceResidency
        self.residency = DeviceResidency(self.runner)
        # fragment heat map (utils/heat.py): per-(index, field, view,
        # shard) access temperature charged by the row-leaf reads, the
        # write path, plan-cache hits and the residency transitions; the
        # placement advisor and `[storage] eviction = heat` consume it.
        # PILOSA_TPU_HEAT=0 builds no tracker — every charge site is one
        # None check and residency eviction stays lru.
        from pilosa_tpu.utils import heat as _heat
        self.heat = _heat.HeatTracker() if _heat.enabled() else None
        self.residency.heat = self.heat
        # hybrid sparse/dense device containers (parallel/residency.py
        # HybridManager; ops/bitvector.py sparse kernels): rows at or
        # below [query] sparse-threshold bits per shard live in HBM as
        # padded sorted-index arrays instead of dense planes, chosen per
        # operand by the planner from exact cardinalities
        # (planner.choose_representation) with promote/demote hysteresis
        # and heat-informed demotion. PILOSA_TPU_HYBRID=0 / threshold 0
        # restore pure-dense behavior (read per decision, no restart).
        from pilosa_tpu.parallel.residency import HybridManager, RowStatsMemo
        self.hybrid = HybridManager(heat=self.heat)
        # a row's per-shard statistics (generations, cardinalities, run
        # statistics, heat coordinates), kept per write version of its
        # view: what the planner, the plan cache's key, the
        # representation choice and the leaf lookup read of a row
        self.row_stats = RowStatsMemo()
        # continuous batching of concurrent simple Counts into single
        # device dispatches (parallel/batcher.py); PILOSA_TPU_BATCH=0
        # falls back to one dispatch per query
        from pilosa_tpu.parallel.batcher import (
            CountBatcher,
            MinMaxBatcher,
            PlaneSumBatcher,
        )
        if os.environ.get("PILOSA_TPU_BATCH", "1") != "0":
            # runner-aware: on a replica×shard mesh the batch scatters
            # over replica slices (SURVEY §2.9 strategy 3 in the
            # PRODUCTION serving path, not just the bench kernels)
            self.batcher = CountBatcher(runner=self.runner)
            self.sum_batcher = PlaneSumBatcher(runner=self.runner)
            self.minmax_batcher = MinMaxBatcher()
        else:
            self.batcher = None
            self.sum_batcher = None
            self.minmax_batcher = None
        # ---- distributed fan-out plumbing (net/coalesce.py) ----
        # persistent bounded pools replacing the per-query
        # ThreadPoolExecutor: created lazily, shut down with the server
        # (shutdown()); sizes are Server/config knobs
        self._fanout_pool = None
        self._batch_exec_pool = None
        self._hedge_pool = None
        self._pool_lock = _threading.Lock()
        self.fanout_pool_size = 32
        self.batch_exec_pool_size = 16
        # hedged replica reads: after hedge_delay seconds without a primary
        # response, the same read-only node batch re-issues to the next
        # live replica and the first response wins. 0 disables.
        self.hedge_delay = 0.0
        self._hedge_lock = _threading.Lock()
        self.hedges_fired = 0
        self.hedges_won = 0
        self.hedges_cancelled = 0
        # network-layer continuous batcher: concurrent fan-out queries to
        # the same remote node coalesce into one /internal/query-batch
        # envelope (PILOSA_TPU_NET_COALESCE=0 falls back to per-query RPC)
        self.coalescer = None
        if client is not None and os.environ.get(
                "PILOSA_TPU_NET_COALESCE", "1") != "0":
            from pilosa_tpu.net.coalesce import NodeCoalescer
            self.coalescer = NodeCoalescer(client)
        # ---- ICI-native slice-local serving (ROADMAP item 1) ----
        # When a query's full shard set is co-resident on this node's
        # multi-chip slice (this node holds a live, un-fenced replica of
        # every shard), the query executes as ONE sharded program over the
        # mesh — shard_map + lax.psum on the interconnect
        # (parallel/mesh.py eval_count_mesh/eval_row_mesh) — instead of
        # HTTP scatter-gather. Modes: "off" never routes slice-local;
        # "auto" (default) routes when the runner has a mesh; "on" routes
        # whenever co-residency holds, mesh or not (a single-device node
        # still saves the fan-out RTTs). PILOSA_TPU_ICI=0 kills it.
        self.ici_mode = "auto"
        self._ici_env = os.environ.get("PILOSA_TPU_ICI", "1") != "0"
        self._ici_lock = _threading.Lock()
        self.ici_slice_local = 0   # queries served as one sharded program
        self.ici_cross_slice = 0   # shard set not co-resident: HTTP plane
        self.ici_fallback = 0      # disabled / write / unroutable shape
        # co-residency memo: (index, shard tuple) -> bool under one
        # topology fingerprint; any membership/liveness change flushes it
        # (the generation-keying discipline applied to cluster state)
        self._ici_route_memo: collections.OrderedDict = \
            collections.OrderedDict()
        self._ici_topo_fp = None
        # flight-recorder journal (utils/events.py, set by Server):
        # topology-fingerprint flips and slice-local routing flips land
        # on the merged cluster timeline; the pre-flush memo lets a flip
        # of a SPECIFIC routing decision be reported, not just the flush
        self.journal = None
        self._ici_prev_memo: dict = {}
        # cost-based query planner (pilosa_tpu/planner.py): cardinality
        # reorders, empty-branch short-circuits, Count/TopN pushdown
        # marking; PILOSA_TPU_PLANNER=0 / [query] plan=off fall back to
        # written-order evaluation
        self.planner = None
        if os.environ.get("PILOSA_TPU_PLANNER", "1") != "0":
            from pilosa_tpu.planner import QueryPlanner
            self.planner = QueryPlanner(self)
        # generation-keyed cross-query subexpression cache
        # (parallel/residency.py PlanCache): evaluated bitmap subtrees stay
        # device-resident keyed by (canonical PQL, shards, row gens) — a
        # write bumps a generation, changing the key, so invalidation is
        # free. PILOSA_TPU_PLAN_CACHE=0 / [query] plan-cache-bytes=0 off.
        self.plan_cache = None
        if os.environ.get("PILOSA_TPU_PLAN_CACHE", "1") != "0":
            from pilosa_tpu.parallel.residency import PlanCache
            self.plan_cache = PlanCache()
        # durable hinted handoff (storage/hints.py HintStore; set by
        # Server): a replica write skipped because the target is down or
        # draining is appended to the target's on-disk hint log instead
        # of being silently dropped. None = the old skip-silently behavior
        # (bare executors / tests without a server).
        self.hints = None
        # read fence (rejoin consistency): (index, shard) pairs whose
        # local fragments may be stale after a down/drain rejoin. Reads
        # for fenced shards route to a peer replica — locally by
        # re-grouping the fan-out plan, remotely by refusing the shard so
        # the coordinator's per-shard failover retries elsewhere — until
        # hint replay or a block-checksum-verified scrub confirms parity
        # (server._verify_fence_pass lifts the fence).
        self.read_fence: set[tuple[str, int]] = set()
        self._fence_lock = _threading.Lock()
        self.fence_rerouted = 0  # reads routed around a fenced local shard
        self.fence_refused = 0  # remote reads refused into peer failover
        self.fence_served_stale = 0  # no live alternative: stale > down
        # announce_shard_fn(index, field, shard): synchronous cluster
        # broadcast of a create-shard, set by Server. Used by the Set()
        # write path when the write CREATES the shard, so the ack implies
        # cluster-wide shard visibility (read-your-writes through ANY
        # node). Shard creation happens once per shard lifetime, so the
        # extra broadcast round-trip is paid ~never; bulk imports keep
        # the async announcement queue.
        self.announce_shard_fn = None
        # ---- streaming ingest (parallel/ingest.py, ISSUE 16) ----
        # write-side continuous batcher: concurrent Set/Clear coalesce
        # into per-(fragment, shard) bulk applies — one WAL group-commit,
        # one container merge, one generation bump per fragment per batch.
        # PILOSA_TPU_INGEST=0 is read per decision at the interception
        # site (execute()), so the batcher object always exists and the
        # kill switch needs no restart. Window/max-batch are Server/config
        # knobs ([ingest] section).
        from pilosa_tpu.parallel.ingest import IngestBatcher
        self.ingest = IngestBatcher(self._apply_ingest_batch)
        self._ingest_lock = _threading.Lock()
        self.ingest_stats = {
            "appliedBatches": 0,    # per-fragment bulk applies
            "walAppends": 0,        # WAL group-commits (<= 1 fsync each)
            "walOps": 0,            # net framed records written
            "remoteBatches": 0,     # replica envelopes sent
            "remoteMutations": 0,   # mutations those envelopes carried
            "hintedMutations": 0,   # mutations demoted to durable hints
            "errors": 0,            # per-mutation failures
            "patchedDense": 0,      # resident dense leaves patched in HBM
            "patchedSparse": 0,     # resident sparse leaves patched in HBM
            "patchDropped": 0,      # stale residents dropped un-patchable
            "hybridEvals": 0,       # write-side hysteresis ticks
            "newShards": 0,         # shards created by batched Sets
        }

    # ------------------------------------------------------ fan-out pools

    def _get_pool(self, attr: str, size: int, name: str):
        pool = getattr(self, attr)
        if pool is not None:
            return pool
        with self._pool_lock:
            if getattr(self, attr) is None:
                # fan-out + inbound-envelope pools are priority-ordered
                # (pilosa_tpu/qos.py PriorityPool): under saturation a
                # batch tenant's submits queue behind interactive ones.
                # With one priority class it degrades to FIFO, and the
                # kill switch falls back to the plain executor.
                from pilosa_tpu import qos
                if attr in ("_fanout_pool", "_batch_exec_pool") \
                        and qos.enabled():
                    setattr(self, attr, qos.PriorityPool(
                        size, thread_name_prefix=name))
                else:
                    from concurrent.futures import ThreadPoolExecutor
                    setattr(self, attr, ThreadPoolExecutor(
                        max_workers=size, thread_name_prefix=name))
            return getattr(self, attr)

    @property
    def fanout_pool(self):
        """Long-lived bounded pool for outbound node fan-out (replaces the
        ThreadPoolExecutor the old code built and tore down per query)."""
        return self._get_pool("_fanout_pool", max(4, self.fanout_pool_size),
                              "pilosa-fanout")

    @property
    def batch_exec_pool(self):
        """Inbound /internal/query-batch envelope execution. Deliberately
        SEPARATE from fanout_pool: inbound entries run with remote=True —
        purely local, never waiting on other nodes — so this pool always
        drains; sharing the outbound pool could distributed-deadlock when
        two coordinators fan out to each other under saturation."""
        return self._get_pool("_batch_exec_pool",
                              max(2, self.batch_exec_pool_size),
                              "pilosa-qbatch")

    @property
    def hedge_pool(self):
        """Hedged-read race threads — separate from fanout_pool so a hedge
        never competes with the primaries for fan-out slots (created only
        when hedge_delay > 0 fires the first race)."""
        return self._get_pool("_hedge_pool", max(4, self.fanout_pool_size),
                              "pilosa-hedge")

    def fanout_pool_stats(self) -> dict:
        """Outbound fan-out pool occupancy for telemetry — WITHOUT forcing
        the lazy pool into existence (an idle node keeps zero threads)."""
        pool = self._fanout_pool
        if pool is None:
            return {"size": max(4, self.fanout_pool_size),
                    "threads": 0, "queued": 0}
        return {"size": pool._max_workers,
                "threads": len(pool._threads),
                "queued": pool._work_queue.qsize()}

    def shutdown(self) -> None:
        """Stop the executor-owned pools (called from Server.close)."""
        with self._pool_lock:
            for attr in ("_fanout_pool", "_batch_exec_pool", "_hedge_pool"):
                pool = getattr(self, attr)
                if pool is not None:
                    pool.shutdown(wait=False, cancel_futures=True)
                    setattr(self, attr, None)

    # ------------------------------------------------- read fence (rejoin)

    def fence_reads(self, keys) -> int:
        """Fence (index, shard) pairs: local reads re-route to a peer
        replica until the server's rejoin verifier lifts the fence."""
        with self._fence_lock:
            before = len(self.read_fence)
            self.read_fence.update(keys)
            return len(self.read_fence) - before

    def unfence_reads(self, key) -> bool:
        with self._fence_lock:
            if key in self.read_fence:
                self.read_fence.discard(key)
                return True
            return False

    def fence_snapshot(self) -> dict:
        with self._fence_lock:
            return {
                "fencedShards": len(self.read_fence),
                "rerouted": self.fence_rerouted,
                "refusedRemote": self.fence_refused,
                "servedStale": self.fence_served_stale,
            }

    def _fence_peer(self, index_name: str, shard: int):
        """A live, un-excluded peer replica for a fenced shard, or None
        (fencing only acts when someone else can serve the read)."""
        for n in self.cluster.shard_nodes(index_name, shard):
            if n.id != self.cluster.local_id and n.uri \
                    and not self.cluster.is_unavailable(n.id):
                return n
        return None

    def _check_remote_fence(self, index_name: str, query: Query,
                            shards) -> None:
        """Remote (fan-out sub-request) entry: refuse fenced shards so
        the COORDINATOR's existing per-shard failover re-maps them onto a
        healthy replica — the rejoining node never serves a possibly
        stale read while a peer can serve a verified one. Writes and
        hint-replay traffic pass through (the fence is a READ fence; the
        heal itself must land here)."""
        if not shards:
            return
        if any(self._call_has_write(c) for c in query.calls):
            return
        with self._fence_lock:
            fenced = [s for s in shards
                      if (index_name, s) in self.read_fence]
        for s in fenced:
            if self._fence_peer(index_name, s) is not None:
                with self._fence_lock:
                    self.fence_refused += 1
                raise ExecutionError(
                    f"shard {s} read-fenced pending rejoin sync "
                    "(code=read-fenced)")
        if fenced:
            # every replica of the fenced shards is down/draining: serve
            # the local copy — stale beats unavailable
            with self._fence_lock:
                self.fence_served_stale += len(fenced)

    def _fanout_groups(self, index: Index, qshards: list[int]) -> dict:
        """shards_by_node plus the local read-fence re-route: fenced
        shards this node owns are planned onto the next live replica (the
        per-shard failover path, taken up front instead of after a
        round-trip refusal)."""
        groups = self.cluster.shards_by_node(index.name, qshards)
        if not self.read_fence:
            return groups
        local = groups.get(self.cluster.local_id)
        if not local:
            return groups
        with self._fence_lock:
            fenced = [s for s in local
                      if (index.name, s) in self.read_fence]
        if not fenced:
            return groups
        keep = [s for s in local if s not in set(fenced)]
        for s in fenced:
            peer = self._fence_peer(index.name, s)
            if peer is None:
                keep.append(s)  # no live alternative: stale > down
                with self._fence_lock:
                    self.fence_served_stale += 1
                continue
            groups.setdefault(peer.id, []).append(s)
            with self._fence_lock:
                self.fence_rerouted += 1
        if keep:
            groups[self.cluster.local_id] = keep
        else:
            groups.pop(self.cluster.local_id, None)
        return groups

    def clear_caches(self) -> None:
        """Drop the host row cache and all HBM-resident leaves. Called on
        index/field deletion: a recreated schema object restarts its
        generation counters, so version-keyed entries from the deleted one
        could otherwise collide and serve the old data."""
        self._row_cache_epoch += 1
        self._row_cache.clear()
        self.residency.clear()
        self.row_stats.clear()
        if self.plan_cache is not None:
            self.plan_cache.clear()

    # ------------------------------------------------------------------ API

    def execute(self, index_name: str, query, shards: Optional[list[int]] = None,
                remote: bool = False, timeout: Optional[float] = None):
        """Execute a PQL query; returns a list of per-call results
        (executor.Execute, executor.go:84). `remote=True` marks a fan-out
        sub-request: execute locally on exactly the given shards
        (opt.Remote, executor.go:2147). `timeout` (seconds) sets a query
        deadline checked between shard batches and fanned out to remote
        nodes (ctx cancellation, executor.go:2591-2608); an inherited
        deadline (HTTP layer) applies when omitted."""
        if isinstance(query, str):
            # bulk-ingest envelopes (runs of Set/Clear calls) take the
            # linear mutation scanner; unique column ids make them
            # useless to the LRU plan cache and the full parser is ~10x
            # slower per call. Everything else keeps the cached parse.
            with tracing.span("pql.parse"):
                query = (parse_mutations_fast(query)
                         or parse_string_cached(query))
        if not isinstance(query, Query):
            raise TypeError("query must be a PQL string or Query")
        index = self.holder.index(index_name)
        if index is None:
            raise ExecutionError(f"index not found: {index_name}")
        if remote and self.read_fence and self.cluster is not None:
            # rejoin read fence: refuse possibly-stale shards back into
            # the coordinator's per-shard failover (see fence_reads)
            self._check_remote_fence(index_name, query, shards)
        distributed = (not remote and self.cluster is not None
                       and self.client is not None
                       and len(self.cluster.nodes) > 1)
        # ---- coalesced streaming ingest (parallel/ingest.py) ----
        # all-Set/Clear queries route through the IngestBatcher: the
        # mutations are translated HERE (submitter thread), queued under
        # the index's compatibility key, and applied by a batch leader as
        # per-fragment bulk operations. remote=True multi-call envelopes
        # (a coordinator's batched replica fan-out) bulk-apply directly —
        # they ARE a batch already; queueing them again would serialize
        # the cluster on one node's admission. Anything the batcher can't
        # take bit-identically (INT fields, mutex/bool, timestamps,
        # missing fields) falls through to the per-bit path below.
        from pilosa_tpu.parallel import ingest as _ingest
        if (_ingest.ingest_env_enabled()
                and query.calls
                and all(c.name in ("Set", "Clear") for c in query.calls)):
            if not remote:
                handled = self._execute_ingest(index, query)
                if handled is not None:
                    return handled
            else:
                handled = self._execute_ingest_remote(index, query)
                if handled is not None:
                    return handled
        import time as _time
        dl_token = (qctx.deadline.set(_time.monotonic() + timeout)
                    if timeout else None)
        try:
            results = []
            prof = qprofile.current_profile.get()  # None = profiling off
            for call in query.calls:
                qctx.check()
                self.stats.count(f"query/{call.name}")
                with tracing.span(f"executor.{call.name}",
                                  index=index_name) as span:
                    if distributed:
                        result = self._execute_distributed(index, call, shards)
                    else:
                        result = self._execute_call(index, call, shards)
                    if not remote:
                        # ids -> keys on the coordinator only; remote
                        # sub-results stay raw (translateResults,
                        # executor.go:2323,2483)
                        with tracing.span("reduce"):
                            result = self._translate_result(
                                index, call, result)
                    results.append(result)
                if prof is not None:
                    prof.record_call(call.name, span.ms)
            return results
        finally:
            if dl_token is not None:
                qctx.deadline.reset(dl_token)

    # ------------------------------------------------------------ dispatch

    def _execute_call(self, index: Index, call: Call, shards):
        # Options() wrapper (executor.go:317)
        if call.name == "Options":
            return self._execute_options(index, call, shards)
        from pilosa_tpu import planner as _planner
        plan_tok = None
        if self.planner is not None and call.name in _planner.PLANNED_CALLS:
            # the planning pass between parse and execution: reorder /
            # short-circuit / pushdown-mark, then install the plan node so
            # plan-cache events recorded during evaluation join it. The
            # profiler serializes it as the call's `plan` entry.
            with tracing.span("plan"):
                call, plan_info = self.planner.plan_call(
                    index, call, self._query_shards(index, shards))
            plan_tok = _planner.current_plan.set(plan_info)
            prof = qprofile.current_profile.get()
            if prof is not None:
                prof.record_plan(plan_info)
        try:
            return self._dispatch_call(index, call, shards)
        finally:
            if plan_tok is not None:
                _planner.current_plan.reset(plan_tok)

    def _dispatch_call(self, index: Index, call: Call, shards):
        handler = {
            "Count": self._execute_count,
            "TopN": self._execute_topn,
            "Sum": self._execute_sum,
            "Min": self._execute_min,
            "Max": self._execute_max,
            "Rows": self._execute_rows,
            "GroupBy": self._execute_group_by,
            "Set": self._execute_set,
            "Clear": self._execute_clear,
            "ClearRow": self._execute_clear_row,
            "Store": self._execute_store,
            "SetRowAttrs": self._execute_set_row_attrs,
            "SetColumnAttrs": self._execute_set_column_attrs,
        }.get(call.name)
        if handler is not None:
            return handler(index, call, shards)
        if call.name in BITMAP_CALLS:
            return self._execute_bitmap_call(index, call, shards)
        raise ExecutionError(f"unknown call: {call.name}")

    def _query_shards(self, index: Index, shards) -> list[int]:
        if shards is not None:
            return sorted(shards)
        # memoized on per-field shard versions; shared list — don't mutate
        return index.available_shards_list()

    # ----------------------------------------------------- bitmap programs

    def _leaf_gens(self, index: Index, field_name: str, view_name: str,
                   shards, row_id: int) -> tuple:
        """Per-shard content generations of one row — the version component
        of a residency key (a write bumps the generation, changing the key)."""
        f = index.field(field_name)
        view = f.view(view_name) if f else None
        if view is None:
            return ()
        out = []
        for s in shards:
            frag = view.fragment(s)
            out.append(0 if frag is None else frag.row_generation(row_id))
        return tuple(out)

    def _row_leaf_dev(self, index: Index, field_name: str, view_name: str,
                      shards, row_id: int, gens: tuple = None):
        """HBM-resident [S(padded), W] device array for one row via the
        residency manager — shared by bitmap programs, BSI planes and TopN
        recounts. `gens` skips the per-shard generation scan when the
        caller already computed it (GroupBy slab keys).

        When the row is already HBM-resident in its SPARSE or RUN hybrid
        form, a dense consumer gets the plane by materializing ON DEVICE
        from the resident index/interval array (one small kernel, zero
        host->device bytes) instead of re-uploading 128 KiB per shard."""
        if gens is None:
            gens = self.row_stats.get(index, field_name, view_name,
                                      shards, row_id).gens
        self._touch_reads(index, field_name, view_name, shards, 1)
        return self._row_leaf_at(index, field_name, view_name, shards,
                                 row_id, gens)

    def _touch_reads(self, index: Index, field_name: str, view_name: str,
                     shards, reads: int) -> None:
        """Read heat at the fragment coordinate, one lock round trip for
        the whole shard set: the read charge of _row_leaf_dev and
        _row_leaves_dev (BSI planes, TopN recounts, GroupBy slabs). A
        bitmap program's row leaves are charged together, once a request
        (_compile_tree, _heat_charge)."""
        tracker = self.heat
        if tracker is not None and tracker.enabled:
            tracker.touch_many(self.row_stats.frag_keys(
                index.name, field_name, view_name, tuple(shards)),
                reads=reads)

    def _row_leaves_dev(self, index: Index, field_name: str, view_name: str,
                        shards, row_ids) -> list:
        """_row_leaf_dev for a block of one field's rows (a TopN recount
        block): the same leaves under the same keys, with what does not
        depend on the row done once a block — every shard's fragment
        looked up once, and the block's reads charged to each fragment in
        one touch (`reads` adds up: len(row_ids) at once is what that many
        touches of one leave) — where a row at a time costs rows × shards
        lookups and heat updates under the tracker's one lock."""
        f = index.field(field_name)
        view = f.view(view_name) if f else None
        frags = [] if view is None else [view.fragment(s) for s in shards]
        self._touch_reads(index, field_name, view_name, shards,
                          len(row_ids))
        return [self._row_leaf_at(
            index, field_name, view_name, shards, rid,
            tuple(0 if fr is None else fr.row_generation(rid)
                  for fr in frags)) for rid in row_ids]

    def _row_leaf_at(self, index: Index, field_name: str, view_name: str,
                     shards, row_id: int, gens: tuple):
        """The resident leaf of one row at these generations (the part of
        _row_leaf_dev below the key)."""
        key = ("row", index.name, field_name, view_name, row_id,
               tuple(shards), gens)

        def make():
            hyb = self.hybrid
            if hyb is not None and hyb.active():
                from pilosa_tpu.ops import bitvector as bv
                # probe (no hit/miss accounting) for a resident sparse
                # twin under the SAME generations: any slot bucket the
                # chooser could have used
                card = self.row_stats.get(index, field_name, view_name,
                                          shards, row_id).max_card
                skey = ("sparse", index.name, field_name, view_name,
                        row_id, tuple(shards), hyb.pad_slots(max(card, 1)),
                        gens)
                sp = self.residency.peek(skey)
                if sp is not None:
                    hyb.record_materialize()
                    return bv.sparse_to_dense(sp, WORDS)
                if hyb.run_threshold > 0:
                    # same probe for a resident RUN twin (interval-pair
                    # array): slot bucket comes from the write-maintained
                    # interval count, generation-cached like cardinality
                    n_iv, _ = self.row_stats.run_stats(
                        index, field_name, view_name, shards, row_id)
                    rkey = ("run", index.name, field_name, view_name,
                            row_id, tuple(shards),
                            hyb.pad_slots(max(n_iv, 1)), gens)
                    rn = self.residency.peek(rkey)
                    if rn is not None:
                        hyb.record_materialize()
                        return bv.run_to_dense(rn, WORDS)
            return np.stack([
                self._cached_row(index, field_name, view_name, s, row_id)
                for s in shards])

        return self.residency.leaf(
            key, make,
            put=lambda h: (self.hybrid.record_upload("dense", h.nbytes),
                           self.runner.put_leaf(h))[1])

    def _row_leaf_run_dev(self, index: Index, field_name: str,
                          view_name: str, shards, row_id: int,
                          gens: tuple, slots: int):
        """HBM-resident RUN row leaf: int32[S(padded), 2, slots] of sorted
        inclusive [start, last] shard-local interval pairs, sentinel-padded
        (ops/bitvector.py run kernels) — the hybrid representation for
        long-run rows above the sparse threshold. Intervals come STRAIGHT
        from the storage run containers (Fragment.row_runs walks each
        container's native run encoding) — no densify→re-encode round trip
        on upload, the TYPE_RUN regime of arXiv:1603.06549 carried to the
        device tier. Byte cost is the real padded allocation
        (S · 2 · slots · 4); pad shards fill with the sentinel in both
        interval planes so they read as empty. The read is the caller's
        to charge (_compile_tree: once a request)."""
        from pilosa_tpu.ops import bitvector as bv
        key = ("run", index.name, field_name, view_name, row_id,
               tuple(shards), slots, gens)
        f = index.field(field_name)
        view = f.view(view_name) if f is not None else None

        def make():
            arr = np.full((len(shards), 2, slots), bv.RUN_SENTINEL,
                          dtype=np.int32)
            for i, s in enumerate(shards):
                frag = view.fragment(s) if view is not None else None
                if frag is None:
                    continue
                # a write racing between the sizing read and this one can
                # exceed the slot bucket; runs_from_intervals truncates,
                # which stays inside the engine's read-consistency
                # envelope (per-shard rows tear the same way on the dense
                # path) and the generation bump re-keys the next lookup
                arr[i] = bv.runs_from_intervals(frag.row_runs(row_id),
                                                slots)
            return arr

        hyb = self.hybrid
        return self.residency.leaf(
            key, make,
            put=lambda h: (hyb.record_upload("run", h.nbytes),
                           self.runner.put_leaf(
                               h, fill=bv.RUN_SENTINEL))[1])

    def _row_leaf_sparse_dev(self, index: Index, field_name: str,
                             view_name: str, shards, row_id: int,
                             gens: tuple, slots: int):
        """HBM-resident SPARSE row leaf: int32[S(padded), slots] of sorted
        shard-local column ids, sentinel-padded (ops/bitvector.py) — the
        hybrid representation for rows below the sparse threshold. Byte
        cost is the real padded allocation (S · slots · 4), charged to the
        residency budget like any leaf; pad shards fill with the sentinel
        through put_leaf's fill parameter so they read as empty. The read
        is the caller's to charge (_compile_tree: once a request)."""
        from pilosa_tpu.ops import bitvector as bv
        key = ("sparse", index.name, field_name, view_name, row_id,
               tuple(shards), slots, gens)
        f = index.field(field_name)
        view = f.view(view_name) if f is not None else None

        def make():
            arr = np.full((len(shards), slots), bv.SPARSE_SENTINEL,
                          dtype=np.int32)
            for i, s in enumerate(shards):
                frag = view.fragment(s) if view is not None else None
                if frag is None:
                    continue
                cols = frag.row_columns(row_id)
                if cols.size:
                    # a write racing between the sizing read and this one
                    # can exceed the slot bucket; truncation stays inside
                    # the engine's existing read-consistency envelope (the
                    # dense path's per-shard rows tear the same way) and
                    # the write's generation bump re-keys the next lookup
                    n = min(cols.size, slots)
                    arr[i, :n] = cols[:n]
            return arr

        hyb = self.hybrid
        return self.residency.leaf(
            key, make,
            put=lambda h: (hyb.record_upload("sparse", h.nbytes),
                           self.runner.put_leaf(
                               h, fill=bv.SPARSE_SENTINEL))[1])

    def hybrid_snapshot(self) -> dict:
        """The /debug/vars `hybrid` block + /metrics family source:
        manager counters (uploads/transitions by representation) merged
        with the residency manager's live per-kind occupancy."""
        out = self.hybrid.snapshot()
        by_kind = self.residency.snapshot()["by_kind"]
        sp = by_kind.get("sparse", {})
        rn = by_kind.get("run", {})
        dn = by_kind.get("row", {})
        out["residentSparseLeaves"] = sp.get("entries", 0)
        out["residentSparseBytes"] = sp.get("bytes", 0)
        out["residentRunLeaves"] = rn.get("entries", 0)
        out["residentRunBytes"] = rn.get("bytes", 0)
        out["residentDenseRowLeaves"] = dn.get("entries", 0)
        out["residentDenseRowBytes"] = dn.get("bytes", 0)
        return out

    # ------------------------------------------------------------- HBM map

    _LEAF_KIND_REP = {"row": "dense", "sparse": "sparse", "run": "run"}

    def _leaf_waste(self, key: tuple, nbytes: int) -> int:
        """Padding waste of one resident row leaf: allocated bytes beyond
        what the row's actual cardinality / interval count needs. Dense
        planes waste only their shard-dim padding (the plane itself is
        the representation); sparse/run leaves waste their power-of-two
        slot padding plus pad shards. Reads are write-maintained caches
        (row_counts / row_run_stats) — dict probes, not container walks."""
        kind, shards = key[0], key[5]
        if kind == "row":
            return max(0, nbytes - len(shards) * WORDS * 4)
        index = self.holder.index(key[1])
        f = index.field(key[2]) if index is not None else None
        view = f.view(key[3]) if f is not None else None
        useful = 0
        if view is not None:
            slots = key[6]
            for s in shards:
                frag = view.fragment(s)
                if frag is None:
                    continue
                if kind == "sparse":
                    useful += min(frag.row_cardinality(key[4]), slots) * 4
                else:  # run: [start, last] int32 pairs
                    n_iv, _ = frag.row_run_stats(key[4])
                    useful += min(n_iv, slots) * 8
        return max(0, nbytes - useful)

    def hbm_snapshot(self, top: int = 64) -> dict:
        """GET /debug/hbm source: what residency THINKS lives in HBM —
        resident leaves grouped by (index, field, rep) with real padded
        bytes and padding waste, non-row kinds (bsicmp masks, GroupBy
        slabs, ...) by kind, plan-cache bytes, budget headroom and the
        heat advisor's pin set — joined against the backend allocator's
        memory_stats() when the backend provides it. `hbmDriftBytes` is
        allocator live bytes minus accounted bytes: sustained growth
        means device memory the accounting layer cannot see (leaked
        handles, fragmentation, another tenant)."""
        from pilosa_tpu.utils import telemetry as _telemetry
        by_field: dict = {}
        other: dict = {}
        waste_by_rep = {"dense": 0, "sparse": 0, "run": 0}
        for key, nbytes in self.residency.entries_snapshot():
            kind = key[0] if isinstance(key, tuple) and key else "?"
            rep = self._LEAF_KIND_REP.get(kind)
            if rep is not None and len(key) >= 6:
                g = by_field.setdefault(
                    (key[1], key[2], rep),
                    {"leaves": 0, "bytes": 0, "wasteBytes": 0})
                g["leaves"] += 1
                g["bytes"] += nbytes
                try:
                    w = self._leaf_waste(key, nbytes)
                except Exception:  # noqa: BLE001 — schema churn mid-walk
                    w = 0
                g["wasteBytes"] += w
                waste_by_rep[rep] += w
            else:
                o = other.setdefault(str(kind), {"entries": 0, "bytes": 0})
                o["entries"] += 1
                o["bytes"] += nbytes
        fields = [
            {"index": idx, "field": fld, "rep": rep, **g}
            for (idx, fld, rep), g in by_field.items()]
        fields.sort(key=lambda e: (-e["bytes"], e["index"], e["field"],
                                   e["rep"]))
        res = self.residency.snapshot()
        pc = self.plan_cache.snapshot() if self.plan_cache is not None \
            else None
        accounted = res["bytes"] + (pc["bytes"] if pc else 0)
        alloc = None
        for dev in _telemetry.device_memory_stats():
            ms = dev["memoryStats"]
            if ms and "bytes_in_use" in ms:
                if alloc is None:
                    alloc = {"bytesInUse": 0, "bytesLimit": 0, "devices": 0}
                alloc["bytesInUse"] += int(ms["bytes_in_use"])
                alloc["bytesLimit"] += int(ms.get("bytes_limit", 0))
                alloc["devices"] += 1
        pins = []
        if self.heat is not None and self.heat.enabled:
            from pilosa_tpu.analysis import advisor as _advisor
            try:
                pins = _advisor.advise(
                    self.heat.snapshot(top=0), residency=res,
                    budget_bytes=self.residency.budget)["hbmPinSet"]
            except Exception:  # noqa: BLE001 — advisory join only
                pins = []
        return {
            "budgetBytes": self.residency.budget,
            "residentBytes": res["bytes"],
            "headroomBytes": max(0, self.residency.budget - res["bytes"]),
            "entries": res["entries"],
            "evictions": res["evictions"],
            "planCacheBytes": pc["bytes"] if pc else 0,
            "planCacheEntries": pc["entries"] if pc else 0,
            "accountedBytes": accounted,
            "allocator": alloc,
            "hbmDriftBytes": (alloc["bytesInUse"] - accounted)
            if alloc is not None else None,
            "wasteByRep": waste_by_rep,
            "byField": fields[:max(0, int(top))] if top else fields,
            "byFieldTruncated": bool(top) and len(fields) > int(top),
            "otherKinds": other,
            "pinSet": pins,
        }

    # ------------------------------------------------------------- EXPLAIN

    def explain_call(self, index: Index, call: Call, shards) -> dict:
        """?explain=true: the planned tree — per-operand representation,
        sizing statistics, predicted kernel family, per-leaf residency
        state and estimated h2d bytes — WITHOUT dispatching a single
        device program or mutating planner state. The walk mirrors
        _compile's leaf discovery exactly; representation choices use
        choose_representation's peek mode, so a subsequent execution of
        the same query makes the same choices (pinned by the EXPLAIN
        parity fuzz in tests/test_device_obs.py)."""
        from pilosa_tpu import planner as _planner
        from pilosa_tpu.constants import EXISTENCE_FIELD_NAME
        from pilosa_tpu.utils.profile import truncate_pql
        shards = self._query_shards(index, shards)
        shards_t = tuple(shards)
        info = None
        planned = call
        if self.planner is not None and call.name in _planner.PLANNED_CALLS:
            planned, info = self.planner.plan_call(index, call, shards)
        leaf_reps: list[str] = []

        def probe_residency(field_name: str, view_name: str, row_id: int,
                            gens: tuple) -> dict:
            kinds = self._LEAF_KIND_REP

            def match(key: tuple, need_gens: bool) -> bool:
                return (isinstance(key, tuple) and len(key) >= 7
                        and key[0] in kinds
                        and key[1] == index.name and key[2] == field_name
                        and key[3] == view_name and key[4] == row_id
                        and key[5] == shards_t
                        and (not need_gens or key[-1] == gens))

            hit = self.residency.probe_where(lambda k: match(k, True))
            if hit is not None:
                return {"resident": True, "rep": kinds[hit[0][0]],
                        "generationMatch": True, "bytes": hit[1]}
            hit = self.residency.probe_where(lambda k: match(k, False))
            if hit is not None:
                # same row, stale generations: a write landed since the
                # upload — the entry will never be hit again and ages out
                return {"resident": True, "rep": kinds[hit[0][0]],
                        "generationMatch": False, "bytes": hit[1]}
            return {"resident": False, "rep": None,
                    "generationMatch": False}

        def est_bytes(rep: str, slots: int) -> int:
            if rep == "sparse":
                return len(shards) * slots * 4
            if rep == "run":
                return len(shards) * 2 * slots * 4
            return len(shards) * WORDS * 4

        def explain_row(field_name: str, row_id: int, c: Optional[Call],
                        expr: str) -> dict:
            stats: dict = {}
            rep, slots, gens = _planner.choose_representation(
                self, index, c, field_name, VIEW_STANDARD, shards, row_id,
                peek=True, stats_out=stats)
            leaf_reps.append(rep)
            res = probe_residency(field_name, VIEW_STANDARD, row_id, gens)
            return {
                "kind": "row", "expr": expr, "field": field_name,
                "rowId": row_id, "rep": rep, "slots": slots,
                "maxShardCardinality": stats.get("maxShardCardinality"),
                "runIntervals": stats.get("runIntervals"),
                "residency": res,
                "estimatedH2dBytes":
                    0 if res["resident"] and res["generationMatch"]
                    else est_bytes(rep, slots),
            }

        def row_leaf(c: Call) -> dict:
            field_name = c.field_arg()
            row_val = c.args[field_name]
            f = index.field(field_name)
            if f is None:
                raise ExecutionError(f"field not found: {field_name}")
            row_id = self._translate_row(index, f, row_val, create=False)
            expr = truncate_pql(c.to_pql(), 96)
            if row_id is None:
                leaf_reps.append("dense")
                return {"kind": "row", "expr": expr, "field": field_name,
                        "rowId": None, "empty": True, "rep": "dense",
                        "residency": {"resident": False, "rep": None,
                                      "generationMatch": False},
                        "estimatedH2dBytes": 0}
            if f.options.type == FieldType.BOOL and isinstance(row_val,
                                                               bool):
                row_id = 1 if row_val else 0
            return explain_row(field_name, row_id, c, expr)

        def range_leaf(c: Call) -> dict:
            expr = truncate_pql(c.to_pql(), 96)
            if "_start" in c.args or "_end" in c.args:
                field_name = c.field_arg()
                f = index.field(field_name)
                if f is None:
                    raise ExecutionError(f"field not found: {field_name}")
                # create=False: EXPLAIN must never mint row ids
                row_id = self._translate_row(index, f, c.args[field_name],
                                             create=False)
                leaf_reps.append("dense")
                if row_id is None:
                    return {"kind": "timerange", "expr": expr,
                            "field": field_name, "rowId": None,
                            "empty": True, "rep": "dense",
                            "residency": {"resident": False, "rep": None,
                                          "generationMatch": False},
                            "estimatedH2dBytes": 0}
                start, end = c.args.get("_start"), c.args.get("_end")
                if not isinstance(start, datetime) \
                        or not isinstance(end, datetime):
                    raise ExecutionError(
                        "Range() requires start and end timestamps")
                views = tuple(timequantum.views_by_time_range(
                    VIEW_STANDARD, start, end, f.options.time_quantum))
                gens = tuple(self._leaf_gens(index, field_name, v, shards,
                                             row_id) for v in views)
                key = ("timerange", index.name, field_name, row_id, views,
                       shards_t, gens)
                nbytes = self.residency.probe(key)
                return {"kind": "timerange", "expr": expr,
                        "field": field_name, "rowId": row_id,
                        "views": len(views), "rep": "dense",
                        "kernelFamily": "bitwise",
                        "residency": {"resident": nbytes is not None,
                                      "rep": "dense"
                                      if nbytes is not None else None,
                                      "generationMatch": nbytes is not None},
                        "estimatedH2dBytes":
                            0 if nbytes is not None
                            else len(shards) * WORDS * 4}
            cond_field, cond = None, None
            for k, v in c.args.items():
                if isinstance(v, Condition):
                    cond_field, cond = k, v
            if cond is None:
                raise ExecutionError(
                    "Range() requires a condition or time bounds")
            f = self._bsi_field(index, cond_field)
            depth = f.bit_depth
            leaf_reps.append("dense")
            gens = tuple(self._leaf_gens(index, cond_field, f.bsi_view_name,
                                         shards, r)
                         for r in range(depth + 1))
            val = cond.value if not isinstance(cond.value, list) \
                else tuple(cond.value)
            key = ("bsicmp", index.name, cond_field, cond.op, val, depth,
                   shards_t, gens)
            nbytes = self.residency.probe(key)
            return {"kind": "bsicmp", "expr": expr, "field": cond_field,
                    "op": cond.op, "bitDepth": depth, "rep": "dense",
                    "kernelFamily": "bsi", "composedOnDevice": True,
                    "residency": {"resident": nbytes is not None,
                                  "rep": "dense"
                                  if nbytes is not None else None,
                                  "generationMatch": nbytes is not None},
                    # a miss re-composes from the BSI planes: depth+1
                    # plane uploads when those are cold too (upper bound)
                    "estimatedH2dBytes":
                        0 if nbytes is not None
                        else (depth + 1) * len(shards) * WORDS * 4}

        def existence_leaf() -> dict:
            if index.existence_field() is None:
                raise ExecutionError(
                    f"index {index.name} does not support existence "
                    f"tracking")
            return explain_row(EXISTENCE_FIELD_NAME, 0, None,
                               f"Not() existence ({EXISTENCE_FIELD_NAME})")

        def walk(c: Call) -> dict:
            if c.name == "Row":
                return row_leaf(c)
            if c.name == "Range":
                return range_leaf(c)
            if c.name in ("Union", "Xor", "Intersect", "Difference"):
                return {"kind": "op", "op": c.name,
                        "children": [walk(ch) for ch in c.children]}
            if c.name == "Not":
                if len(c.children) != 1:
                    raise ExecutionError("Not() takes exactly one argument")
                return {"kind": "op", "op": "Not",
                        "children": [existence_leaf(),
                                     walk(c.children[0])]}
            raise ExecutionError(f"expected bitmap call, got {c.name}")

        doc: dict = {"call": call.name, "shards": len(shards),
                     "planned": info is not None}
        if info is not None:
            doc["plan"] = info
        if planned.name in _planner.BITMAP_CALLS:
            doc["tree"] = walk(planned)
        else:
            operands = [walk(ch) for ch in planned.children
                        if ch.name in _planner.BITMAP_CALLS]
            if operands:
                doc["tree"] = operands[0] if len(operands) == 1 \
                    else {"kind": "op", "op": "operands",
                          "children": operands}
        # predicted kernel family per row leaf, decided tree-wide: an
        # all-dense program takes the runner's fused path; any hybrid
        # leaf routes evaluation through the sparse/run kernel families
        all_dense = all(r == "dense" for r in leaf_reps)
        fam_of = {"dense": "bitwise" if not all_dense else "program",
                  "sparse": "sparse", "run": "run"}

        def fill_family(node: dict) -> None:
            if node.get("kind") == "op":
                for ch in node.get("children", ()):
                    fill_family(ch)
            elif "kernelFamily" not in node and "rep" in node:
                node["kernelFamily"] = fam_of.get(node["rep"], "bitwise")

        if "tree" in doc:
            fill_family(doc["tree"]
                        if isinstance(doc["tree"], dict) else {})
            est = 0

            def sum_bytes(node: dict) -> None:
                nonlocal est
                if node.get("kind") == "op":
                    for ch in node.get("children", ()):
                        sum_bytes(ch)
                else:
                    est += int(node.get("estimatedH2dBytes") or 0)

            sum_bytes(doc["tree"])
            doc["estimatedH2dBytes"] = est
        return doc

    def _compile(self, index: Index, call: Call, shards: list[int]):
        """_compile_tree under the `leaves` span: residency lookups, and on
        a first touch the leaf's build and upload (its child spans)."""
        with tracing.span("leaves"):
            return self._compile_tree(index, call, shards)

    def _compile_tree(self, index: Index, call: Call, shards: list[int]):
        """Walk the call tree -> (program, leaves, kinds) where leaves are
        HBM-resident device arrays from the residency manager and kinds[i]
        marks leaf i "dense" ([S, W] uint32 plane), "sparse" ([S, slots]
        int32 sorted-index array) or "run" ([S, 2, slots] int32 interval
        pairs) — the hybrid representation the planner chose per row."""
        from pilosa_tpu import planner as _planner
        leaves: list = []
        kinds: list = []
        shards_t = tuple(shards)
        # (field, view) -> row leaves resolved: their reads are charged to
        # the heat tracker once a request, below
        reads: dict = {}

        def leaf(key: tuple, make):
            leaves.append(self.residency.leaf(key, make))
            kinds.append("dense")
            return ("leaf", len(leaves) - 1)

        def leaf_arr(arr, kind: str = "dense"):
            leaves.append(arr)
            kinds.append(kind)
            return ("leaf", len(leaves) - 1)

        def hybrid_leaf(c: Optional[Call], field_name: str, row_id: int):
            rep, slots, gens = _planner.choose_representation(
                self, index, c, field_name, VIEW_STANDARD, shards_t, row_id)
            pair = (field_name, VIEW_STANDARD)
            reads[pair] = reads.get(pair, 0) + 1
            if rep == "sparse":
                return leaf_arr(self._row_leaf_sparse_dev(
                    index, field_name, VIEW_STANDARD, shards_t, row_id,
                    gens, slots), "sparse")
            if rep == "run":
                return leaf_arr(self._row_leaf_run_dev(
                    index, field_name, VIEW_STANDARD, shards_t, row_id,
                    gens, slots), "run")
            return leaf_arr(self._row_leaf_at(
                index, field_name, VIEW_STANDARD, shards_t, row_id, gens))

        def row_leaf(c: Call):
            field_name = c.field_arg()
            row_val = c.args[field_name]
            f = index.field(field_name)
            if f is None:
                raise ExecutionError(f"field not found: {field_name}")
            row_id = self._translate_row(index, f, row_val, create=False)
            if row_id is None:  # unknown key: empty row, no id minting
                return leaf(("zeros", len(shards)),
                            lambda: np.zeros((len(shards), WORDS), dtype=np.uint32))
            if f.options.type == FieldType.BOOL and isinstance(row_val, bool):
                row_id = 1 if row_val else 0
            return hybrid_leaf(c, field_name, row_id)

        def range_leaf(c: Call):
            if "_start" in c.args or "_end" in c.args:
                field_name = c.field_arg()
                f = index.field(field_name)
                if f is None:
                    raise ExecutionError(f"field not found: {field_name}")
                row_id = self._translate_row(index, f, c.args[field_name])
                start, end = c.args.get("_start"), c.args.get("_end")
                if not isinstance(start, datetime) or not isinstance(end, datetime):
                    raise ExecutionError("Range() requires start and end timestamps")
                views = tuple(timequantum.views_by_time_range(
                    VIEW_STANDARD, start, end, f.options.time_quantum))
                gens = tuple(self._leaf_gens(index, field_name, v, shards, row_id)
                             for v in views)
                key = ("timerange", index.name, field_name, row_id, views,
                       shards_t, gens)
                return leaf(key, lambda: self._materialize_range_call(index, c, shards))
            # BSI condition: the comparison result row is itself a leaf
            cond_field, cond = None, None
            for k, v in c.args.items():
                if isinstance(v, Condition):
                    cond_field, cond = k, v
            if cond is None:
                raise ExecutionError("Range() requires a condition or time bounds")
            f = self._bsi_field(index, cond_field)
            depth = f.bit_depth
            gens = tuple(self._leaf_gens(index, cond_field, f.bsi_view_name,
                                         shards, r) for r in range(depth + 1))
            val = cond.value if not isinstance(cond.value, list) else tuple(cond.value)
            key = ("bsicmp", index.name, cond_field, cond.op, val, depth,
                   shards_t, gens)
            return leaf(key, lambda: self._bsi_compare_dev(
                index, cond_field, cond, shards))

        def existence_leaf():
            from pilosa_tpu.constants import EXISTENCE_FIELD_NAME
            if index.existence_field() is None:
                raise ExecutionError(
                    f"index {index.name} does not support existence tracking")
            # the existence row is the archetypal run-container row (long
            # contiguous column ranges) — route it through the planner's
            # representation choice so it can upload as interval pairs
            return hybrid_leaf(None, EXISTENCE_FIELD_NAME, 0)

        def walk(c: Call):
            if c.name == "Row":
                return row_leaf(c)
            if c.name == "Range":
                return range_leaf(c)
            if c.name in ("Union", "Xor"):
                # zero-arg Union()/Xor() = empty row (executor.go:1446,
                # 1468: NewRow() with no children to fold in)
                if not c.children:
                    return leaf(("zeros", len(shards)), lambda: np.zeros(
                        (len(shards), WORDS), dtype=np.uint32))
                op = "or" if c.name == "Union" else "xor"
                return (op, *[walk(ch) for ch in c.children])
            if c.name == "Intersect":
                if not c.children:
                    from pilosa_tpu.planner import empty_operand_error
                    raise empty_operand_error(c)
                return ("and", *[walk(ch) for ch in c.children])
            if c.name == "Difference":
                if not c.children:  # executor.go:835
                    from pilosa_tpu.planner import empty_operand_error
                    raise empty_operand_error(c)
                return ("andnot", *[walk(ch) for ch in c.children])
            if c.name == "Not":
                if len(c.children) != 1:
                    raise ExecutionError("Not() takes exactly one argument")
                # Not = existence &~ child (executor.go:1478-1520)
                ex = existence_leaf()
                return ("andnot", ex, walk(c.children[0]))
            raise ExecutionError(f"expected bitmap call, got {c.name}")

        program = walk(call)
        self._heat_charge(index, reads, shards_t, reads=1)
        if not leaves:
            leaves.append(self.residency.leaf(
                ("zeros", len(shards)),
                lambda: np.zeros((len(shards), WORDS), dtype=np.uint32)))
            kinds.append("dense")
        return program, leaves, kinds

    def _composed_row_dev(self, index: Index, call: Call, shards):
        """Device [S', W] result of a bitmap call tree, through the
        generation-keyed plan cache: overlapping queries (many dashboard
        users sharing a filter subtree) reuse the HBM-resident evaluated
        result instead of recomputing it. On a miss the composed result is
        inserted under the planner's canonical key; a write under the
        subtree changes the key on the next lookup (free invalidation)."""
        import time as _time

        from pilosa_tpu import planner as _planner
        from pilosa_tpu.utils import accounting
        shards = tuple(shards)  # the tuple every key below holds, once
        key = None
        pc = self.plan_cache
        if (pc is not None and pc.enabled
                and call.name in _planner.BITMAP_CALLS
                and not _planner.is_empty_call(call)):
            with tracing.span("plan"):
                key = _planner.subtree_cache_key(self, index, call, shards)
        heat_on = self.heat is not None and self.heat.enabled
        epoch = 0
        if key is not None:
            with tracing.span("plan"):
                epoch = pc.epoch
                hit = pc.get(key)
                _planner.record_cache_event(call, hit is not None)
            if hit is not None:
                if heat_on:
                    # a cached read still HEATS its operands: the hit
                    # never reaches _row_leaf_dev, but the caller wanted
                    # exactly these fragments hot — reuse is the
                    # strongest pin signal the advisor has
                    self._heat_call_touch(index, call, shards, reads=1)
                return hit
        acct = accounting.current_account.get()
        t0 = _time.perf_counter() if (acct is not None or heat_on) else 0.0
        program, leaves, kinds = self._compile(index, call, shards)
        # asynchronous launches: the consumer's fetch waits for them
        with tracing.span("dispatch"):
            dev = self._eval_program_dense(program, leaves, kinds)
        if acct is not None or heat_on:
            # the composed-subtree evaluation is per-query device work the
            # batchers never see — charged as wall time of the compile +
            # dispatch (the attribution available without a device sync)
            elapsed_ms = (_time.perf_counter() - t0) * 1e3
            if acct is not None:
                acct.charge(device_ms=elapsed_ms)
            if heat_on:
                # attributed device-ms per fragment (split evenly across
                # the operand coordinates — the dispatch-share convention)
                self._heat_call_touch(index, call, shards,
                                      device_ms=elapsed_ms)
        if key is not None:
            pc.put(key, dev, dev.nbytes, epoch=epoch)
        return dev

    def _eval_program_dense(self, program, leaves, kinds):
        """Dense [S', W] result of a compiled program. All-dense programs
        take the runner's fused path (XLA jit / ICI shard_map);
        hybrid programs evaluate through the sparse/run kernel families
        and materialize the root to a plane only if it is still sparse or
        run — downstream consumers (plan cache, Row segments, BSI/GroupBy
        filter folds) all expect planes."""
        if "sparse" not in kinds and "run" not in kinds:
            return self.runner.row_leaves_dev(leaves, program)
        from pilosa_tpu.ops import bitvector as bv
        kind, arr = bv.eval_hybrid(program, leaves, kinds, WORDS)
        if kind == "sparse":
            self.hybrid.record_materialize()
            return bv.sparse_to_dense(arr, WORDS)
        if kind == "run":
            self.hybrid.record_materialize()
            return bv.run_to_dense(arr, WORDS)
        return arr

    def _heat_call_touch(self, index: Index, call: Call, shards,
                         reads: int = 0, device_ms: float = 0.0) -> None:
        """Charge a bitmap call tree's operand fragments (the plan-cache
        hit path and the composed-dispatch device-ms attribution). The
        walk mirrors _compile's leaf discovery at fragment granularity:
        Row -> standard view, BSI Range -> the bsig_ view, time Range
        approximated at the standard view (the per-quantum expansion is
        not worth a second full walk on a hit path), Not -> existence."""
        from pilosa_tpu.constants import EXISTENCE_FIELD_NAME
        tracker = self.heat
        if tracker is None or not tracker.enabled:
            return
        pairs: dict = {}  # (field, view) -> leaves of the tree on it

        def note(field_name: str, view_name: str) -> None:
            pair = (field_name, view_name)
            pairs[pair] = pairs.get(pair, 0) + 1

        def walk(c: Call) -> None:
            if c.name == "Row":
                note(c.field_arg(), VIEW_STANDARD)
            elif c.name == "Range":
                cond_field = None
                for k, v in c.args.items():
                    if isinstance(v, Condition):
                        cond_field = k
                if cond_field is not None:
                    note(cond_field, "bsig_" + cond_field)
                else:
                    fa = c.field_arg()
                    if fa:
                        note(fa, VIEW_STANDARD)
            elif c.name == "Not":
                note(EXISTENCE_FIELD_NAME, VIEW_STANDARD)
            for ch in c.children:
                walk(ch)

        walk(call)
        self._heat_charge(index, pairs, tuple(shards), reads=reads,
                          device_ms=device_ms)

    def _heat_charge(self, index: Index, pairs: dict, shards_t: tuple,
                     reads: int = 0, device_ms: float = 0.0) -> None:
        """Charge the fragments under `pairs` ((field, view) -> leaves on
        it) in ONE round trip of the tracker's lock: a fragment is read
        `reads` times a leaf on it, and `device_ms` is split a leaf and
        then a shard, which is what a touch a leaf gave each fragment.
        The coordinates come memoised (RowStatsMemo.frag_keys): no list
        a leaf, no walk over the shards."""
        tracker = self.heat
        if not pairs or tracker is None or not tracker.enabled:
            return
        n_leaves = sum(pairs.values())
        tracker.touch_groups([
            (self.row_stats.frag_keys(index.name, f, v, shards_t),
             reads * n, device_ms * n / n_leaves)
            for (f, v), n in pairs.items()])

    def _execute_bitmap_call(self, index: Index, call: Call, shards) -> Row:
        from pilosa_tpu import planner as _planner
        shards = self._query_shards(index, shards)
        if _planner.is_empty_call(call):
            # planner short-circuit: provably empty — no leaf
            # materialization, no device dispatch
            return Row()
        dense = np.asarray(
            self._composed_row_dev(index, call, shards))[:len(shards)]
        out = Row()
        n_cols = 0
        for i, shard in enumerate(shards):
            cols = columns_from_dense(dense[i])
            if cols.size:
                n_cols += cols.size
                out.segments[shard] = cols.astype(np.uint64) + np.uint64(shard * SHARD_WIDTH)
        self._record_actual(n_cols)
        # top-level Row() results carry the row's attrs (executeBitmapCall
        # attaches them from the row attr store, executor.go:1173-1208)
        if call.name == "Row":
            f = index.field(call.field_arg())
            if f is not None:
                row_id = self._translate_row(index, f,
                                             call.args[call.field_arg()],
                                             create=False)
                if row_id is not None:
                    attrs = f.row_attrs.attrs(row_id)
                    if attrs:
                        out.attrs = attrs
        return out

    # programs the continuous batcher can coalesce (batcher.py): a bare
    # leaf or one binary op over two leaves — the dominant Count shapes
    _BATCHABLE_OPS = ("and", "or", "xor", "andnot")

    def _execute_count(self, index: Index, call: Call, shards) -> int:
        if len(call.children) != 1:
            raise ExecutionError("Count() takes exactly one argument")
        from pilosa_tpu import planner as _planner
        from pilosa_tpu.parallel.residency import PlanCache
        child = call.children[0]
        if _planner.is_empty_call(child):
            # planner short-circuit: zero leaves uploaded, zero dispatches
            return 0
        # the tuple every key below holds, built here and passed down
        shards = tuple(self._query_shards(index, shards))
        key = None
        epoch = 0
        pc = self.plan_cache
        if (pc is not None and pc.enabled
                and child.name in _planner.BITMAP_CALLS):
            with tracing.span("plan"):
                key = _planner.subtree_cache_key(self, index, child, shards)
                cached = None
                if key is not None:
                    key = ("count",) + key  # scalar value, distinct from
                    # the dense row result of the same subtree
                    epoch = pc.epoch
                    cached = pc.get(key)
                    _planner.record_cache_event(child, cached is not None)
            if cached is not None:
                # cached Counts heat their operands too (see
                # _composed_row_dev: reuse is still access)
                self._heat_call_touch(index, child, shards, reads=1)
                self._record_actual(cached)
                return cached
        n = self._count_device(index, child, shards)
        if key is not None:
            with tracing.span("plan"):
                pc.put(key, int(n), PlanCache.SCALAR_COST, epoch=epoch)
        self._record_actual(n)
        return n

    @staticmethod
    def _record_actual(count) -> None:
        """Actual result cardinality into the executing call's plan node —
        the profiler's estimated-vs-actual comparison (?profile=true)."""
        from pilosa_tpu import planner as _planner
        plan = _planner.current_plan.get()
        if plan is not None:
            plan["actualCardinality"] = int(count)

    def _count_device(self, index: Index, child: Call, shards) -> int:
        from pilosa_tpu.utils import accounting
        program, leaves, kinds = self._compile(index, child, shards)
        if "sparse" in kinds or "run" in kinds:
            # hybrid program: count through the sparse/run kernel
            # families — a sparse root counts its live slots, a run root
            # sums its interval lengths, with no plane ever materialized
            # (the hybrid-count pushdown). Skips the batcher and the
            # dense chain kernel, which both assume uint32 planes.
            from pilosa_tpu.ops import bitvector as bv

            def launch():
                return bv.hybrid_count_dev(program, leaves, kinds)
        elif self.batcher is not None and (
                shape := self._batchable(program, leaves)) is not None:
            # concurrent Counts coalesce into one device dispatch
            # (continuous batching — parallel/batcher.py; the batcher's
            # _run charges each co-batched query its wall-time share,
            # and their heat was already charged in _compile_tree)
            return self.batcher.count(*shape)
        elif (isinstance(program, tuple) and len(program) > 3
                and program[0] == "and"
                and all(p == ("leaf", i) for i, p in enumerate(program[1:]))
                and len({l.shape for l in leaves}) == 1):
            # the planner's Count(Intersect(...)) pushdown on 3+-way
            # chains: one fused AND+popcount dispatch keyed on chain
            # arity, so cardinality-reordered chains of the same width
            # share a compilation (ops/bitvector.py)
            from pilosa_tpu.ops.bitvector import intersect_chain_count_total

            def launch():
                return self.runner.collective(
                    intersect_chain_count_total, tuple(leaves))
        else:
            # on a mesh a psum program, launched like the chain's on the
            # one collective thread (parallel/mesh.py)
            def launch():
                return self.runner.count_total_leaves_dev(leaves, program)
        # un-batched dispatches are this query's alone: `dispatch` is the
        # host enqueueing the programs (asynchronous launches, which still
        # block while the runtime's in-flight queue is full: on a busy
        # device most of the wait is booked here), `device.wait` the one
        # fetch that blocks on them, and the two together are the wall
        # charged to the caller's account and the operands' heat
        with tracing.span("dispatch") as enqueue:
            handle = launch()
        with tracing.span("device.wait") as wait:
            partials = np.asarray(handle)
        with tracing.span("reduce"):
            # per-shard (or whole) int32 partials, finished exactly on host
            n = int(partials.sum(dtype=np.int64))
        elapsed_ms = enqueue.ms + wait.ms
        acct = accounting.current_account.get()
        if acct is not None:
            acct.charge(device_ms=elapsed_ms)
        if self.heat is not None and self.heat.enabled:
            self._heat_call_touch(index, child, shards, device_ms=elapsed_ms)
        return n

    def _batchable(self, program, leaves: list):
        """(op, a, b) for the programs the continuous batcher coalesces —
        a bare leaf, or one binary op over two leaves of one shape — else
        None."""
        if program == ("leaf", 0) and len(leaves) == 1:
            return "id", leaves[0], None
        if (len(leaves) == 2 and isinstance(program, tuple)
                and len(program) == 3
                and program[0] in self._BATCHABLE_OPS
                and program[1] == ("leaf", 0)
                and program[2] == ("leaf", 1)
                and leaves[0].shape == leaves[1].shape):
            return program[0], leaves[0], leaves[1]
        return None

    # ------------------------------------------------- leaf materialization

    def _cached_row(self, index: Index, field_name: str, view_name: str,
                    shard: int, row_id: int) -> np.ndarray:
        f = index.field(field_name)
        view = f.view(view_name) if f else None
        frag = view.fragment(shard) if view else None
        if frag is None:
            return np.zeros(WORDS, dtype=np.uint32)
        key = (index.name, field_name, view_name, shard, row_id,
               frag.row_generation(row_id))
        cached = self._row_cache.get(key)
        if cached is None:
            epoch = self._row_cache_epoch
            cached = frag.row_dense(row_id)
            if self._row_cache_epoch == epoch:
                # same fence as DeviceResidency: a clear_caches() that lands
                # while row_dense() is in flight means this row may belong
                # to a deleted field whose recreation could reach an
                # identical generation tuple — serve it, don't cache it
                self._row_cache[key] = cached
        return cached

    def _materialize_range_call(self, index: Index, c: Call, shards) -> np.ndarray:
        # time range: Range(f=row, start, end) (executor.go executeRange)
        if "_start" in c.args or "_end" in c.args:
            field_name = c.field_arg()
            f = index.field(field_name)
            if f is None:
                raise ExecutionError(f"field not found: {field_name}")
            row_id = self._translate_row(index, f, c.args[field_name])
            start, end = c.args.get("_start"), c.args.get("_end")
            if not isinstance(start, datetime) or not isinstance(end, datetime):
                raise ExecutionError("Range() requires start and end timestamps")
            views = timequantum.views_by_time_range(
                VIEW_STANDARD, start, end, f.options.time_quantum)
            out = np.zeros((len(shards), WORDS), dtype=np.uint32)
            for vname in views:
                for i, s in enumerate(shards):
                    out[i] |= self._cached_row(index, field_name, vname, s, row_id)
            return out
        # BSI condition: Range(f < 10) etc.
        cond_field, cond = None, None
        for k, v in c.args.items():
            if isinstance(v, Condition):
                cond_field, cond = k, v
        if cond is None:
            raise ExecutionError("Range() requires a condition or time bounds")
        return self._bsi_compare(index, cond_field, cond, shards)

    # ------------------------------------------------------------- BSI ops

    def _bsi_field(self, index: Index, field_name: str):
        f = index.field(field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        if f.options.type != FieldType.INT:
            raise ExecutionError(f"field {field_name} is not an int field")
        return f

    def _bsi_planes(self, index: Index, f, shards):
        """(planes[depth, S', W], exists[S', W]) device arrays for an int
        field, assembled by stacking HBM-resident plane leaves on device
        (S' = S padded to the mesh size; pad shards are all-zero so every
        BSI kernel sees them as empty). The stacked slab is itself cached
        in the residency manager keyed by the plane generations, so repeat
        aggregations reuse one HBM slab — no host memory, no restack."""
        depth = f.bit_depth
        vname = f.bsi_view_name
        exists = self._row_leaf_dev(index, f.name, vname, shards, depth)
        gens = tuple(self._leaf_gens(index, f.name, vname, shards, i)
                     for i in range(depth))
        key = ("bsiplanes", index.name, f.name, depth, tuple(shards), gens)
        # the stack is built from HOST rows so the per-plane leaves don't
        # also occupy residency budget — only the slab (what the kernels
        # read) is cached; on a mesh the runner shards the [depth, S', W]
        # slab over the shard axis like any leaf batch
        planes = self.residency.leaf(key, lambda: self.runner.put_plane_slab(
            np.stack([
                np.stack([self._cached_row(index, f.name, vname, s, i)
                          for s in shards])
                for i in range(depth)])))
        return planes, exists

    def _bsi_compare(self, index: Index, field_name: str, cond: Condition,
                     shards) -> np.ndarray:
        """Host [S, W] comparison mask — only for results that leave the
        device (top-level Range -> Row columns). Query composition uses
        _bsi_compare_dev, which never round-trips the mask through the
        host (megabytes per query on a high-latency device link)."""
        s = len(shards)
        return np.asarray(self._bsi_compare_dev(
            index, field_name, cond, shards))[:s]

    def _bsi_compare_dev(self, index: Index, field_name: str,
                         cond: Condition, shards):
        """Device [S', W] mask of columns satisfying `cond` — computed and
        LEFT in HBM (one fused comparison-sweep dispatch, zero fetches)."""
        f = self._bsi_field(index, field_name)
        planes, exists = self._bsi_planes(index, f, shards)
        depth = f.bit_depth
        op = cond.op

        def fetch(dev):  # composition stays on device
            return dev

        def empty():
            return self.runner.put_leaf(
                np.zeros((len(shards), WORDS), dtype=np.uint32))

        # != null -> not-null row (executor.go:1344)
        if op == NEQ and cond.value is None:
            return fetch(exists)

        import jax
        if op == BETWEEN:
            lo, hi = cond.int_slice_value()
            # clamp to field range (baseValueBetween, field.go:1410)
            if hi < f.options.min or lo > f.options.max:
                return empty()
            if lo <= f.options.min and hi >= f.options.max:
                return fetch(exists)
            blo = max(lo - f.base, 0)
            bhi = min(hi, f.options.max) - f.base
            dlo = bsi_ops.compare(planes, exists,
                                  bsi_ops.value_to_bits(blo, depth),
                                  bsi_ops.GTE)
            dhi = bsi_ops.compare(planes, exists,
                                  bsi_ops.value_to_bits(bhi, depth),
                                  bsi_ops.LTE)
            return fetch(jax.numpy.bitwise_and(dlo, dhi))

        value = cond.value
        if isinstance(value, bool) or not isinstance(value, int):
            raise ExecutionError("Range(): conditions only support integer values")
        op_map = {LT: bsi_ops.LT, LTE: bsi_ops.LTE, GT: bsi_ops.GT,
                  GTE: bsi_ops.GTE, EQ: bsi_ops.EQ, NEQ: bsi_ops.NEQ}
        if op not in op_map:
            raise ExecutionError(f"unsupported condition op: {op}")
        # out-of-range clamps (baseValue, field.go:1385)
        if op in (GT, GTE) and value > f.options.max:
            return empty()
        if op in (LT, LTE) and value < f.options.min:
            return empty()
        if op in (EQ,) and (value < f.options.min or value > f.options.max):
            return empty()
        if op == NEQ and (value < f.options.min or value > f.options.max):
            return fetch(exists)
        if (op == LT and value > f.options.max) or (op == LTE and value >= f.options.max):
            return fetch(exists)
        if (op == GT and value < f.options.min) or (op == GTE and value <= f.options.min):
            return fetch(exists)
        base_value = min(max(value - f.base, 0), f.options.max - f.base)
        pred = bsi_ops.value_to_bits(base_value, depth)
        return fetch(bsi_ops.compare(planes, exists, pred, op_map[op]))

    def _bsi_filter(self, index: Index, call: Call, shards):
        """Optional filter child for Sum/Min/Max — a device array [S', W]
        composed in HBM (no host round trip), via the plan cache so
        dashboards sharing one filter subtree compose it once."""
        if not call.children:
            return None
        return self._composed_row_dev(index, call.children[0], shards)

    def _execute_sum(self, index: Index, call: Call, shards) -> ValCount:
        import jax.numpy as jnp
        field_name = call.args.get("field")
        if field_name is None:
            raise ExecutionError("Sum(): field required")
        f = self._bsi_field(index, field_name)
        shards = self._query_shards(index, shards)
        planes, exists = self._bsi_planes(index, f, shards)
        filt = self._bsi_filter(index, call, shards)
        if filt is not None:
            exists = jnp.bitwise_and(exists, filt)
        if self.sum_batcher is not None:
            # concurrent Sums sharing this plane slab coalesce into one
            # vmapped dispatch (parallel/batcher.py PlaneSumBatcher)
            totals = self.sum_batcher.plane_sums(planes, exists)  # [depth+1]
            counts_per_plane, n = totals[:-1], int(totals[-1])
        else:
            # one dispatch + one fetch: per-plane counts with the exists
            # count packed as the last row (bsi_ops.sum_counts)
            packed = np.asarray(bsi_ops.sum_counts(planes, exists))
            counts_per_plane, n = packed[:-1].sum(axis=1), int(packed[-1].sum())
        raw_sum = bsi_ops.counts_to_sum(counts_per_plane)
        # add base back per counted value (val = raw + base*count)
        return ValCount(val=raw_sum + f.base * n, count=n)

    def _execute_min(self, index: Index, call: Call, shards) -> ValCount:
        return self._execute_min_max(index, call, shards, is_min=True)

    def _execute_max(self, index: Index, call: Call, shards) -> ValCount:
        return self._execute_min_max(index, call, shards, is_min=False)

    def _execute_min_max(self, index: Index, call: Call, shards, is_min: bool) -> ValCount:
        field_name = call.args.get("field")
        if field_name is None:
            raise ExecutionError(f"{'Min' if is_min else 'Max'}(): field required")
        import jax.numpy as jnp
        f = self._bsi_field(index, field_name)
        shards = self._query_shards(index, shards)
        planes, exists = self._bsi_planes(index, f, shards)
        filt = self._bsi_filter(index, call, shards)
        if filt is not None:
            exists = jnp.bitwise_and(exists, filt)
        if self.minmax_batcher is not None:
            # concurrent Min/Max descents sharing this slab coalesce into
            # one vmapped dispatch (parallel/batcher.py MinMaxBatcher)
            packed = self.minmax_batcher.packed(planes, exists, is_min)
        else:
            fn = bsi_ops.bsi_min_packed if is_min else bsi_ops.bsi_max_packed
            packed = np.asarray(fn(planes, exists))  # [depth+1, S'] 1 fetch
        bits, cnt = packed[:-1], packed[-1]
        best_val, best_cnt = None, 0
        for i in range(len(shards)):
            if cnt[i] == 0:
                continue
            v = bsi_ops.bits_to_value(bits[:, i]) + f.base
            if best_val is None or (v < best_val if is_min else v > best_val):
                best_val, best_cnt = v, int(cnt[i])
            elif v == best_val:
                best_cnt += int(cnt[i])
        if best_val is None:
            return ValCount(0, 0)
        return ValCount(best_val, best_cnt)

    # --------------------------------------------------------------- TopN

    def _execute_topn(self, index: Index, call: Call, shards) -> list[tuple[int, int]]:
        """Two-phase TopN (executor.go:694-761) with device ranking kernels
        (ops/topn.py) and the reference's threshold-pruning walk
        (fragment.go:1121-1136): phase 1 ranks rank-cache candidates
        (device recount only when a Src bitmap needs intersection counts),
        phase 2 recounts merged winners exactly — never a full row scan."""
        field_name = call.args.get("_field")
        f = index.field(field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        # explicit n=0 means unlimited, same as omitting it (the reference's
        # opt.N zero value, executor.go:694)
        n = call.uint_arg("n") or None
        shards = self._query_shards(index, shards)

        src_dense = None
        if call.children:
            # [S', W] in HBM, plan-cached: the ranking phases fetch int32
            # count vectors only — the src bitmap never lands on host
            src_dense = self._composed_row_dev(index, call.children[0],
                                               shards)

        ids_arg = call.uint_slice_arg("ids")
        threshold = call.uint_arg("threshold") or 0
        tanimoto = call.uint_arg("tanimotoThreshold") or 0
        attr_name = call.string_arg("attrName")
        attr_values = call.args.get("attrValues")

        # row-attribute candidate filter (topOptions.AttrName/AttrValues,
        # fragment.go:1191-1208; applied :1056-1076, including the RowIDs
        # path). The filter exists only when BOTH name and values are given
        # (fragment.go:1029) — attrName alone is a no-op.
        allowed = None
        if attr_name and attr_values is not None:
            allowed = set(attr_values if isinstance(attr_values, list)
                          else [attr_values])

        if ids_arg is not None:
            # explicit ids / distributed phase-2 recount: exact counts for
            # just these rows. Plain row counts come from HOST container
            # metadata (row().Count() sums container cardinalities — the
            # reference's fragment.top RowIDs path); the device is only
            # needed when an intersection source is in play.
            ids = list(ids_arg)
            if allowed is not None:
                ids = [rid for rid in ids
                       if f.row_attrs.attrs(rid).get(attr_name) in allowed]
            if src_dense is None:
                pairs = self._host_row_counts(index, f, shards, ids)
            else:
                pairs = self._exact_counts(index, f, shards, ids,
                                           src_dense, tanimoto)
        else:
            with tracing.span("topn.candidates"):
                cand_ids, cand_counts = self._topn_candidate_arrays(
                    index, f, shards)
                if allowed is not None:
                    keep = np.fromiter(
                        (f.row_attrs.attrs(int(r)).get(attr_name) in allowed
                         for r in cand_ids), bool, cand_ids.size)
                    cand_ids, cand_counts = cand_ids[keep], cand_counts[keep]
                if threshold:
                    # cached counts bound the final count from above (they
                    # are full row counts; intersection can only shrink
                    # them), so rows under the floor can be dropped before
                    # any recount
                    keep = cand_counts >= threshold
                    cand_ids, cand_counts = cand_ids[keep], cand_counts[keep]
            if src_dense is not None:
                with tracing.span("topn.recount", field=f.name):
                    pairs = self._topn_src_walk(index, f, shards, cand_ids,
                                                cand_counts, src_dense, n,
                                                tanimoto)
            else:
                # cached counts are exact per-shard (write-maintained,
                # view.py:141-147) but a row can be missing from a shard's
                # cache (evicted below the floor), so the merged winners are
                # recounted — on the HOST from container cardinality sums
                # (the reference's two-phase exact recount walks
                # fragment.row().Count(), not dense bits; materializing a
                # dense [S, W] leaf per winner would move MBs for rows that
                # hold a handful of bits)
                winner_ids = cand_ids[:n] if n is not None else cand_ids
                pairs = self._host_row_counts(
                    index, f, shards, winner_ids.tolist())
        with tracing.span("reduce"):
            if threshold:
                pairs = [(i, c) for i, c in pairs if c >= threshold]
            merged = merge_pairs([pairs])
            if n is not None and ids_arg is None:
                merged = merged[:n]
            return Pairs((i, c) for i, c in merged if c > 0)

    def _topn_candidate_arrays(self, index: Index, f, shards):
        """Merged (ids, cached_counts) int64 arrays from per-shard rank
        caches, count-desc — all-numpy (memoized per-cache rank order +
        vectorized reduce; the pure-Python tuple walk dominated TopN p50).
        The cross-shard MERGE is additionally memoized on the per-cache
        versions, so a repeat TopN over unchanged caches is a dict hit.
        A ranked field's missing/empty cache is rebuilt in place
        (guaranteed-present); a cache-less field yields NO candidates,
        matching the reference's nopCache (cache.go:461-481) — the round-1
        full-row-id-scan fallback is gone."""
        from pilosa_tpu.models.cache import merge_pair_arrays

        view = f.view(VIEW_STANDARD)
        if view is None:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        per_shard = []
        versions = []
        for s in shards:
            cache = view.rank_caches.get(s)
            if (cache is None or not len(cache)) and view.track_rank:
                frag = view.fragment(s)
                if frag is not None and frag.bit_count() > 0:
                    view.refresh_rank_cache(s)
                    cache = view.rank_caches.get(s)
            if cache is not None and len(cache):
                # version read BEFORE top_arrays(): a racing write makes
                # the tag stale, never the data sticky (cache.py pattern)
                versions.append((s, cache._version))
                per_shard.append(cache.top_arrays())
        key = (index.name, f.name, tuple(shards))
        vt = tuple(versions)
        with self._topn_memo_lock:
            memo = self._topn_merge_memo.get(key)
            if memo is not None and memo[0] == vt:
                self._topn_merge_memo.move_to_end(key)  # LRU touch
                return memo[1], memo[2]
        ids, counts = merge_pair_arrays(per_shard)
        with self._topn_memo_lock:
            self._topn_merge_memo[key] = (vt, ids, counts)
            self._topn_merge_memo.move_to_end(key)
            while len(self._topn_merge_memo) > 256:  # evict coldest only
                self._topn_merge_memo.popitem(last=False)
        return ids, counts

    def _pairs_entry(self, index: Index, f, shards):
        """The field's resident pairs entry for this shard set, or None
        where the hybrid representation is off: every row whose fullest
        shard holds no more bits than the sparse threshold (the number
        planner.choose_representation goes by; no hysteresis, the entry is
        rebuilt with its generations), in the layout whose recount is the
        faster (ops/bitvector.py pairs_count takes either). By column, one
        rank a column laid bit-major, 4 MiB a shard whatever the rows
        hold: where no column holds two of the rows, the sorted columns
        would take PAIRS_BY_COLUMN_SLOTS slots a shard or more (from
        there the gather by pairs costs more than the pass over every
        column) and 4 MiB a shard fit a quarter of the residency budget.
        By pairs, sorted columns with the row's rank beside each, 8 bytes
        a slot: every other field. One entry a
        (field, view, shard set, fragment generations), built once however
        many threads ask (DeviceResidency.leaf is single-flight), charged
        to the residency budget like any leaf; a write to the field bumps
        a fragment's generation, so the next TopN builds anew and the old
        entry ages out. A field whose small rows would take more than a
        quarter of the budget in either layout gets an entry of no rows,
        and its TopN the dense walk."""
        hyb = self.hybrid
        view = f.view(VIEW_STANDARD)
        if hyb is None or not hyb.active() or view is None:
            return None
        frags = [view.fragment(s) for s in shards]
        gens = tuple(0 if fr is None else fr.generation for fr in frags)
        key = ("pairs", index.name, f.name, VIEW_STANDARD, tuple(shards),
               gens)
        tracker = self.heat
        if tracker is not None and tracker.enabled:
            tracker.touch_many([(index.name, f.name, VIEW_STANDARD, s)
                                for s in shards], reads=1)

        built: dict = {}   # make() -> put(): the entry's host-side half

        def make():
            from pilosa_tpu.ops import bitvector as bv
            with tracing.span("pairs.build"):
                bits = [(np.empty(0, np.int32),) * 2 if fr is None
                        else fr.rows_columns() for fr in frags]
                # the fullest shard of every row, from the same pass: a
                # shard's rows are sorted, so its counts are one diff
                per = []
                for rows, _ in bits:
                    cut = np.flatnonzero(np.diff(rows, prepend=-1))
                    per.append((rows[cut], np.diff(cut, append=rows.size)))
                ids = np.unique(np.concatenate([u for u, _ in per]))
                fullest = np.zeros(ids.size, np.int64)
                for uids, counts in per:
                    at = np.searchsorted(ids, uids)
                    fullest[at] = np.maximum(fullest[at], counts)
                ids = ids[fullest <= hyb.threshold]
                kept = []
                for rows, cols in bits:
                    at = np.searchsorted(ids, rows)
                    hit = at < ids.size
                    hit[hit] = ids[at[hit]] == rows[hit]
                    kept.append((cols[hit], at[hit].astype(np.int32)))
                slots = hyb.pad_slots(max(
                    [c.size for c, _ in kept] + [1]))
                quarter = self.residency.budget // 4
                # by column where that is the faster recount, its 4 MiB a
                # shard fit and the data allows it (no column holds two
                # of the rows); else by pairs, where those fit
                by_column = (
                    slots >= bv.PAIRS_BY_COLUMN_SLOTS
                    and len(shards) * SHARD_WIDTH * 4 <= quarter
                    and all(np.bincount(cols, minlength=1).max(initial=0)
                            <= 1 for cols, _ in kept))
                if not by_column and len(shards) * slots * 8 > quarter:
                    ids, slots = ids[:0], hyb.pad_slots(1)
                    kept = [(np.empty(0, np.int32),) * 2] * len(shards)
                arr = (bv.pairs_by_column(kept) if by_column
                       else bv.pairs_by_pairs(kept, slots))
                stored = np.zeros(ids.size, np.int64)
                for _, rank in kept:
                    stored += np.bincount(rank, minlength=ids.size)
                built["ids"], built["stored"] = ids.astype(np.int64), stored
                return arr

        def put(arr):
            hyb.record_upload("sparse", arr.nbytes)
            entry = PairsEntry(self.runner.put_pairs(arr), built["ids"],
                               built["stored"])
            self.pairs_entries_built += 1
            self.pairs_entry_bytes += entry.nbytes
            return entry

        return self.residency.leaf(key, make, put=put)

    def _pairs_recount(self, entry, n_shards: int, row_ids: np.ndarray,
                       src_dense):
        """Launch the recount of the entry's rows under the filter plane
        (one program, whatever the rows hold); returns fetch() -> int64
        counts of `row_ids`, which all have to be rows of the entry."""
        from pilosa_tpu.ops.bitvector import pairs_count_slots
        at = np.searchsorted(entry.ids, row_ids)
        with tracing.span("dispatch"):
            handle = self.runner.pairs_count(
                entry.dev, src_dense, pairs_count_slots(entry.ids.size))
        self.topn_pairs_recounts += 1
        self.topn_pairs_recounts_by_column += int(entry.by_column)
        self.topn_pairs_bytes += (4 * int(entry.stored[at].sum())
                                  + n_shards * WORDS * 4)

        def fetch() -> np.ndarray:
            with tracing.span("device.wait"):
                counts = np.asarray(handle)
            return counts[at].astype(np.int64)

        return fetch

    def _topn_src_walk(self, index: Index, f, shards,
                       cand_ids: np.ndarray, cand_counts: np.ndarray,
                       src_dense, n, tanimoto: int) -> list[tuple[int, int]]:
        """Phase-1 intersection ranking with the reference's threshold walk
        (fragment.go:1121-1136): recount |row ∩ src| on the device and stop
        once the next cached count — an upper bound on every remaining
        intersection count — cannot beat the current n-th best. Rows above
        the sparse threshold are walked in count-desc blocks of planes,
        counted where they lie (ops/topn kernels); the rows below it are
        recounted all at once from the field's pairs entry, in one launch,
        unless the n-th best so far already beats the largest cached count
        among them. The two count vectors meet in one heap: count
        descending, id ascending, ties as the dense walk alone gives
        them."""
        import heapq

        import jax.numpy as jnp

        from pilosa_tpu.ops.bitvector import popcount
        from pilosa_tpu.ops.topn import leaves_counts_packed

        scount = 0
        if tanimoto:
            # Tanimoto count bounds (fragment.go:1043-1060):
            # tanimoto(a, b) > T/100 requires |b| in
            # (|src|*T/100, |src|*100/T) — rows outside the band are
            # skipped WITHOUT materialization. The band tests EXACT row
            # counts from container metadata, not merged cache counts: a
            # row evicted from one shard's cache undercounts in the merge
            # (executor.py _execute_topn recount rationale) and a stale
            # band test would drop rows whose true tanimoto qualifies.
            with tracing.span("topn.band"):
                with tracing.span("dispatch"):
                    handle = jnp.sum(popcount(src_dense))
                with tracing.span("device.wait"):
                    scount = int(handle)
                lo = scount * tanimoto / 100
                hi = scount * 100 / tanimoto
                exact = self._host_row_count_arr(index, f, shards, cand_ids)
                keep = (exact > lo) & (exact < hi)
                self.topn_band_in += int(cand_ids.size)
                cand_ids, cand_counts = cand_ids[keep], exact[keep]
                self.topn_band_kept += int(cand_ids.size)
        with tracing.span("leaves"):
            entry = self._pairs_entry(index, f, shards)
        small = (np.isin(cand_ids, entry.ids) if entry is not None
                 else np.zeros(cand_ids.size, bool))
        pairs = list(zip(cand_ids[~small].tolist(),
                         cand_counts[~small].tolist()))
        # min-heap of (count, -row_id): evicts lowest count, then largest id,
        # preserving Pairs order (count desc, id asc) at the boundary
        heap: list[tuple[int, int]] = []
        out: list[tuple[int, int]] = []

        def offer(block_pairs) -> None:
            if n is None:
                out.extend(block_pairs)
                return
            for rid, c in block_pairs:
                if c <= 0:
                    continue
                item = (c, -rid)
                if len(heap) < n:
                    heapq.heappush(heap, item)
                elif item > heap[0]:
                    heapq.heapreplace(heap, item)

        CHUNK = _recount_chunk(len(shards))
        for start in range(0, len(pairs), CHUNK):
            qctx.check()  # abort between walk blocks
            block = pairs[start:start + CHUNK]
            if (n is not None and len(heap) >= n
                    and block[0][1] < heap[0][0]):
                break  # threshold prune: no remaining row can reach top n
            with tracing.span("leaves"):
                leaves = self._stackable(self._row_leaves_dev(
                    index, f.name, VIEW_STANDARD, shards,
                    [rid for rid, _ in block]))
            self.topn_recount_rows += len(block)
            # one dispatch, one host fetch of the packed counts, over the
            # leaves where they lie
            with tracing.span("dispatch"):
                handle = self.runner.collective(
                    leaves_counts_packed, leaves, src_dense)
            with tracing.span("device.wait"):
                packed = np.asarray(handle)[:, :len(block)]
            with tracing.span("reduce"):
                # all block counts come back (B int32s — trivial transfer)
                # rather than a device top_k: lax.top_k breaks ties by
                # position (= cached-count order), which would cut a tied
                # smaller row id and violate Pairs order; the host heap's
                # (count, -id) key keeps tie-breaking exact
                counts = packed[0]
                if tanimoto:
                    inter, rcounts = packed[0], packed[1]
                    scount = int(packed[2, 0])
                    # the strict reference mask (ops/topn.tanimoto_mask) on
                    # the fetched counts: 100·inter > T·(union)
                    keep = (100 * inter.astype(np.int64)
                            > tanimoto * (rcounts.astype(np.int64)
                                          + scount - inter))
                    counts = np.where(keep, inter, 0)
                offer([(block[i][0], int(counts[i]))
                       for i in range(len(block))])
        if small.any() and not (
                n is not None and len(heap) >= n
                and int(cand_counts[small].max()) < heap[0][0]):
            # the entry as a whole under the same prune: no launch where
            # no row of it can reach the top n
            qctx.check()
            ids = cand_ids[small]
            inter = self._pairs_recount(entry, len(shards), ids,
                                        src_dense)()
            with tracing.span("reduce"):
                if tanimoto:
                    # cand_counts are exact row counts here (the band
                    # recounted them); STRICT like the dense mask
                    keep = 100 * inter > tanimoto * (
                        cand_counts[small] + scount - inter)
                    inter = np.where(keep, inter, 0)
                if n is not None and ids.size > n:
                    # the entry's own n best by the heap's order (count
                    # descending, id ascending): no other row of it can
                    # be among the n best of all, and the heap sees n
                    # pairs where a grid field has 10,000
                    best = np.lexsort((ids, -inter))[:n]
                    ids, inter = ids[best], inter[best]
                offer(list(zip(ids.tolist(), inter.tolist())))
        if n is None:
            return out
        return [(-nrid, c) for c, nrid in heap]

    @staticmethod
    def _stackable(leaves: list) -> tuple:
        """The leaves of one recount block as a program's operands, padded
        to a multiple of eight with the last one so that blocks of about
        one size share a program (the counts past the block are dropped)."""
        return tuple(leaves + leaves[-1:] * (-len(leaves) % 8))

    def _host_row_count_arr(self, index: Index, f, shards,
                            row_ids) -> np.ndarray:
        """Exact full-row counts from container metadata — one vectorized
        Fragment.row_counts call per shard, zero dense materialization
        (fragment.go top RowIDs path via row().Count())."""
        view = f.view(VIEW_STANDARD)
        totals = np.zeros(len(row_ids), dtype=np.int64)
        if view is not None:
            for s in shards:
                frag = view.fragment(s)
                if frag is not None:
                    totals += frag.row_counts(row_ids)
        return totals

    def _host_row_counts(self, index: Index, f, shards,
                         row_ids: list[int]) -> list[tuple[int, int]]:
        totals = self._host_row_count_arr(index, f, shards, row_ids)
        return [(rid, int(c)) for rid, c in zip(row_ids, totals)]

    def _exact_counts(self, index: Index, f, shards, row_ids: list[int],
                      src_dense, tanimoto: int):
        """Batched device recount of exactly these rows (`ids=`, the
        distributed phase 2): the rows of the field's pairs entry in one
        launch from their sorted columns, the others as HBM-resident row
        leaves counted in chunks; only int32 count vectors leave the chip
        (src_dense, if given, is already a device array [S', W])."""
        from pilosa_tpu.ops.bitvector import popcount
        from pilosa_tpu.ops.topn import leaves_counts_packed
        import jax.numpy as jnp

        ids_arr = np.asarray(row_ids, dtype=np.int64)
        got: dict[int, int] = {}
        fetch_small = None
        if src_dense is not None and ids_arr.size:
            with tracing.span("leaves"):
                entry = self._pairs_entry(index, f, shards)
            small = (np.isin(ids_arr, entry.ids) if entry is not None
                     else np.zeros(ids_arr.size, bool))
            if small.any():
                small_ids = np.unique(ids_arr[small])
                fetch_small = self._pairs_recount(
                    entry, len(shards), small_ids, src_dense)
                row_ids = ids_arr[~small].tolist()
        scount = None
        CHUNK = _recount_chunk(len(shards))
        for start in range(0, len(row_ids), CHUNK):
            qctx.check()  # abort between recount chunks
            chunk = row_ids[start : start + CHUNK]
            with tracing.span("leaves"):
                leaves = self._stackable(self._row_leaves_dev(
                    index, f.name, VIEW_STANDARD, shards, chunk))
            self.topn_recount_rows += len(chunk)
            if src_dense is not None:
                packed = np.asarray(self.runner.collective(
                    leaves_counts_packed, leaves, src_dense
                ))[:, :len(chunk)].astype(np.int64)
                counts, scount = packed[0], int(packed[2, 0])
                if tanimoto:
                    # STRICT like tanimoto_mask / the pairs recount: the
                    # distributed phase-2 recount must agree with phase 1
                    keep = 100 * counts > tanimoto * (packed[1] + scount
                                                      - counts)
                    counts = np.where(keep, counts, 0)
            else:
                counts = np.asarray(popcount(jnp.stack(leaves))).sum(axis=1)
            got.update(zip(chunk, (int(c) for c in counts)))
        if fetch_small is not None:
            inter = fetch_small()
            if tanimoto:
                if scount is None:
                    scount = int(np.asarray(popcount(src_dense)).sum())
                rcounts = self._host_row_count_arr(index, f, shards,
                                                   small_ids)
                keep = 100 * inter > tanimoto * (rcounts + scount - inter)
                inter = np.where(keep, inter, 0)
            got.update(zip(small_ids.tolist(), inter.tolist()))
        return [(int(rid), got[int(rid)]) for rid in ids_arr.tolist()]

    # ------------------------------------------------------- Rows / GroupBy

    def _execute_rows(self, index: Index, call: Call, shards) -> list[int]:
        field_name = call.args.get("_field") or call.args.get("field")
        f = index.field(field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        shards = self._query_shards(index, shards)
        limit = call.uint_arg("limit")
        previous = call.args.get("previous")
        if isinstance(previous, str):
            # keyed paging: previous is a row KEY (rows() RowKey handling,
            # executor.go:2693). An unknown/stale key must ERROR, not
            # silently restart paging from the beginning (the client would
            # re-receive the full result set)
            prev_key = previous
            previous = self._translate_row(index, f, previous, create=False)
            if previous is None:
                raise ExecutionError(f"row key not found: {prev_key!r}")
        else:
            previous = call.uint_arg("previous")  # validated: `previous+1`
            # must not shift semantics for fractional inputs
        column = call.uint_arg("column")
        view = f.view(VIEW_STANDARD)
        out: set[int] = set()
        start = (previous + 1) if previous is not None else 0
        if view is not None:
            for s in shards:
                frag = view.fragment(s)
                if frag is None:
                    continue
                if column is not None:
                    if column // SHARD_WIDTH != s:
                        continue
                    # column probe (fragment.go:2446 filterColumn): only
                    # the candidate container per row is membership-tested
                    out.update(r for r in frag.rows_for_column(column)
                               if r >= start)
                else:
                    # limit pushdown: any row in the global ascending
                    # top-k is inside some shard's ascending top-k, so
                    # the union of per-shard prefixes suffices — at
                    # billion-row scale this is O(shards · k), not
                    # O(total rows) (rows() start/limit semantics,
                    # fragment.go:2000-2138)
                    out.update(frag.row_ids(start=start, limit=limit))
        rows = sorted(out)
        if limit is not None:
            rows = rows[:limit]
        return RowIdentifiers(rows)

    def _execute_group_by(self, index: Index, call: Call, shards) -> list[dict]:
        """GroupBy(Rows(...), ..., limit=, filter=) — cross product of row
        iterators with intersection counts (executor.go:897-1090).

        Single-program redesign of the reference's per-combination iterator
        walk: each Rows axis becomes one HBM-resident [R, S, W] slab (built
        once from host rows, cached by the residency manager), and each
        level of the cross product is evaluated by the cross_count_matrix
        kernel family — counts[P, R] = popcount(prefix ⊗ axis) fused on
        device (ops/bitvector.py; sharded psum form in parallel/mesh.py).
        Prefix slabs are never persisted: each chunk's prefix is
        re-gathered from the component axis slabs and AND-reduced inside
        the fused dispatch, so device memory stays O(P_CHUNK · S · W)
        regardless of how many combinations survive.

        Zero-count pruning runs ON DEVICE (live_from_matrix: jnp.nonzero
        with a static bound + true live count), and chunk dispatches
        PIPELINE: every chunk of a level is enqueued before the first host
        sync, then one jax.device_get fetches the whole level's compact
        (indices, counts) batch — device compute overlaps the link RTT the
        way parallel/batcher.py overlaps executor dispatches, and the host
        pays at most ONE sync per level (groupby_host_syncs asserts it;
        the rare dense chunk whose live set overflows the bound costs one
        extra full-matrix fetch). Groups emit in lexicographic iterator
        order, so `limit` matches the reference's cutoff semantics — and a
        limited final level probes its lex-first chunk before fanning out
        the rest, keeping the old early-exit's compute bound (a probe miss
        costs one extra sync for the remaining chunks)."""
        shards = self._query_shards(index, shards)
        limit = call.uint_arg("limit")
        rows_calls = [c for c in call.children if c.name == "Rows"]
        if not rows_calls:
            raise ExecutionError("GroupBy requires at least one Rows() call")
        # filter: the reference takes it as a NAMED arg (executor.go
        # groupByCall filter); a positional trailing bitmap call is also
        # accepted for convenience
        filt_calls = [c for c in call.children if c.name != "Rows"]
        named_filter = call.args.get("filter")
        if isinstance(named_filter, Call):
            filt_calls.append(named_filter)
        if len(filt_calls) > 1:
            raise ExecutionError("GroupBy supports at most one filter call")
        filter_dev = None
        if filt_calls:
            filter_dev = self._composed_row_dev(index, filt_calls[0],
                                                shards)  # [S', W]

        # per Rows call: (field, [row_ids], device slab [R, S', W])
        axes = []
        with tracing.span("leaves"):
            empty = self._groupby_axes(index, rows_calls, shards, axes)
        if empty:
            return GroupCounts([])
        return self._groupby_levels(axes, filter_dev, limit)

    def _groupby_axes(self, index: Index, rows_calls, shards,
                      axes: list) -> bool:
        """Resolve every Rows axis to its resident slab, appending (field,
        row ids, slab) to `axes`; True where an axis has no row, so the
        result is empty."""
        for rc in rows_calls:
            fname = rc.args.get("_field") or rc.args.get("field")
            f = index.field(fname)
            if f is None:
                raise ExecutionError(f"field not found: {fname}")
            row_ids = list(self._execute_rows(index, rc, shards))
            if not row_ids:
                return True
            # the stacked [R, S', W] axis slab is itself residency-cached
            # (gen-keyed like its component leaves): repeat GroupBys skip
            # the R-operand host→device upload. Built from HOST rows
            # (the _bsi_planes pattern) so the per-row leaves don't also
            # occupy residency budget — only the slab the kernels read is
            # cached, in one shard-axis-sharded upload
            gens = tuple(
                self._leaf_gens(index, fname, VIEW_STANDARD, shards, rid)
                for rid in row_ids)
            slab = self.residency.leaf(
                ("rows_slab", index.name, fname, VIEW_STANDARD,
                 tuple(shards), tuple(row_ids), gens),
                lambda f=fname, rids=row_ids: self.runner.put_plane_slab(
                    np.stack([
                        np.stack([self._cached_row(index, f, VIEW_STANDARD,
                                                   s, rid)
                                  for s in shards])
                        for rid in rids])))
            axes.append((fname, row_ids, slab))
        return False

    def _groupby_levels(self, axes: list, filter_dev, limit):
        """The cross product level by level over the resolved axis slabs
        (see _execute_group_by): launches under `dispatch`, each level's
        one fetch under `device.wait`, the host's bookkeeping between them
        and the result's assembly under `reduce`."""
        import jax
        import jax.numpy as jnp
        from pilosa_tpu.ops.bitvector import popcount

        # prefixes per dispatch: the [chunk, R, S, W] intermediate is fused
        # into the popcount reduction (never hits HBM), so chunking is
        # bounded by per-dispatch COMPUTE (~2^31 words = ~8.6 GB of fused
        # and+popcount, ~15 ms at the measured stream rate). Dispatches are
        # asynchronous — all of a level's chunks enqueue before its one
        # host sync — so chunk size only sets abort granularity and the
        # peak size of the fused working set, not the number of RTTs
        def chunk_for(slab) -> int:
            r, s, w = slab.shape
            return int(min(512, max(16, (1 << 31) // max(1, r * s * w))))

        # level-0 slab with the filter folded in (one [R0, S, W] array — the
        # only level whose slab is ever materialized beyond the axis leaves)
        fname0, rows0, slab0 = axes[0]
        if filter_dev is not None:
            with tracing.span("dispatch"):
                slab0 = jnp.bitwise_and(slab0, filter_dev[None])
        axis_slabs = [slab0] + [a[2] for a in axes[1:]]

        # comb: one index array per axis consumed so far; row-major order of
        # the arrays IS the reference's lexicographic iterator order
        comb = [np.arange(len(rows0))]
        if len(axes) == 1:
            # one fused dispatch + one fetch of the [R0] count vector
            with tracing.span("dispatch"):
                handle = jnp.sum(popcount(slab0), axis=-1)
            with tracing.span("device.wait"):
                counts = np.asarray(handle)
            self.groupby_host_syncs += 1
            live = np.nonzero(counts)[0]
            comb, counts = [live], counts[live]
        else:
            counts = None
            for li in range(1, len(axes)):
                _, row_ids, slab = axes[li]
                last = li == len(axes) - 1
                limited_last = last and limit is not None
                P, R = len(comb[0]), len(row_ids)
                # no wider than the prefixes there are, rounded up to a
                # power of two so that shapes repeat: the gathered prefix
                # slab is [p_chunk, S, W] whatever P is, and nine prefixes
                # padded to 256 were 1 GiB a request at 32 shards
                p_chunk = min(chunk_for(slab),
                              max(16, 1 << (P - 1).bit_length()))
                bound = max(1, min(p_chunk * R, self._groupby_live_cap))
                if limited_last:
                    # the result is a lexicographic prefix, so no chunk
                    # ever contributes more than `limit` groups — capping
                    # the prune transfer also makes an over-`bound` live
                    # set harmless (no refetch: the lex-first `bound`
                    # entries are all that can be reported)
                    bound = max(1, min(bound, limit))

                def dispatch(st, li=li, slab=slab, bound=bound):
                    """One async chunk dispatch — index arrays are padded
                    to a static chunk shape (one XLA program per level),
                    padding rows masked by n_valid inside the kernel."""
                    en = min(st + p_chunk, P)
                    idx = tuple(jnp.asarray(np.ascontiguousarray(np.pad(
                        ci[st:en], (0, p_chunk - (en - st))).astype(
                            np.int32))) for ci in comb)
                    return (st, idx, self.runner.groupby_chunk(
                        axis_slabs[:li], idx, slab, jnp.int32(en - st),
                        bound))

                starts = list(range(0, P, p_chunk))
                # an unlimited level enqueues EVERY chunk before its one
                # batched fetch. A limited FINAL level probes its first
                # chunk alone: the lex-first chunk usually satisfies
                # `limit`, preserving the early-exit's compute bound at
                # one sync — only a miss pays a second sync for the rest
                waves = [starts[:1], starts[1:]] if limited_last else \
                    [starts]
                live_p_parts, live_r_parts, count_parts = [], [], []
                found = 0
                for wave in waves:
                    if not wave or (limited_last and found >= limit):
                        continue
                    pending = []
                    with tracing.span("dispatch"):
                        for st in wave:
                            qctx.check()  # abort between dispatches (no sync)
                            pending.append(dispatch(st))
                    # the wave's single host sync: one batched fetch of
                    # every chunk's (n_live, flat indices, counts) triple
                    with tracing.span("device.wait"):
                        fetched = jax.device_get(
                            [o for (_, _, o) in pending])
                    self.groupby_host_syncs += 1
                    for (st, idx, _), (n_live, flat_idx, cvals) in zip(
                            pending, fetched):
                        n_live = int(n_live)
                        if n_live > bound and not (limited_last
                                                   and bound >= limit):
                            # dense chunk overflowed the prune bound:
                            # refetch its full count matrix (extra sync,
                            # counted; no group is ever silently dropped)
                            with tracing.span("device.wait"):
                                cmat = np.asarray(self.runner.groupby_cmat(
                                    axis_slabs[:li], idx, slab,
                                    jnp.int32(min(st + p_chunk, P) - st)))
                            self.groupby_host_syncs += 1
                            lp, lr = np.nonzero(cmat)
                            cv = cmat[lp, lr]
                        else:
                            k = min(n_live, bound)
                            fi = flat_idx[:k].astype(np.int64)
                            lp, lr = fi // R, fi % R
                            cv = cvals[:k]
                        live_p_parts.append(lp.astype(np.int64) + st)
                        live_r_parts.append(lr.astype(np.int64))
                        count_parts.append(cv.astype(np.int64))
                        found += lp.size
                        if limited_last and found >= limit:
                            break  # lex order: nothing later can precede
                live_p = np.concatenate(live_p_parts) if live_p_parts else \
                    np.empty(0, dtype=np.int64)
                live_r = np.concatenate(live_r_parts) if live_r_parts else \
                    np.empty(0, dtype=np.int64)
                if live_p.size == 0:
                    return GroupCounts([])
                counts = np.concatenate(count_parts)
                comb = [ci[live_p] for ci in comb] + [live_r]

        with tracing.span("reduce"):
            results = []
            axis_rows = [rows0] + [a[1] for a in axes[1:]]
            axis_names = [fname0] + [a[0] for a in axes[1:]]
            for k in range(len(counts)):
                if limit is not None and len(results) >= limit:
                    break  # before append: limit=0 yields [] (old recursion)
                results.append({
                    "group": [{"field": axis_names[a],
                               "rowID": int(axis_rows[a][comb[a][k]])}
                              for a in range(len(comb))],
                    "count": int(counts[k]),
                })
        return GroupCounts(results)

    # -------------------------------------------------------------- writes

    def _translate_result(self, index: Index, call: Call, result):
        """Map result ids back to keys on keyed fields (translateResult,
        executor.go:2497-2590): TopN Pair.Key, Rows RowIdentifiers.Keys,
        GroupBy FieldRow.RowKey. Row column keys render at the API layer
        (api.py) where the JSON/protobuf writers live."""
        if self.translator is None:
            return result
        while call.name == "Options" and call.children:
            call = call.children[0]

        def row_key(fname: str, rid: int) -> str:
            # fall back to the decimal id, never "": proto3 strings have no
            # presence, so an empty key would decode as "unkeyed" on the
            # wire (a translator miss here is pathological anyway — keyed
            # fields only hold ids the translator minted)
            return (self.translator.translate_row_to_string(
                index.name, fname, int(rid)) or str(rid))

        if isinstance(result, Pairs):
            fname = call.args.get("_field")
            f = index.field(fname) if fname else None
            if f is not None and f.options.keys:
                result.row_keys = [row_key(fname, rid) for rid, _ in result]
        elif isinstance(result, RowIdentifiers):
            fname = call.args.get("_field") or call.args.get("field")
            f = index.field(fname) if fname else None
            if f is not None and f.options.keys:
                result.row_keys = [row_key(fname, rid) for rid in result]
        elif isinstance(result, GroupCounts):
            for gc in result:
                for fr in gc["group"]:
                    f = index.field(fr.get("field"))
                    if f is not None and f.options.keys and "rowID" in fr:
                        fr["rowKey"] = row_key(fr["field"], fr.pop("rowID"))
        return result

    def _translate_col(self, index: Index, value, create: bool = True):
        """Column key -> id. Reads pass create=False: querying an unknown key
        must not mint ids into the shared translate log."""
        if isinstance(value, str):
            if self.translator is None:
                raise ExecutionError("string keys require a translator")
            return self.translator.translate_column(index.name, value, create=create)
        return int(value)

    def _translate_row(self, index: Index, f, value, create: bool = True):
        if isinstance(value, bool):
            return 1 if value else 0
        if isinstance(value, str):
            if self.translator is None:
                raise ExecutionError("string keys require a translator")
            return self.translator.translate_row(index.name, f.name, value,
                                                 create=create)
        return int(value)

    def _execute_set(self, index: Index, call: Call, shards) -> bool:
        col = self._translate_col(index, call.args["_col"])
        field_name = call.field_arg()
        f = index.field(field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        if f.options.type == FieldType.INT:
            changed = f.set_value(col, int(call.args[field_name]))
        else:
            row_id = self._translate_row(index, f, call.args[field_name])
            ts = call.args.get("_timestamp")
            changed = f.set_bit(row_id, col, timestamp=ts)
        index.mark_exists(col)
        # write heat on the replica that APPLIED the mutation: the
        # distributed write path executes this call on every live owner
        # (locally or via remote=True fan-out), so each node's tracker is
        # charged for the fragments it owns — never the coordinator's
        self._heat_write(index, f, col)
        return changed

    def _heat_write(self, index: Index, f, col: int,
                    view_name: str = None) -> None:
        tracker = self.heat
        if tracker is None or not tracker.enabled:
            return
        if view_name is None:
            view_name = (f.bsi_view_name
                         if f.options.type == FieldType.INT
                         else VIEW_STANDARD)
        tracker.touch(index.name, f.name, view_name, col // SHARD_WIDTH,
                      writes=1)

    def _execute_clear(self, index: Index, call: Call, shards) -> bool:
        col = self._translate_col(index, call.args["_col"], create=False)
        field_name = call.field_arg()
        f = index.field(field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        if col is None:
            return False  # unknown column key: nothing to clear
        if f.options.type == FieldType.INT:
            changed = f.clear_value(col)
            if changed:
                self._heat_write(index, f, col)
            return changed
        row_id = self._translate_row(index, f, call.args[field_name], create=False)
        if row_id is None:
            return False
        changed = f.clear_bit(row_id, col)
        if changed:
            self._heat_write(index, f, col)
        return changed

    def _execute_clear_row(self, index: Index, call: Call, shards) -> bool:
        field_name = call.field_arg()
        f = index.field(field_name)
        if f is None:
            raise ExecutionError(f"field not found: {field_name}")
        row_id = self._translate_row(index, f, call.args[field_name], create=False)
        if row_id is None:
            return False
        changed = False
        tracker = self.heat
        for v in f.views.values():
            if v.name.startswith("bsig_"):
                continue
            for s in list(v.fragments):
                frag_changed = v.fragments[s].clear_row(row_id) > 0
                changed |= frag_changed
                if frag_changed and tracker is not None and tracker.enabled:
                    tracker.touch(index.name, f.name, v.name, s, writes=1)
        return changed

    def _execute_store(self, index: Index, call: Call, shards) -> bool:
        """Store(bitmap, f=row): overwrite row with computed bitmap
        (executeSetRow, executor.go:2050-2140)."""
        field_name = call.field_arg()
        f = index.field(field_name)
        if f is None:
            f = index.create_field(field_name)
        row_id = self._translate_row(index, f, call.args[field_name])
        row = self._execute_bitmap_call(index, call.children[0], shards)
        view = f.create_view_if_not_exists(VIEW_STANDARD)
        qshards = self._query_shards(index, shards)
        tracker = self.heat
        if tracker is not None and tracker.enabled:
            tracker.touch_many([(index.name, f.name, VIEW_STANDARD, s)
                                for s in qshards], writes=1)
        for s in qshards:
            frag = view.create_fragment_if_not_exists(s)
            seg = row.segments.get(s)
            cols = (np.asarray(seg, dtype=np.uint64) % SHARD_WIDTH) if seg is not None else np.empty(0, dtype=np.uint64)
            frag.set_row(row_id, cols)
            view.refresh_rank_cache(s)
            f.add_available_shard(s)
        return True

    def _execute_set_row_attrs(self, index: Index, call: Call, shards) -> None:
        f = index.field(call.args["_field"])
        if f is None:
            raise ExecutionError(f"field not found: {call.args['_field']}")
        row_id = self._translate_row(index, f, call.args["_row"])
        attrs = {k: v for k, v in call.args.items() if not k.startswith("_")}
        f.row_attrs.set_attrs(row_id, attrs)

    def _execute_set_column_attrs(self, index: Index, call: Call, shards) -> None:
        col = self._translate_col(index, call.args["_col"])
        attrs = {k: v for k, v in call.args.items() if not k.startswith("_")}
        index.column_attrs.set_attrs(col, attrs)

    # --------------------------------------------- distributed fan-out
    # The reference's mapReduce (executor.go:2183-2321): shards grouped by
    # owning node, the PQL string re-sent to remote nodes with Remote=true,
    # failures re-mapped onto replicas, results reduced associatively.

    WRITE_CALLS = frozenset({"Set", "Clear", "ClearRow", "Store",
                             "SetRowAttrs", "SetColumnAttrs"})

    # ---------------------------------------- ICI slice-local routing
    # Route labels (the /metrics pilosa_iciServing_total{route=} keyspace)
    ROUTE_SLICE_LOCAL = "slice_local"
    ROUTE_CROSS_SLICE = "cross_slice"
    ROUTE_FALLBACK = "fallback"

    def ici_enabled(self) -> bool:
        return self._ici_env and self.ici_mode != "off"

    def _ici_topo_fingerprint(self) -> tuple:
        """Cheap cluster-state version for the co-residency memo: any
        membership, liveness or drain change produces a new fingerprint,
        flushing stale routing decisions (O(nodes), nodes are few)."""
        c = self.cluster
        return (tuple(n.id for n in c.nodes), c.replica_n,
                frozenset(c.down_ids), frozenset(c.draining_ids))

    def _ici_co_resident(self, index: Index, qshards: list[int]) -> bool:
        """True when this node owns a replica of EVERY query shard —
        memoized per (index, shard tuple) under one topology fingerprint."""
        fp = self._ici_topo_fingerprint()
        key = (index.name, tuple(qshards))
        topo_flipped = False
        with self._ici_lock:
            if fp != self._ici_topo_fp:
                topo_flipped = self._ici_topo_fp is not None
                self._ici_prev_memo = dict(self._ici_route_memo)
                self._ici_route_memo.clear()
                self._ici_topo_fp = fp
            hit = self._ici_route_memo.get(key)
            if hit is not None:
                self._ici_route_memo.move_to_end(key)
                return hit
        if topo_flipped and self.journal is not None:
            try:
                self.journal.emit(
                    "topology.change", observer="ici-router",
                    nodes=len(fp[0]), down=len(fp[2]),
                    draining=len(fp[3]))
            except Exception:  # noqa: BLE001 — recording must never
                pass  # break routing
        local = self.cluster.local_id
        ok = all(
            any(n.id == local
                for n in self.cluster.shard_nodes(index.name, s))
            for s in qshards)
        prev = self._ici_prev_memo.get(key)
        if prev is not None and prev != ok and self.journal is not None:
            # a memoized slice-local decision flipped under the new
            # topology: the query mix just changed serving plane
            try:
                self.journal.emit(
                    "ici.route_flip", index=index.name,
                    shards=len(qshards),
                    route="slice_local" if ok else "cross_slice")
            except Exception:  # noqa: BLE001 — never break routing
                pass
        with self._ici_lock:
            if fp == self._ici_topo_fp:
                self._ici_route_memo[key] = ok
                while len(self._ici_route_memo) > 512:
                    self._ici_route_memo.popitem(last=False)
        return ok

    def _ici_route(self, index: Index, call: Call,
                   qshards: list[int]) -> tuple[str, str]:
        """(route, reason) for one distributed read. slice_local = the
        whole shard set is co-resident on this node's slice: execute as
        one sharded program, zero internal HTTP envelopes. cross_slice =
        routable but not co-resident: the coalesced HTTP plane serves it
        bit-identically. fallback = routing doesn't apply (disabled,
        write, or nothing to route)."""
        if not self.ici_enabled():
            return self.ROUTE_FALLBACK, "disabled"
        if self._call_has_write(call):
            # writes fan out to every replica by design — a slice-local
            # write would silently drop replication
            return self.ROUTE_FALLBACK, "write"
        if not qshards:
            return self.ROUTE_FALLBACK, "no shards"
        if self.ici_mode == "auto" and self.runner.mesh is None:
            # a single-device runner is not a slice; "on" overrides (the
            # fan-out RTTs are worth removing even without ICI)
            return self.ROUTE_CROSS_SLICE, "no mesh"
        if not self._ici_co_resident(index, qshards):
            return self.ROUTE_CROSS_SLICE, "shards not co-resident"
        if self.read_fence:
            with self._fence_lock:
                fenced = any((index.name, s) in self.read_fence
                             for s in qshards)
            if fenced:
                # a fenced local shard may be stale: let the HTTP plane's
                # fence re-routing serve the verified replica
                return self.ROUTE_CROSS_SLICE, "read-fenced"
        return self.ROUTE_SLICE_LOCAL, "co-resident"

    def _record_route(self, route: str, reason: str, call: Call,
                      n_shards: int) -> dict:
        with self._ici_lock:
            if route == self.ROUTE_SLICE_LOCAL:
                self.ici_slice_local += 1
            elif route == self.ROUTE_CROSS_SLICE:
                self.ici_cross_slice += 1
            else:
                self.ici_fallback += 1
        info = {"route": route, "reason": reason, "call": call.name,
                "shards": n_shards}
        prof = qprofile.current_profile.get()
        if prof is not None:
            prof.record_route(info)
        return info

    def ici_snapshot(self) -> dict:
        """The iciServing observability block (/debug/vars, /metrics,
        telemetry rings): route decision counters + the serving-mode
        program-cache economics."""
        from pilosa_tpu.parallel.mesh import ici_program_cache_stats
        with self._ici_lock:
            out = {
                "mode": self.ici_mode if self._ici_env else "off",
                "sliceLocal": self.ici_slice_local,
                "crossSlice": self.ici_cross_slice,
                "fallback": self.ici_fallback,
            }
        out["programCache"] = ici_program_cache_stats()
        return out

    def _execute_distributed(self, index: Index, call: Call, shards):
        # Unwrap Options() BEFORE fan-out — the wrapper is not an associative
        # reduce; its shards= / excludeColumns apply around the inner call.
        if call.name == "Options":
            if len(call.children) != 1:
                raise ExecutionError("Options() takes exactly one query argument")
            if call.args.get("shards") is not None:
                shards = [int(s) for s in call.uint_slice_arg("shards")]
            result = self._execute_distributed(index, call.children[0], shards)
            if call.bool_arg("excludeColumns") and isinstance(result, Row):
                result.segments = {}
            if call.bool_arg("excludeRowAttrs") and isinstance(result, Row):
                result.attrs = {}
            return result
        if call.name in self.WRITE_CALLS:
            return self._execute_write_distributed(index, call, shards)
        qshards = self._query_shards(index, shards)
        from pilosa_tpu import planner as _planner
        route, reason = self._ici_route(index, call, qshards)
        route_info = self._record_route(route, reason, call, len(qshards))
        route_tok = _planner.current_route.set(route_info)
        try:
            if route == self.ROUTE_SLICE_LOCAL:
                # the whole shard set is co-resident on this node's
                # slice: ONE sharded program over the mesh (shard_map +
                # psum on ICI), zero /internal/query-batch envelopes —
                # the paper's pjit-over-the-pod form replacing the
                # reference's HTTP mapReduce (executor.go:2183-2321)
                return self._execute_call(index, call, qshards)
            return self._execute_cross_slice(index, call, shards, qshards)
        finally:
            _planner.current_route.reset(route_tok)

    def _execute_cross_slice(self, index: Index, call: Call, shards,
                             qshards: list[int]):
        """The coalesced HTTP scatter-gather plane — bit-identical to the
        slice-local path, taken when the shard set spans slices (or ICI
        serving is off)."""
        fan_call = call
        if call.name == "GroupBy" and call.uint_arg("limit") is not None:
            # per-node truncation breaks the merge; limit applies post-reduce
            fan_call = Call(call.name,
                            {k: v for k, v in call.args.items() if k != "limit"},
                            call.children)
        groups = self._fanout_groups(index, qshards)
        if len(groups) <= 1:
            partials = []
            for node_id, node_shards in groups.items():
                partials.extend(
                    self._map_node(index, fan_call, node_id, node_shards, set()))
            return self._reduce(call, partials, index, shards)
        # concurrent per-node fan-out — the goroutine-per-node mapper
        # (executor.go:2256); reduce as responses land. Submits go to the
        # PERSISTENT executor-owned pool (a fresh ThreadPoolExecutor per
        # query was pure churn: thread spawn + teardown on every request,
        # and per-thread keep-alive connections never reused). Each submit
        # runs in a fresh context copy: pool threads don't inherit
        # contextvars, so tracing.current_trace_id would read None and drop
        # the X-Pilosa-Trace-Id header on remote calls (Context.run is also
        # non-reentrant, hence one copy per future).
        import contextvars
        pool = self.fanout_pool
        local_shards = groups.pop(self.cluster.local_id, None)
        futures = [
            pool.submit(contextvars.copy_context().run, self._map_node,
                        index, fan_call, node_id, node_shards, set())
            for node_id, node_shards in groups.items()
        ]
        partials = []
        if local_shards is not None:
            # the local group runs INLINE on the request thread (no pool
            # slot, no context copy, no future wait): its device execution
            # overlaps the remote round trips already in flight above
            partials.extend(self._map_node(index, fan_call,
                                           self.cluster.local_id,
                                           local_shards, set()))
        partials.extend(p for fut in futures for p in fut.result())
        return self._reduce(call, partials, index, shards)

    def _map_node(self, index: Index, call: Call, node_id: str,
                  node_shards: list[int], excluded: set) -> list:
        """Execute `call` for node_shards on node_id; on failure, re-map each
        shard onto its next live replica individually (executor.go:2216-2231).
        Returns a list of partials."""
        from pilosa_tpu.net.client import ClientError
        qctx.check()  # abort between node batches (executor.go:2591)
        prof = qprofile.current_profile.get()
        if node_id == self.cluster.local_id:
            if prof is None:
                return [self._execute_call(index, call, node_shards)]
            import time as _time
            t0 = _time.perf_counter()
            out = [self._execute_call(index, call, node_shards)]
            prof.record_fanout(node_id, len(node_shards),
                               (_time.perf_counter() - t0) * 1e3, "local")
            return out
        node = self.cluster.node_by_id(node_id)
        err: Exception | None = None
        if node is not None and node.uri:
            try:
                return [self._fanout_remote(index, call, node, node_shards,
                                            excluded)]
            except ClientError as e:
                err = e
                if e.shed_reason == "draining":
                    # the peer announced its drain through the rejection
                    # itself (we raced its broadcast): mark it draining NOW
                    # so every later query this node plans routes around
                    # it without another round trip
                    self.cluster.mark_draining(node_id)
        if prof is not None:
            # the batch re-maps shard-by-shard onto replicas below; the
            # profile keeps the evidence (which node failed, how many
            # shards had to re-route, why)
            prof.record_retry(node_id, len(node_shards), str(err or
                              "node unknown / no uri"))
        # failover: per-shard re-mapping onto surviving replicas
        excluded = excluded | {node_id}
        regroup: dict[str, list[int]] = {}
        for s in node_shards:
            replicas = [n.id for n in self.cluster.shard_nodes(index.name, s)
                        if n.id not in excluded]
            # prefer replicas not marked down/draining by liveness; fall
            # back to a marked one (the marker may be stale) before erroring
            cand = next((r for r in replicas
                         if not self.cluster.is_unavailable(r)),
                        replicas[0] if replicas else None)
            if cand is None:
                raise ExecutionError(
                    f"shard {s} unavailable on all replicas: {err}")
            regroup.setdefault(cand, []).append(s)
        partials = []
        for cand, cand_shards in regroup.items():
            partials.extend(self._map_node(index, call, cand, cand_shards,
                                           excluded))
        return partials

    @classmethod
    def _call_has_write(cls, call: Call) -> bool:
        """True if any call in the tree is non-idempotent (hedge/coalesce
        eligibility is decided on the WHOLE tree, defensively — the read
        fan-out path should never see one, but a hedge IS a re-send and the
        single-retry rule in net/client.py:70-95 forbids re-sending
        side-effecting requests)."""
        if call.name in cls.WRITE_CALLS:
            return True
        return any(cls._call_has_write(c) for c in call.children)

    def _fanout_remote(self, index: Index, call: Call, node,
                       node_shards: list[int], excluded: set):
        """One remote node-batch query, with per-node latency accounting
        and (when enabled + eligible) a hedged replica read. Returns the
        node's partial result."""
        if self.hedge_delay > 0 and not self._call_has_write(call):
            hedge_node = self._hedge_candidate(index, node, node_shards,
                                               excluded)
            if hedge_node is not None:
                return self._hedged_query(index, call, node, hedge_node,
                                          node_shards)
        return self._timed_node_query(index, call, node, node_shards)

    def _timed_node_query(self, index: Index, call: Call, node,
                          node_shards: list[int], hedge: bool = False):
        """The node RPC itself: coalesced into a /internal/query-batch
        envelope when the coalescer is on, per-query query_proto otherwise.
        Wall time feeds the per-node fan-out latency histogram
        (stats timing buckets; /debug/vars) — the signal hedge_delay should
        be tuned against (docs/operations.md) — and, when this query is
        being profiled, a per-shard-group fanout record with the transport
        actually used (coalesced envelope vs per-query proto)."""
        import time as _time
        from pilosa_tpu.net.client import ClientError
        from pilosa_tpu.utils import failpoints

        # failpoint: raises ClientError so the injected fault drives the
        # same per-shard failover a real peer failure would
        failpoints.hit("executor.fanout", exc=ClientError)
        t0 = _time.perf_counter()
        err = ""
        coalesced = self.coalescer is not None
        try:
            if coalesced:
                results = self.coalescer.query(
                    node.uri, index.name, call.to_pql(), shards=node_shards)
            else:
                results = self.client.query_proto(
                    node.uri, index.name, call.to_pql(),
                    shards=node_shards, remote=True)
        except BaseException as e:
            err = f"{type(e).__name__}: {e}"
            raise
        finally:
            ms = (_time.perf_counter() - t0) * 1e3
            self.stats.timing(f"fanoutLatency/{node.id}", ms)
            prof = qprofile.current_profile.get()
            if prof is not None:
                prof.record_fanout(node.id, len(node_shards), ms,
                                   "coalesced" if coalesced else "proto",
                                   error=err, hedge=hedge)
        return results[0]

    def _hedge_candidate(self, index: Index, node, node_shards: list[int],
                         excluded: set):
        """The next live replica holding EVERY shard of this node batch
        (including this node itself as a local-execution hedge), or None.
        Hedging is batch-granular: splitting the batch per shard would
        re-create the per-query fan-out the coalescer exists to remove."""
        common: Optional[set] = None
        for s in node_shards:
            owners = {n.id for n in self.cluster.shard_nodes(index.name, s)}
            common = owners if common is None else common & owners
            if not common:
                return None
        common.discard(node.id)
        common -= set(excluded)
        common = {c for c in common if not self.cluster.is_unavailable(c)}
        if not common:
            return None
        if self.cluster.local_id in common:
            # prefer hedging onto the local device slice: no second RPC
            return self.cluster.node_by_id(self.cluster.local_id)
        # deterministic pick: cluster node order (the replica ring order)
        for n in self.cluster.nodes:
            if n.id in common:
                return n
        return None

    def _hedged_query(self, index: Index, call: Call, node, hedge_node,
                      node_shards: list[int]):
        """Tail-latency hedge for a READ-ONLY node batch: the primary RPC
        dispatches on the hedge pool; if it hasn't answered within
        hedge_delay, the same batch re-issues to `hedge_node` (the next
        live replica — or this node's own local slice) and the first
        response wins. The loser is cancelled if still queued, discarded
        if in flight — safe because only idempotent reads ever reach here
        (_fanout_remote guards on _call_has_write), so a discarded
        completion has no side effects and a winner is counted exactly
        once. Both racers failing raises the primary's error, which feeds
        the normal per-shard failover in _map_node."""
        import contextvars
        import threading as _threading
        from concurrent.futures import FIRST_COMPLETED
        from concurrent.futures import wait as _fwait

        pool = self.hedge_pool
        started = _threading.Event()

        def _primary():
            started.set()
            return self._timed_node_query(index, call, node, node_shards)

        primary = pool.submit(contextvars.copy_context().run, _primary)
        # the hedge clock starts when the RPC actually STARTS, not at pool
        # submit: under a saturated hedge pool a queued primary would
        # otherwise "time out" before ever sending, firing spurious hedges
        # that double the load exactly when the system is overloaded (and
        # making hedgesFired meaningless as a tuning signal)
        started.wait()
        done, _ = _fwait([primary], timeout=self.hedge_delay)
        if done:
            return primary.result()
        with self._hedge_lock:
            self.hedges_fired += 1
        if hedge_node.id == self.cluster.local_id:
            def _local_backup():
                # timed like _map_node's local branch, so a hedge won by
                # the local slice still leaves a per-shard-group timing in
                # the profile (the primary's record may land after the
                # response seals — the winner's must not be missing)
                prof = qprofile.current_profile.get()
                if prof is None:
                    return self._execute_call(index, call, node_shards)
                import time as _time
                t0 = _time.perf_counter()
                out = self._execute_call(index, call, node_shards)
                prof.record_fanout(hedge_node.id, len(node_shards),
                                   (_time.perf_counter() - t0) * 1e3,
                                   "local", hedge=True)
                return out

            backup = pool.submit(contextvars.copy_context().run,
                                 _local_backup)
        else:
            backup = pool.submit(contextvars.copy_context().run,
                                 self._timed_node_query, index, call,
                                 hedge_node, node_shards, True)
        racers = [primary, backup]
        done, pending = _fwait(racers, return_when=FIRST_COMPLETED)
        winner = next((f for f in done if f.exception() is None), None)
        if winner is None and pending:
            # first finisher failed: defer to the survivor
            done2, _ = _fwait(pending)
            winner = next((f for f in done2 if f.exception() is None), None)
        if winner is None:
            raise primary.exception()  # both failed: normal failover path
        loser = backup if winner is primary else primary
        with self._hedge_lock:
            if winner is backup:
                self.hedges_won += 1
            if not loser.done():
                loser.cancel()  # drops it if still queued; else discarded
                self.hedges_cancelled += 1
        prof = qprofile.current_profile.get()
        if prof is not None:
            prof.record_hedge(node.id, hedge_node.id, won=winner is backup)
        return winner.result()

    def _execute_write_distributed(self, index: Index, call: Call, shards):
        """Set/Clear/SetColumnAttrs fan out to every replica of the column's
        shard (executeSetBitField, executor.go:1865-1895); Store/ClearRow are
        per-shard ops routed like reads; SetRowAttrs broadcasts (row attr
        stores are per-node replicas)."""
        from pilosa_tpu.net.client import ClientError
        pql = call.to_pql()

        if call.name in ("Store", "ClearRow"):
            qshards = self._query_shards(index, shards)
            groups = self.cluster.shards_by_node(index.name, qshards)
            partials = []
            hinted: dict[str, list[int]] = {}  # skipped replica -> shards
            for node_id, node_shards in groups.items():
                # writes also land on replicas of each shard
                replica_targets: dict[str, list[int]] = {}
                for s in node_shards:
                    owners = self.cluster.shard_nodes(index.name, s)
                    live = [n for n in owners
                            if not self.cluster.is_unavailable(n.id)]
                    if not live:
                        # never ack a write that landed nowhere
                        raise ExecutionError(
                            f"all replicas down for write to shard {s}")
                    for n in live:
                        replica_targets.setdefault(n.id, []).append(s)
                    for n in owners:
                        if n not in live:
                            # down/draining replica: the write becomes a
                            # durable hint, replayed in order when the
                            # node returns (storage/hints.py)
                            hinted.setdefault(n.id, []).append(s)
                for rid, rshards in replica_targets.items():
                    if rid == self.cluster.local_id:
                        partials.append(self._execute_call(index, call, rshards))
                    else:
                        node = self.cluster.node_by_id(rid)
                        try:
                            results = self.client.query_proto(
                                node.uri, index.name, pql,
                                shards=rshards, remote=True)
                            partials.append(results[0])
                        except ClientError as e:
                            raise ExecutionError(f"replica write failed: {e}")
            for nid, hshards in hinted.items():
                self._hint_write(nid, index.name, pql, hshards)
            return any(bool(p) for p in partials)

        new_shard = False
        if call.name in ("Set", "Clear", "SetColumnAttrs"):
            col = self._translate_col(index, call.args["_col"])
            targets = self.cluster.shard_nodes(index.name, col // SHARD_WIDTH)
            if call.name == "Set":
                fld = index.field(call.field_arg())
                new_shard = (fld is not None and not
                             fld.available_shards.contains(
                                 col // SHARD_WIDTH))
        else:  # SetRowAttrs
            targets = self.cluster.nodes
        # Down/draining replicas are skipped from the synchronous write —
        # but no longer silently: each skipped replica gets the mutation
        # appended to its durable hint log (storage/hints.py), replayed in
        # order when liveness reports it back. All replicas down -> hard
        # error (never ack a write that landed nowhere).
        live = [n for n in targets if not self.cluster.is_unavailable(n.id)]
        if targets and not live:
            raise ExecutionError("all replicas down for write")
        skipped = [n for n in targets if n not in live]
        targets = live
        result = None
        acked = 0
        for node in targets:
            if node.id == self.cluster.local_id:
                r = self._execute_call(index, call, None)
                acked += 1
            else:
                try:
                    results = self.client.query_proto(node.uri, index.name,
                                                      pql, shards=None,
                                                      remote=True)
                    r = results[0]
                    acked += 1
                except ClientError as e:
                    if e.shed_reason == "draining" \
                            or self.cluster.is_unavailable(node.id):
                        # the replica started draining (or was marked
                        # down) between planning and send: demote it to a
                        # hint instead of failing the whole write
                        if e.shed_reason == "draining":
                            self.cluster.mark_draining(node.id)
                        skipped.append(node)
                        continue
                    raise ExecutionError(f"replica write failed: {e}")
            result = r if result is None else (result or r)
        if skipped and not acked:
            # every target raced into draining: the write landed nowhere
            raise ExecutionError("all replicas draining for write")
        for node in skipped:
            self._hint_write(node.id, index.name, pql, None)
        if new_shard and self.announce_shard_fn is not None:
            # this Set CREATED the shard: announce it SYNCHRONOUSLY so
            # the ack implies every live node can already plan queries
            # over it — an immediately-following read through any node
            # must not race the async announcement queue. The replicas'
            # own async announcements still fire (idempotent); this just
            # closes the window before the write is acked.
            self.announce_shard_fn(index.name, call.field_arg(),
                                   col // SHARD_WIDTH)
        if (call.name == "Set"
                and all(n.id != self.cluster.local_id for n in targets)):
            # first-hand knowledge: the Set just landed on the shard's
            # replicas, so the shard exists cluster-wide — merge it into
            # this coordinator's availability view NOW rather than waiting
            # for the owners' async create-shard announcement
            # (AddRemoteAvailableShards, field.go:283). Only when every
            # replica is remote: a local replica's own set_bit must do the
            # (non-quiet) add so the announcement fires; a quiet pre-add
            # would swallow it. Clear never creates shards (clear_bit
            # deliberately doesn't mark availability).
            f = index.field(call.field_arg())
            if f is not None:
                f.add_available_shard(col // SHARD_WIDTH, quiet=True)
        return result

    def _hint_write(self, node_id: str, index_name: str, pql: str,
                    hshards: Optional[list[int]]) -> None:
        """Queue one skipped replica write as a durable hint (nop without
        a HintStore — bare executors keep the legacy skip-silently
        behavior, which the anti-entropy scrubber still covers)."""
        if self.hints is None:
            return
        self.hints.append(node_id, index_name, pql, shards=hshards)

    # ----------------------------------- coalesced streaming ingest (ISSUE 16)

    def _ingest_mutation(self, index: Index, call: Call, fields: dict,
                         Mutation):
        """One Set/Clear -> a pre-translated ingest Mutation; a bare bool
        for calls that resolve without touching storage (unknown Clear
        keys, matching the per-bit early returns); None when only the
        per-bit path serves it bit-identically — missing field (its
        error), INT fields (per-plane BSI writes), mutex/bool fields
        (cross-row clear side effects), timestamped writes (time views).
        `fields` caches field resolution across the envelope (bulk runs
        repeat one or two fields thousands of times; False = known
        non-batchable) — this loop is the per-mutation cost floor of the
        whole ingest path, so it stays allocation- and lookup-lean."""
        args = call.args
        fname = None
        for k, v in args.items():  # call.field_arg(), sans the raise
            if k[0] != "_" and not isinstance(v, Condition):
                fname = k
                break
        f = fields.get(fname)
        if f is None:
            if fname is None:
                return None
            f = index.field(fname)
            if f is None or f.options.type != FieldType.SET:
                fields[fname] = False
                return None
            fields[fname] = f
        elif f is False:
            return None
        if args.get("_timestamp") is not None:
            return None
        if call.name == "Set":
            col = self._translate_col(index, args["_col"])
            row_id = self._translate_row(index, f, args[fname])
            return Mutation(True, fname, int(row_id), int(col), call)
        col = self._translate_col(index, args["_col"], create=False)
        if col is None:
            return False  # unknown column key: nothing to clear
        row_id = self._translate_row(index, f, args[fname], create=False)
        if row_id is None:
            return False
        return Mutation(False, fname, int(row_id), int(col), call)

    def _ingest_prepare(self, index: Index, query):
        """(slots, muts) for an all-Set/Clear query, or None to fall back
        to the per-bit path. Each slot is either a pre-resolved bool or
        an index into `muts`. Translation happens here, on the submitting
        thread — the batch leader never pays a stranger's translator
        round trip, and create=True minting is idempotent so a later
        fallback re-translates to the same ids."""
        from pilosa_tpu.parallel.ingest import Mutation
        slots: list = []
        muts: list = []
        fields: dict = {}
        try:
            for call in query.calls:
                m = self._ingest_mutation(index, call, fields, Mutation)
                if m is None:
                    return None
                if isinstance(m, bool):
                    slots.append(m)
                else:
                    slots.append(len(muts))
                    muts.append(m)
        except ExecutionError:
            raise  # translator contract errors, identical per-bit
        except Exception:  # noqa: BLE001 — any oddity: per-bit decides
            return None
        return slots, muts

    @staticmethod
    def _ingest_unpack(slots: list, outcomes: list) -> list:
        results = []
        for s in slots:
            if isinstance(s, bool):
                results.append(s)
                continue
            status, val = outcomes[s]
            if status == "err":
                raise val
            results.append(val)
        return results

    def _execute_ingest(self, index: Index, query) -> Optional[list]:
        """Coordinator-side ingest interception: translate, enqueue under
        the index's compatibility key, block until a batch leader applies
        the batch (locally or across replicas), unpack this request's
        outcomes. Returns None to fall back to the per-bit path."""
        prepared = self._ingest_prepare(index, query)
        if prepared is None:
            return None
        slots, muts = prepared
        if not muts:
            return list(slots)
        outcomes = self.ingest.submit((index.name,), muts)
        return self._ingest_unpack(slots, outcomes)

    def _execute_ingest_remote(self, index: Index, query) -> Optional[list]:
        """Replica-side bulk apply of a coordinator's batched envelope
        (remote=True, multi-call). The envelope IS a batch: apply it
        directly — one WAL group-commit per touched fragment — without
        re-queueing through this node's batcher (which would serialize
        the cluster on one node's admission window). A failed mutation
        fails the whole envelope (HTTP error), which the coordinator
        maps back onto this replica's mutations."""
        prepared = self._ingest_prepare(index, query)
        if prepared is None:
            return None
        slots, muts = prepared
        if not muts:
            return list(slots)
        outcomes = self._apply_ingest_local(index, muts)
        return self._ingest_unpack(slots, outcomes)

    def _apply_ingest_batch(self, index_name: str, muts) -> list:
        """IngestBatcher apply hook, run on the batch leader's thread
        under the QoS `batch` class — every pool submit and replica
        envelope the apply makes queues behind interactive traffic, so
        sustained ingest cannot move interactive p99 through queue
        position."""
        from pilosa_tpu import qos
        index = self.holder.index(index_name)
        if index is None:
            e = ExecutionError(f"index not found: {index_name}")
            return [("err", e)] * len(muts)
        tok = qos.current_priority.set("batch")
        try:
            if (self.cluster is not None and self.client is not None
                    and len(self.cluster.nodes) > 1):
                return self._apply_ingest_distributed(index, muts)
            return self._apply_ingest_local(index, muts)
        finally:
            qos.current_priority.reset(tok)

    def _apply_ingest_distributed(self, index: Index, muts) -> list:
        """The per-mutation replica discipline of _execute_write_distributed
        applied batch-wide: live/skip split per shard, draining demotion
        to durable hints, all-down/all-draining hard errors per mutation,
        synchronous new-shard announcement before waking waiters. Each
        remote replica receives ONE multi-call envelope per batch (bulk-
        applied by its remote=True interception); each skipped replica
        gets ONE hint record per batch."""
        from pilosa_tpu.net.client import ClientError
        outcomes: list = [None] * len(muts)
        acked = [0] * len(muts)
        ored = [False] * len(muts)
        skipped = [False] * len(muts)
        local: list = []
        by_node: dict[str, list] = {}
        hint_by_node: dict[str, list] = {}
        new_shard_muts: list = []
        for mi, m in enumerate(muts):
            shard = m.shard
            targets = self.cluster.shard_nodes(index.name, shard)
            live = [n for n in targets
                    if not self.cluster.is_unavailable(n.id)]
            if targets and not live:
                outcomes[mi] = ("err", ExecutionError(
                    "all replicas down for write"))
                continue
            if m.is_set:
                fld = index.field(m.field_name)
                if (fld is not None
                        and not fld.available_shards.contains(shard)):
                    new_shard_muts.append((m.field_name, shard, mi))
            for n in targets:
                if n in live:
                    if n.id == self.cluster.local_id:
                        local.append((mi, m))
                    else:
                        by_node.setdefault(n.id, []).append((mi, m))
                else:
                    skipped[mi] = True
                    hint_by_node.setdefault(n.id, []).append((mi, m))
        if local:
            res = self._apply_ingest_local(index, [m for _, m in local])
            for (mi, _m), out in zip(local, res):
                if outcomes[mi] is not None:
                    continue
                if out[0] == "err":
                    outcomes[mi] = out
                else:
                    acked[mi] += 1
                    ored[mi] = ored[mi] or bool(out[1])
        for node_id, items in by_node.items():
            node = self.cluster.node_by_id(node_id)
            pql = "\n".join(m.call.to_pql() for _, m in items)
            try:
                results = self.client.query_proto(
                    node.uri, index.name, pql, shards=None, remote=True)
                with self._ingest_lock:
                    self.ingest_stats["remoteBatches"] += 1
                    self.ingest_stats["remoteMutations"] += len(items)
                for (mi, _m), r in zip(items, results):
                    if outcomes[mi] is not None:
                        continue
                    acked[mi] += 1
                    ored[mi] = ored[mi] or bool(r)
            except ClientError as e:
                if (e.shed_reason == "draining"
                        or self.cluster.is_unavailable(node_id)):
                    # started draining between planning and send: demote
                    # this node's share of the batch to a durable hint
                    if e.shed_reason == "draining":
                        self.cluster.mark_draining(node_id)
                    for mi, _m in items:
                        skipped[mi] = True
                    hint_by_node.setdefault(node_id, []).extend(items)
                else:
                    err = ExecutionError(f"replica write failed: {e}")
                    for mi, _m in items:
                        if outcomes[mi] is None:
                            outcomes[mi] = ("err", err)
        for mi in range(len(muts)):
            if outcomes[mi] is not None:
                continue
            if skipped[mi] and not acked[mi]:
                # every target raced into draining: landed nowhere
                outcomes[mi] = ("err", ExecutionError(
                    "all replicas draining for write"))
            else:
                outcomes[mi] = ("ok", ored[mi])
        # skipped replicas: one group hint per node per batch, covering
        # only mutations that actually acked (a failed mutation was never
        # acked, so replaying it could resurrect a write the client saw
        # rejected)
        for node_id, items in hint_by_node.items():
            good = [m for mi, m in items if outcomes[mi][0] == "ok"]
            if not good:
                continue
            self._hint_write(node_id, index.name,
                             "\n".join(m.call.to_pql() for m in good), None)
            with self._ingest_lock:
                self.ingest_stats["hintedMutations"] += len(good)
        # shard-creating Sets: announce synchronously BEFORE waking the
        # waiters, so the ack implies cluster-wide planability (the
        # read-your-writes-through-any-node contract)
        seen: set = set()
        for fname, shard, mi in new_shard_muts:
            if outcomes[mi][0] != "ok" or (fname, shard) in seen:
                continue
            seen.add((fname, shard))
            with self._ingest_lock:
                self.ingest_stats["newShards"] += 1
            if self.announce_shard_fn is not None:
                self.announce_shard_fn(index.name, fname, shard)
            if not any(n.id == self.cluster.local_id
                       for n in self.cluster.shard_nodes(index.name,
                                                         shard)):
                # every replica is remote: merge availability first-hand
                # (quiet — the owners' own announcements still fire)
                fld = index.field(fname)
                if fld is not None:
                    fld.add_available_shard(shard, quiet=True)
        n_err = sum(1 for o in outcomes if o[0] == "err")
        if n_err:
            with self._ingest_lock:
                self.ingest_stats["errors"] += n_err
        return outcomes

    def _apply_ingest_local(self, index: Index, muts) -> list:
        """Apply one coalesced batch to THIS node's fragments: group per
        (field, view, shard), one Fragment.apply_batch each — one WAL
        group-commit, one sorted-dedup container merge, one generation
        bump per fragment — then the batch-granular side effects the
        per-bit path pays per mutation: rank-cache refresh and hybrid
        hysteresis once per touched row, heat charged batch-size-
        weighted, existence marked through the same bulk apply, resident
        leaves patched in place. Returns ("ok", changed) / ("err", exc)
        per mutation, order-aligned."""
        outcomes: list = [None] * len(muts)
        groups: dict = {}
        fields: dict = {}
        for mi, m in enumerate(muts):
            f = fields.get(m.field_name)
            if f is None:
                f = index.field(m.field_name)
                if f is None:
                    outcomes[mi] = ("err", ExecutionError(
                        f"field not found: {m.field_name}"))
                    continue
                fields[m.field_name] = f
            shard = m.shard
            if m.is_set:
                view = f.create_view_if_not_exists(VIEW_STANDARD)
                view.create_fragment_if_not_exists(shard)
                groups.setdefault((m.field_name, VIEW_STANDARD, shard),
                                  []).append((mi, m))
            else:
                in_any = False
                for v in list(f.views.values()):
                    if v.name.startswith("bsig_"):
                        continue
                    if v.fragments.get(shard) is None:
                        continue
                    groups.setdefault((m.field_name, v.name, shard),
                                      []).append((mi, m))
                    in_any = True
                if not in_any:
                    outcomes[mi] = ("ok", False)
        tracker = self.heat
        hyb = self.hybrid
        # (field, view, row) -> {shard: [pre_gen, post_gen, net_set_cols,
        # net_clear_cols]} — the residency patch input
        touched: dict = {}
        set_cols_by_shard: dict[int, set] = {}
        for (fname, vname, shard), items in groups.items():
            f = fields[fname]
            view = f.view(vname)
            frag = view.fragments[shard]
            rows = {m.row_id for _, m in items}
            pre = {r: frag.row_generation(r) for r in rows}
            try:
                changed, wal_ops, wal_appends = frag.apply_batch(
                    [(m.is_set, m.row_id, m.col) for _, m in items])
            except BaseException as e:  # noqa: BLE001 — per-group failure
                for mi, _m in items:
                    outcomes[mi] = ("err", e)
                continue
            changed_rows: set = set()
            for (mi, m), ch in zip(items, changed):
                if ch:
                    changed_rows.add(m.row_id)
                prev = outcomes[mi]
                if prev is not None and prev[0] == "err":
                    continue  # an earlier view's failure is sticky
                outcomes[mi] = ("ok",
                                ch if prev is None else (prev[1] or ch))
            if changed_rows:
                # net last-write-wins state per (row, local col): the
                # idempotent patch payload (setting a set bit / clearing
                # a clear bit are no-ops on the device side)
                net: dict = {}
                for _mi, m in items:
                    s_, c_ = net.setdefault(m.row_id, (set(), set()))
                    lc = m.col % SHARD_WIDTH
                    if m.is_set:
                        s_.add(lc)
                        c_.discard(lc)
                    else:
                        c_.add(lc)
                        s_.discard(lc)
                for r in changed_rows:
                    # once per changed row, not per mutation: rank cache
                    view._update_rank(shard, frag, r)
                    t = touched.setdefault((fname, vname, r), {})
                    t[shard] = [pre[r], frag.row_generation(r),
                                net[r][0], net[r][1]]
                if hyb is not None and hyb.active():
                    fk = [(index.name, fname, vname, shard)]
                    for r in changed_rows:
                        card = frag.row_cardinality(r)
                        # run stats only when the run band is reachable:
                        # below the sparse threshold the transition rule
                        # never reads them, and row_run_stats on a fresh
                        # generation walks containers
                        rs = (frag.row_run_stats(r)
                              if (card > hyb.threshold
                                  and hyb.run_threshold > 0) else None)
                        hyb.observe((index.name, fname, vname, r),
                                    card, frag_keys=fk, run_stats=rs)
                    with self._ingest_lock:
                        self.ingest_stats["hybridEvals"] += \
                            len(changed_rows)
            if tracker is not None and tracker.enabled:
                # batch-size-weighted write heat, one charge per fragment
                # (satellite: Sets charge like the per-bit path — every
                # Set — Clears only when they changed a bit)
                w = sum(1 for (_mi, m), ch in zip(items, changed)
                        if m.is_set or ch)
                if w:
                    tracker.touch(index.name, fname, vname, shard,
                                  writes=w)
            if any(m.is_set for _mi, m in items):
                f.add_available_shard(shard)
                set_cols_by_shard.setdefault(shard, set()).update(
                    m.col for _mi, m in items if m.is_set)
            with self._ingest_lock:
                st = self.ingest_stats
                st["appliedBatches"] += 1
                st["walAppends"] += wal_appends
                st["walOps"] += wal_ops
        self._ingest_mark_exists(index, set_cols_by_shard, outcomes, muts)
        if touched:
            try:
                self._ingest_patch_residency(index, touched)
            except Exception:  # noqa: BLE001 — patching is optional
                # the durable write already happened and the generation
                # bump re-keys every touched leaf, so a failed patch can
                # only cost a re-upload — it must never fail acked writes
                with self._ingest_lock:
                    self.ingest_stats["patchDropped"] += 1
        n_err = sum(1 for o in outcomes if o is not None and o[0] == "err")
        if n_err:
            with self._ingest_lock:
                self.ingest_stats["errors"] += n_err
        return [o if o is not None else ("ok", False) for o in outcomes]

    def _ingest_mark_exists(self, index: Index, set_cols_by_shard: dict,
                            outcomes: list, muts) -> None:
        """Batched index.mark_exists: the per-bit path pays one existence
        set_bit (with its own WAL op + fsync) per Set — which would undo
        the whole group commit — so the existence row rides the same
        Fragment.apply_batch, one WAL append per existence fragment."""
        if not set_cols_by_shard or not getattr(index, "track_existence",
                                                False):
            return
        ef = index.existence_field()
        if ef is None:
            return
        ev = ef.create_view_if_not_exists(VIEW_STANDARD)
        for shard, cols in sorted(set_cols_by_shard.items()):
            efrag = ev.create_fragment_if_not_exists(shard)
            try:
                ech, wal_ops, wal_appends = efrag.apply_batch(
                    [(True, 0, c) for c in sorted(cols)])
            except BaseException as e:  # noqa: BLE001 — existence failure
                # fails the shard's Sets, as the per-bit mark_exists would
                for mi, m in enumerate(muts):
                    if m.is_set and m.shard == shard:
                        outcomes[mi] = ("err", e)
                continue
            if any(ech):
                ev._update_rank(shard, efrag, 0)
            ef.add_available_shard(shard)
            with self._ingest_lock:
                st = self.ingest_stats
                st["appliedBatches"] += 1
                st["walAppends"] += wal_appends
                st["walOps"] += wal_ops

    def _ingest_patch_residency(self, index: Index, touched: dict) -> None:
        """Patch HBM-resident row leaves with the batch's net effect
        instead of letting the generation bump strand them: a matching
        dense leaf absorbs per-word set/clear masks (2·k·8 bytes over the
        link instead of 128 KiB per shard on the next read), a sparse
        leaf absorbs sorted add/remove arrays when it stays in its slot
        bucket. Purely an optimization — generation-keyed lookups mean
        any dropped or unmatched entry is re-uploaded correctly on its
        next read."""
        from pilosa_tpu.ops import bitvector as bv
        iname = index.name

        def p2(n: int) -> int:
            k = 8
            while k < n:
                k <<= 1
            return k

        def parse(key):
            if not (isinstance(key, tuple) and key
                    and key[1:2] == (iname,)):
                return None
            if key[0] == "row" and len(key) == 7:
                out = key[2], key[3], key[4], key[5], key[6], 0
            elif key[0] in ("sparse", "run") and len(key) == 8:
                out = key[2], key[3], key[4], key[5], key[7], key[6]
            else:
                return None
            # shards/gens must be same-length tuples: a leaf uploaded
            # before its view existed carries gens=() (_leaf_gens on a
            # missing view) — un-patchable, re-keyed on its next read
            if (not isinstance(out[3], tuple) or not isinstance(out[4], tuple)
                    or len(out[3]) != len(out[4])):
                return None
            return out

        def matcher(key):
            p = parse(key)
            if p is None:
                return False
            fld, vw, row, shards_t, gens, _slots = p
            hit = False
            for i, s in enumerate(shards_t):
                e = touched.get((fld, vw, row), {}).get(s)
                if e is not None:
                    if gens[i] != e[0]:
                        return False  # older-stale: un-patchable, leave
                    hit = True
            return hit

        def patcher(key, arr):
            fld, vw, row, shards_t, gens, slots = parse(key)
            t = touched[(fld, vw, row)]
            new_gens = tuple(t[s][1] if s in t else g
                             for s, g in zip(shards_t, gens))
            if key[0] == "row":
                # per-(shard, word) mask reduction: each coordinate once
                pairs: dict = {}
                for i, s in enumerate(shards_t):
                    e = t.get(s)
                    if e is None:
                        continue
                    for c in e[2]:
                        mm = pairs.setdefault((i, c >> 5), [0, 0])
                        mm[0] |= 1 << (c & 31)
                    for c in e[3]:
                        mm = pairs.setdefault((i, c >> 5), [0, 0])
                        mm[1] |= 1 << (c & 31)
                n = p2(len(pairs))
                sidx = np.full(n, arr.shape[0], dtype=np.int32)
                widx = np.zeros(n, dtype=np.int32)
                smask = np.zeros(n, dtype=np.uint32)
                cmask = np.zeros(n, dtype=np.uint32)
                for j, ((i, w), (sm, cm)) in enumerate(
                        sorted(pairs.items())):
                    sidx[j] = i
                    widx[j] = w
                    smask[j] = sm
                    cmask[j] = cm
                new_arr = bv.patch_dense_words(arr, sidx, widx, smask,
                                               cmask)
                with self._ingest_lock:
                    self.ingest_stats["patchedDense"] += 1
                return (("row", iname, fld, vw, row, shards_t, new_gens),
                        new_arr)
            if key[0] == "run":
                # run leaves are interval-encoded: a point write can
                # split/merge/extend intervals, which has no in-place
                # device patch — drop the stale entry so its HBM frees
                # NOW instead of stranding until LRU; the next read
                # re-encodes straight from the storage run containers
                with self._ingest_lock:
                    self.ingest_stats["patchDropped"] += 1
                return None
            # sparse: only while the row stays in the SAME slot bucket —
            # the read path probes with pad_slots(current card), so a
            # bucket move would strand the entry anyway
            f = index.field(fld)
            view = f.view(vw) if f is not None else None
            if view is None:
                return None
            max_card = 0
            for s in shards_t:
                fr = view.fragment(s)
                if fr is not None:
                    c = fr.row_cardinality(row)
                    if c > max_card:
                        max_card = c
            if self.hybrid.pad_slots(max(max_card, 1)) != slots:
                with self._ingest_lock:
                    self.ingest_stats["patchDropped"] += 1
                return None
            na = max((len(t[s][2]) for s in t), default=0)
            nr = max((len(t[s][3]) for s in t), default=0)
            adds = np.full((arr.shape[0], p2(na)), bv.SPARSE_SENTINEL,
                           np.int32)
            rems = np.full((arr.shape[0], p2(nr)), bv.SPARSE_SENTINEL,
                           np.int32)
            for i, s in enumerate(shards_t):
                e = t.get(s)
                if e is None:
                    continue
                if e[2]:
                    cs = np.sort(np.fromiter(e[2], np.int64)).astype(
                        np.int32)
                    adds[i, :cs.size] = cs
                if e[3]:
                    cs = np.sort(np.fromiter(e[3], np.int64)).astype(
                        np.int32)
                    rems[i, :cs.size] = cs
            new_arr = bv.patch_sparse_rows(arr, adds, rems)
            with self._ingest_lock:
                self.ingest_stats["patchedSparse"] += 1
            return (("sparse", iname, fld, vw, row, shards_t, slots,
                     new_gens), new_arr)

        self.residency.patch_entries(matcher, patcher)

    def ingest_snapshot(self) -> dict:
        """The /debug/vars `ingest` block + /metrics family source:
        batcher queue/coalesce counters merged with the executor-level
        apply/WAL/patch counters."""
        from pilosa_tpu.parallel.ingest import ingest_env_enabled
        out = self.ingest.snapshot()
        with self._ingest_lock:
            out.update(self.ingest_stats)
        out["enabled"] = ingest_env_enabled()
        out["windowS"] = self.ingest.admission_s
        out["maxBatch"] = self.ingest.max_batch
        return out

    def _reduce(self, call: Call, partials: list, index: Optional[Index] = None,
                shards: Optional[list[int]] = None):
        """Associative reduce (reduceFn, executor.go:2209-2242): host work,
        the `reduce` span, but for TopN's exact recount, a fan-out of its
        own that the span is closed before."""
        with tracing.span("reduce") as sp:
            if not partials:
                raise ExecutionError("no shards to execute")
            if call.name == "Count":
                return sum(partials)
            if call.name == "Sum":
                return ValCount(sum(p.val for p in partials),
                                sum(p.count for p in partials))
            if call.name in ("Min", "Max"):
                best = None
                for p in partials:
                    if p.count == 0:
                        continue
                    if best is None:
                        best = ValCount(p.val, p.count)
                    elif p.val == best.val:
                        best.count += p.count
                    elif (call.name == "Min") == (p.val < best.val):
                        best = ValCount(p.val, p.count)
                return best or ValCount(0, 0)
            if call.name == "TopN":
                merged = merge_pairs(partials)
                # n=0 is the reference zero value: unlimited (same mapping as
                # the single-node path, _execute_topn)
                n = call.uint_arg("n") or None
                if n is not None and call.uint_slice_arg("ids") is None and index is not None:
                    # phase 2: exact recount of winning ids on the query's shards
                    # (executor.go:694-761)
                    ids = [i for i, _ in merged[:n]]
                    sp.finish()
                    return self._recount_topn(index, call, ids, shards)
                return Pairs(merged)
            if call.name == "Rows":
                out = sorted(set().union(*[set(p) for p in partials]))
                limit = call.uint_arg("limit")
                return RowIdentifiers(out[:limit] if limit is not None else out)
            if call.name == "GroupBy":
                acc: dict[str, dict] = {}
                for p in partials:
                    for g in p:
                        key = str(g["group"])
                        if key in acc:
                            acc[key]["count"] += g["count"]
                        else:
                            acc[key] = dict(g)
                out = sorted(acc.values(), key=lambda g: [
                    (x["field"], x["rowID"]) for x in g["group"]])
                limit = call.uint_arg("limit")
                return GroupCounts(out[:limit] if limit is not None else out)
            if call.name in BITMAP_CALLS:
                out = partials[0]
                for p in partials[1:]:
                    out = out.merge(p)
                return out
            return partials[0]

    def _recount_topn(self, index: Index, call: Call, ids: list[int],
                      shards: Optional[list[int]]):
        recount = Call("TopN", {**call.args, "ids": ids}, call.children)
        recount.args.pop("n", None)
        partials = []
        qshards = self._query_shards(index, shards)
        groups = self._fanout_groups(index, qshards)
        for node_id, node_shards in groups.items():
            partials.extend(self._map_node(index, recount, node_id,
                                           node_shards, set()))
        merged = merge_pairs(partials)
        n = call.uint_arg("n")
        return Pairs(merged[:n] if n is not None else merged)

    # -------------------------------------------------------------- options

    def _execute_options(self, index: Index, call: Call, shards):
        if len(call.children) != 1:
            raise ExecutionError("Options() takes exactly one query argument")
        if call.args.get("shards") is not None:
            shards = [int(s) for s in call.uint_slice_arg("shards")]
        result = self._execute_call(index, call.children[0], shards)
        # the two flags are independent: excludeColumns clears only segments,
        # excludeRowAttrs clears only attrs (executor.go Options handling)
        if call.bool_arg("excludeColumns") and isinstance(result, Row):
            result.segments = {}
        if call.bool_arg("excludeRowAttrs") and isinstance(result, Row):
            result.attrs = {}
        return result
