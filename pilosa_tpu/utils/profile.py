"""Per-query distributed profiler: the answer to "why was THIS query slow?"

The fast paths earned in the batching rounds deliberately smear per-query
cost across queries: a Count may ride a CountBatcher dispatch shared with
K strangers (parallel/batcher.py), its remote fan-out may ride a coalesced
/internal/query-batch envelope shared with M strangers (net/coalesce.py),
and a hedged replica read may serve it from a node the planner never
picked. Flat spans (utils/tracing.py) and aggregate counters (/debug/vars)
cannot attribute any of that back to one query — the same
dispatch-attribution problem batched inference servers face.

QueryProfile rides a contextvar (the utils/qctx.py pattern: fan-out pool
submits run in copied contexts, so every thread serving this query sees
the SAME profile object), and every layer appends its attribution record:

  - per-call spans (executor.execute) and the request's stage tree
    (`stages`: every utils/tracing.py span that finished under it)
  - per-shard-group fan-out: node, shard count, RPC wall time, transport
    (local / coalesced envelope / per-query proto / legacy fallback),
    hedge fired/won, per-shard failover retries (executor fan-out)
  - device dispatch attribution: which batched dispatch served this query,
    the batch size it shared, its wall-time share (parallel/batcher.py) —
    NodeCoalescer inherits the same hook, so envelope coalesce factor
    comes from the identical mechanism
  - residency hit/miss counts + host->device bytes (parallel/residency.py)
  - remote profile fragments: each remote node serializes its own profile
    into QueryResponse.Profile (proto/pilosa.proto), and the coordinator
    grafts them under the fan-out records — a cross-node profile TREE.

Disabled cost: one ContextVar.get() returning None per instrumentation
site (the nop fast path). Nothing allocates, locks, or formats unless a
profile is installed.
"""

from __future__ import annotations

import contextvars
import threading
import time
from typing import Optional

# the profile being recorded for the current query, or None (= profiling
# off: every instrumentation site checks this and returns immediately).
# Fan-out pool submits run in copied contexts, so pool threads share the
# coordinator thread's profile object (appends are lock-guarded below).
current_profile: contextvars.ContextVar[Optional["QueryProfile"]] = \
    contextvars.ContextVar("pilosa_query_profile", default=None)

# the finished profile of the query a handler just ran: api.query_results
# publishes here after resetting current_profile, so the HTTP layer can
# attach it to the response without a return-type change on the hot path
last_profile: contextvars.ContextVar[Optional["QueryProfile"]] = \
    contextvars.ContextVar("pilosa_last_profile", default=None)


def current() -> Optional["QueryProfile"]:
    """The active profile, or None when profiling is off (the nop path)."""
    return current_profile.get()


class QueryProfile:
    """One query's attribution tree, assembled coordinator-side.

    Appends are thread-safe: fan-out pool threads, hedge racers and batcher
    leader threads all record into the query's one profile concurrently."""

    __slots__ = ("trace_id", "node_id", "index", "pql", "start",
                 "start_wall", "elapsed_ms", "calls", "stages", "fanout",
                 "dispatches",
                 "residency_hits", "residency_misses", "h2d_bytes",
                 "remotes", "plans", "routes", "qos", "_lock", "_sealed",
                 "_cached_dict")

    def __init__(self, trace_id: str = "", node_id: str = "",
                 index: str = "", pql: str = ""):
        self._sealed = False  # finish() seals: late records (a discarded
        # hedge loser's RPC landing after the response serialized) are
        # dropped, so every surface sees ONE deterministic tree
        self._cached_dict: Optional[dict] = None
        self.trace_id = trace_id
        self.node_id = node_id
        self.index = index
        self.pql = pql
        self.start = time.perf_counter()
        self.start_wall = time.time()  # wall-clock: export timestamps
        self.elapsed_ms: float = 0.0
        self.calls: list[dict] = []        # [{call, ms}]
        self.stages: list[dict] = []       # the request's span tree
        self.fanout: list[dict] = []       # per-shard-group RPC records
        self.dispatches: list[dict] = []   # device/envelope dispatch shares
        self.residency_hits = 0
        self.residency_misses = 0
        self.h2d_bytes = 0                 # host->device upload bytes
        self.remotes: list[dict] = []      # [{node, profile}] child trees
        self.plans: list[dict] = []        # planner decisions per call
        self.routes: list[dict] = []       # ICI routing decisions per call
        # QoS admission context (pilosa_tpu/qos.py): priority class,
        # deadline budget and the admission-time wait estimate — set once
        # by api.query_results when a plane is wired, None otherwise
        self.qos: Optional[dict] = None
        self._lock = threading.Lock()

    # -- recording hooks (each guarded by a current() is-None check at the
    # call site; these only run when profiling is on) ----------------------

    def record_call(self, name: str, ms: float) -> None:
        with self._lock:
            if self._sealed:
                return
            self.calls.append({"call": name, "ms": round(ms, 3)})

    def record_stage(self, span) -> None:
        """One finished span (utils/tracing.py Span, its sink c): the
        stages of this request as a tree. `startMs` counts from the
        profile's start; `parent` is the `id` of the stage that opened
        this one, absent from the list where that span outlives the
        profile (the HTTP layer's)."""
        rec = {"id": f"{span.span_id:016x}", "name": span.name,
               "startMs": round((span.start - self.start) * 1e3, 3),
               "ms": round(span.ms, 3),
               "selfMs": round(span.self_ms, 3),
               "parent": (f"{span.parent.span_id:016x}"
                          if span.parent is not None else "")}
        if span.tags:
            rec["tags"] = {k: str(v) for k, v in span.tags.items()}
        with self._lock:
            if self._sealed:
                return
            self.stages.append(rec)

    def record_fanout(self, node_id: str, shards: int, ms: float,
                      transport: str, error: str = "",
                      hedge: bool = False) -> None:
        """One node-batch RPC (or local-slice execution): the per-node
        timing ?profile=true surfaces for every remote shard group."""
        rec = {"node": node_id, "shards": shards, "ms": round(ms, 3),
               "transport": transport}
        if error:
            rec["error"] = error
        if hedge:
            rec["hedge"] = True
        with self._lock:
            if self._sealed:
                return
            self.fanout.append(rec)

    def record_hedge(self, node_id: str, hedge_node_id: str,
                     won: bool) -> None:
        with self._lock:
            if self._sealed:
                return
            self.fanout.append({"node": node_id, "hedgeNode": hedge_node_id,
                                "kind": "hedge", "hedgeWon": won})

    def record_retry(self, node_id: str, shards: int, error: str) -> None:
        """A failed node batch re-mapped per shard onto replicas."""
        with self._lock:
            if self._sealed:
                return
            self.fanout.append({"node": node_id, "shards": shards,
                                "kind": "failover", "error": error})

    def record_dispatch(self, batcher: str, seq: int, batch_size: int,
                        wall_ms: float) -> None:
        """This query's share of one batched dispatch: `seq` identifies the
        dispatch (shared by every co-batched query), `batch_size` is how
        many queries shared it, and the wall-time share divides the
        dispatch's wall clock evenly (the attribution convention of batched
        inference servers: a query cannot be charged less than its seat)."""
        with self._lock:
            if self._sealed:
                return
            self.dispatches.append({
                "batcher": batcher, "dispatch": seq,
                "batchSize": batch_size, "wallMs": round(wall_ms, 3),
                "shareMs": round(wall_ms / max(1, batch_size), 3)})

    def record_plan(self, plan: dict) -> None:
        """One planner decision node (pilosa_tpu/planner.py plan_call):
        chosen operand order, estimated cardinalities, reorder /
        short-circuit / pushdown flags. The dict is appended by REFERENCE
        at plan time — the executor fills cache hit/miss events and the
        actual cardinality into it while the call runs, and to_dict()
        serializes whatever has accumulated (the tree seals afterwards)."""
        with self._lock:
            if self._sealed:
                return
            self.plans.append(plan)

    def record_route(self, info: dict) -> None:
        """One ICI routing decision (executor._ici_route): slice_local =
        served as a single sharded program over the local slice (zero
        internal HTTP envelopes), cross_slice = coalesced HTTP
        scatter-gather, fallback = routing didn't apply."""
        with self._lock:
            if self._sealed:
                return
            self.routes.append(dict(info))

    def record_residency(self, hit: bool, nbytes: int = 0) -> None:
        with self._lock:
            if self._sealed:
                return
            if hit:
                self.residency_hits += 1
            else:
                self.residency_misses += 1
                self.h2d_bytes += int(nbytes)

    def add_remote_fragment(self, node: str, fragment: dict) -> None:
        """Graft a remote node's profile fragment (decoded from
        QueryResponse.Profile) under this coordinator profile. Legacy peers
        send no fragment — the tree simply has no child for that node."""
        with self._lock:
            if self._sealed:
                return
            self.remotes.append({"node": node, "profile": fragment})

    def finish(self) -> None:
        self.elapsed_ms = round((time.perf_counter() - self.start) * 1e3, 3)
        with self._lock:
            self._sealed = True

    # -- serialization ------------------------------------------------------

    def to_dict(self) -> dict:
        """JSON tree: what ?profile=true returns and what rides
        QueryResponse.Profile across nodes. After finish() the tree is
        immutable and this memoizes — the slow-query history entry and the
        response body share ONE serialization (identical by construction)."""
        with self._lock:
            if self._cached_dict is not None:
                return self._cached_dict
            d = {
                "traceId": self.trace_id,
                "node": self.node_id,
                "index": self.index,
                "pql": self.pql,
                "startWall": self.start_wall,
                "elapsedMs": self.elapsed_ms,
                "calls": list(self.calls),
                "stages": list(self.stages),
                "fanout": list(self.fanout),
                "dispatches": list(self.dispatches),
                "residency": {"hits": self.residency_hits,
                              "misses": self.residency_misses,
                              "hostToDeviceBytes": self.h2d_bytes},
                "plan": [dict(p) for p in self.plans],
                "route": [dict(r) for r in self.routes],
                "remoteProfiles": list(self.remotes),
            }
            if self.qos is not None:
                d["qos"] = dict(self.qos)
            if self._sealed:
                self._cached_dict = d
            return d


def truncate_pql(pql, limit: int = 256) -> str:
    """Slow-log / history PQL truncation: an unbounded import-sized PQL
    must not land in a log line or sit in the ring buffer N times over."""
    s = pql if isinstance(pql, str) else str(pql)
    return s if len(s) <= limit else s[: limit - 3] + "..."


class QueryHistory:
    """Structured slow-query ring buffer (GET /debug/query-history): the
    last `size` queries over long-query-time, newest first, each with
    trace id, truncated PQL, elapsed seconds and the full profile tree
    (when profiling was on for that query)."""

    def __init__(self, size: int = 100):
        import collections
        self._lock = threading.Lock()
        self._entries: "collections.deque[dict]" = collections.deque(
            maxlen=max(1, int(size)))

    @property
    def size(self) -> int:
        return self._entries.maxlen

    @size.setter
    def size(self, size: int) -> None:
        import collections
        with self._lock:
            self._entries = collections.deque(self._entries,
                                              maxlen=max(1, int(size)))

    def append(self, entry: dict) -> None:
        with self._lock:
            self._entries.append(entry)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(reversed(self._entries))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)
