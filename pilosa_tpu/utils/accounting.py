"""Per-principal resource accounting + SLO burn-rate tracking.

The serving stack deliberately smears per-query cost across queries
(ContinuousBatcher co-batches device dispatches, NodeCoalescer merges
fan-out envelopes), so aggregate counters cannot answer the question
admission control and quotas hinge on: *who is spending the hardware*.
This module is the attribution layer ROADMAP item 4's enforcement will
act on:

* `Account` on a contextvar (the utils/profile.py pattern: fan-out pool
  submits run in copied contexts, so every thread serving a request sees
  the same account). The HTTP layer installs one per request — principal
  from `X-API-Key` / `Authorization` (digested, never stored raw) with a
  remote-addr fallback — and internal RPCs inherit the coordinator's
  principal via the `X-Pilosa-Principal` header / per-entry envelope
  field, mirroring how trace ids propagate.
* `UsageLedger`: bounded per-principal aggregates (device-ms, HBM bytes
  moved, RPC bytes, queue-wait ms, query/error counts, plan-cache hits)
  with lowest-spender spill into a `~other` bucket so an unbounded key
  space (per-customer API keys, rotating tokens) cannot OOM the server,
  plus a since-cursor delta ring for `GET /debug/usage` (the
  /debug/timeseries contract).
* `SLOTracker`: `[slo]` latency/availability objectives per query class
  evaluated with multi-window (5m/1h) burn-rate math — burn = observed
  bad-event ratio over the window divided by the error budget — feeding
  `slo/*` gauges and the shared health_score.

Disabled cost: one ContextVar.get() returning None per charge site (the
profiler's nop-fast-path discipline). `PILOSA_TPU_ACCOUNTING=0` is the
kill switch.
"""

from __future__ import annotations

import collections
import contextvars
import os
import threading
import time
from typing import Optional

PRINCIPAL_HEADER = "X-Pilosa-Principal"

# the spill bucket: charges from principals beyond the ledger bound land
# here (top-K semantics — the lowest spender is merged out, never the data)
SPILL = "~other"

# every per-principal aggregate the ledger tracks; snapshot/merge/exposition
# all iterate this one tuple so a new metric cannot silently miss a surface
FIELDS = ("deviceMs", "hbmBytes", "rpcBytes", "queueMs", "queries",
          "errors", "planCacheHits")


def enabled() -> bool:
    """PILOSA_TPU_ACCOUNTING=0 kills account installation (read per
    request at the HTTP layer; charge sites stay nop via the contextvar)."""
    return os.environ.get("PILOSA_TPU_ACCOUNTING", "1") != "0"


class Account:
    """(ledger, principal) carried on the request context. Charge sites
    deep in the stack (batcher leaders, residency, the RPC client) read
    this instead of a process global, so in-process multi-server tests
    and envelope entries each charge the right node's ledger."""

    __slots__ = ("ledger", "principal")

    def __init__(self, ledger: "UsageLedger", principal: str):
        self.ledger = ledger
        self.principal = principal

    def charge(self, **fields) -> None:
        self.ledger.charge(self.principal, **fields)


# the account of the request being served, or None (= accounting off: every
# charge site checks this and returns immediately)
current_account: contextvars.ContextVar[Optional[Account]] = \
    contextvars.ContextVar("pilosa_account", default=None)


def current() -> Optional[Account]:
    return current_account.get()


def _sanitize(raw: str, limit: int = 64) -> str:
    """Principal labels ride stats tag values (comma-separated, colon
    key/value) and JSON surfaces: strip separators and control bytes, cap
    length so a hostile header cannot bloat every snapshot."""
    out = "".join("_" if (c in ",\n\r\t\"\\" or ord(c) < 0x20) else c
                  for c in raw.strip())
    return out[:limit] if out else "anonymous"


def principal_from_headers(headers, client_addr: Optional[str] = None) -> str:
    """Extract the caller's principal (http/handler middleware order):

    1. `X-Pilosa-Principal` — internal fan-out RPCs inherit the
       coordinator's principal (injected by InternalClient, exactly how
       X-Pilosa-Trace-Id propagates), so remote work is charged to the
       original caller, not to the coordinator node.
    2. `X-API-Key` — used verbatim (operators pick readable key names).
    3. `Authorization` — digested to `auth:<16 hex>`: the header may carry
       a bearer token or password and must never be stored or exposed raw.
    4. remote address fallback, so unauthenticated deployments still get
       per-source attribution.
    """
    h = headers if headers is not None and hasattr(headers, "get") else {}
    inherited = h.get(PRINCIPAL_HEADER)
    if inherited:
        return _sanitize(inherited)
    key = h.get("X-API-Key")
    if key:
        return "key:" + _sanitize(key)
    auth = h.get("Authorization")
    if auth:
        import hashlib
        return "auth:" + hashlib.blake2b(auth.encode(),
                                         digest_size=8).hexdigest()
    if client_addr:
        return "addr:" + _sanitize(str(client_addr))
    return "anonymous"


# ---------------------------------------------------------------------------
# Usage ledger
# ---------------------------------------------------------------------------


class UsageLedger:
    """Bounded per-principal usage aggregates + a since-cursor delta ring.

    Bound: at most `max_principals` tracked entries. A new principal
    arriving at capacity evicts the lowest-deviceMs entry into the SPILL
    bucket (top-K by spend survives; the spilled charges are never lost —
    totals stay exact). `sample_tick()` (driven by the telemetry sampler)
    appends per-principal deltas since the previous tick into a bounded
    ring served at `GET /debug/usage?since=` — the /debug/timeseries
    cursor contract, so a usage poller transfers each tick once."""

    def __init__(self, max_principals: int = 256, ring_size: int = 360):
        from pilosa_tpu.utils.telemetry import Ring
        self.enabled = True  # runtime toggle (bench A/B); env kill switch
        # is checked at account-install time (see http_server.dispatch)
        self.max_principals = max(2, int(max_principals))
        self._lock = threading.Lock()
        self._p: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        self.spilled_principals = 0  # distinct principals merged into SPILL
        self.ring = Ring(ring_size)
        self._prev: dict[str, dict] = {}  # last tick's per-principal totals

    # -- charging (the hot path) -------------------------------------------

    def charge(self, principal: str, device_ms: float = 0.0,
               hbm_bytes: int = 0, rpc_bytes: int = 0,
               queue_ms: float = 0.0, queries: int = 0, errors: int = 0,
               plan_cache_hits: int = 0) -> None:
        with self._lock:
            e = self._p.get(principal)
            if e is None:
                if len(self._p) >= self.max_principals:
                    principal = self._spill_locked(principal)
                    e = self._p.get(principal)
                if e is None:
                    e = self._p[principal] = dict.fromkeys(FIELDS, 0.0)
            e["deviceMs"] += device_ms
            e["hbmBytes"] += hbm_bytes
            e["rpcBytes"] += rpc_bytes
            e["queueMs"] += queue_ms
            e["queries"] += queries
            e["errors"] += errors
            e["planCacheHits"] += plan_cache_hits
            e["lastChargeWall"] = time.time()  # wall-clock: serialized

    def _spill_locked(self, newcomer: str) -> str:
        """At capacity: merge lowest-deviceMs tracked principals into the
        SPILL bucket until the newcomer fits (totals stay exact — only the
        per-principal resolution of the evictees is lost). If only the
        SPILL bucket remains, the newcomer's charges go to it directly."""
        spill = self._p.get(SPILL)
        if spill is None:
            spill = self._p[SPILL] = dict.fromkeys(FIELDS, 0.0)
        while len(self._p) >= self.max_principals:
            victim_key = None
            victim_ms = None
            for k, e in self._p.items():
                if k == SPILL:
                    continue
                if victim_ms is None or e["deviceMs"] < victim_ms:
                    victim_key, victim_ms = k, e["deviceMs"]
            if victim_key is None:
                return SPILL  # only the spill bucket is left
            victim = self._p.pop(victim_key)
            for f in FIELDS:
                spill[f] += victim[f]
            self.spilled_principals += 1
        return newcomer

    # -- read side ----------------------------------------------------------

    def peek(self, principal: str) -> Optional[dict]:
        """One principal's current aggregates (a copy), or None when not
        tracked. The QoS plane's quota buckets withdraw the DELTA of these
        between a principal's requests — the measured spend, batch-smeared
        attribution included, not an up-front estimate."""
        with self._lock:
            e = self._p.get(principal)
            return {f: e[f] for f in FIELDS} if e is not None else None

    def totals(self) -> dict:
        """Exact cluster-auditable sums over every principal (spill
        included) — what /debug/vars and the usage/* counter families
        report, and what per-principal rows must add up to."""
        with self._lock:
            out = dict.fromkeys(FIELDS, 0.0)
            for e in self._p.values():
                for f in FIELDS:
                    out[f] += e[f]
            return out

    def snapshot(self, top: int = 0) -> dict:
        """Per-principal aggregates sorted by deviceMs desc (`top` bounds
        the list; 0 = all tracked), plus exact totals and the spill
        metadata a reader needs to interpret the bound."""
        with self._lock:
            items = sorted(self._p.items(),
                           key=lambda kv: (-kv[1]["deviceMs"],
                                           -kv[1]["queries"], kv[0]))
            totals = dict.fromkeys(FIELDS, 0.0)
            for _, e in items:
                for f in FIELDS:
                    totals[f] += e[f]
            if top and top > 0:
                items = items[:top]
            return {
                "principals": {k: dict(e) for k, e in items},
                "totals": totals,
                "trackedPrincipals": len(self._p),
                "spilledPrincipals": self.spilled_principals,
                "maxPrincipals": self.max_principals,
            }

    def sample_tick(self, ts: Optional[float] = None) -> Optional[int]:
        """One delta tick into the ring (driven by the telemetry sampler):
        {principal: {field: delta}} for principals active since the last
        tick. Ring-bounded, so usage history memory is fixed regardless of
        principal count or poller behavior."""
        with self._lock:
            cur = {k: {f: e[f] for f in FIELDS} for k, e in self._p.items()}
        deltas: dict[str, dict] = {}
        for p, e in cur.items():
            prev = self._prev.get(p, {})
            d = {f: round(e[f] - prev.get(f, 0.0), 3) for f in FIELDS
                 if e[f] - prev.get(f, 0.0) > 0}
            if d:
                deltas[p] = d
        self._prev = cur
        if not deltas:
            # still advance the cursor so pollers see quiet ticks cheaply
            return self.ring.append({}, ts=ts)
        return self.ring.append(deltas, ts=ts)

    def since(self, cursor: int = 0, limit: int = 0) -> dict:
        return self.ring.since(cursor, limit)

    def clear(self) -> None:
        with self._lock:
            self._p.clear()
            self._prev = {}
            self.spilled_principals = 0


# ---------------------------------------------------------------------------
# SLO objectives + burn-rate tracking
# ---------------------------------------------------------------------------

# PQL call name -> query class for [slo] objectives. Bitmap reads are the
# "read" class (point reads); aggregations map to their own classes.
_CLASS_BY_CALL = {
    "Row": "read", "Union": "read", "Intersect": "read",
    "Difference": "read", "Xor": "read", "Not": "read", "Range": "read",
    "Count": "count", "TopN": "topn", "GroupBy": "groupby",
}

QUERY_CLASSES = ("read", "count", "topn", "groupby")


def classify_query(query) -> str:
    """Query class of a request for SLO bucketing: the FIRST call decides
    (multi-call requests are rare on the serving path and a single class
    keeps the objective math unambiguous)."""
    calls = getattr(query, "calls", None)
    if not calls:
        return "other"
    call = calls[0]
    name = getattr(call, "name", "")
    if name == "Options" and getattr(call, "children", None):
        name = getattr(call.children[0], "name", "")
    return _CLASS_BY_CALL.get(name, "other")


class Objective:
    """One SLO: `qclass` None = all queries (availability); `latency_ms`
    None = availability only (bad = error), else bad = error OR slower
    than the target. `target` is the good-event fraction (0.999 = three
    nines); the error budget is 1 - target."""

    __slots__ = ("name", "qclass", "latency_ms", "target")

    def __init__(self, name: str, qclass: Optional[str],
                 latency_ms: Optional[float], target: float):
        if not 0.0 < target < 1.0:
            raise ValueError(f"slo target must be in (0, 1), got {target}")
        self.name = name
        self.qclass = qclass
        self.latency_ms = latency_ms
        self.target = target


_STATUS_LEVEL = {"green": 0, "yellow": 1, "red": 2}


class SLOTracker:
    """Multi-window burn-rate evaluation over bucketed event counts.

    Observations land in fixed-width time buckets per objective (bounded:
    long_window / BUCKET_S buckets survive trimming), so memory is O(1)
    per objective regardless of traffic. Burn rate over a window =
    (bad / total) / (1 - target); an objective goes yellow/red only when
    BOTH the short (5m) and long (1h) windows exceed the threshold — the
    standard multi-window guard against paging on a blip."""

    BUCKET_S = 15.0

    def __init__(self, objectives: list[Objective],
                 short_window: float = 300.0, long_window: float = 3600.0,
                 burn_yellow: float = 6.0, burn_red: float = 14.4):
        if short_window <= 0 or long_window < short_window:
            raise ValueError("slo windows must satisfy 0 < short <= long")
        self.objectives = list(objectives)
        self.short_window = short_window
        self.long_window = long_window
        self.burn_yellow = burn_yellow
        self.burn_red = burn_red
        self._lock = threading.Lock()
        # per objective: deque of [bucket_start_monotonic, total, bad]
        self._buckets: list[collections.deque] = [
            collections.deque() for _ in self.objectives]

    def observe(self, qclass: str, elapsed_s: float, ok: bool,
                now: Optional[float] = None) -> None:
        if not self.objectives:
            return
        if now is None:
            now = time.monotonic()
        with self._lock:
            for ob, dq in zip(self.objectives, self._buckets):
                if ob.qclass is not None and ob.qclass != qclass:
                    continue
                bad = (not ok) or (ob.latency_ms is not None
                                   and elapsed_s * 1e3 > ob.latency_ms)
                if dq and now - dq[-1][0] < self.BUCKET_S:
                    b = dq[-1]
                else:
                    dq.append([now, 0, 0])
                    b = dq[-1]
                    self._trim(dq, now)
                b[1] += 1
                if bad:
                    b[2] += 1

    def _trim(self, dq: collections.deque, now: float) -> None:
        horizon = now - self.long_window - self.BUCKET_S
        while dq and dq[0][0] < horizon:
            dq.popleft()

    def _window(self, dq, now: float, span: float) -> tuple[int, int]:
        total = bad = 0
        cutoff = now - span
        for ts, t, b in dq:
            if ts + self.BUCKET_S >= cutoff:
                total += t
                bad += b
        return total, bad

    def evaluate(self, now: Optional[float] = None) -> dict:
        """{objective: {burnShort, burnLong, status, target, latencyMs,
        class, totals...}} — the slo/* gauge source. Objectives with no
        traffic report burn 0 / green (an idle class is not a violation)."""
        if now is None:
            now = time.monotonic()
        out: dict[str, dict] = {}
        with self._lock:
            for ob, dq in zip(self.objectives, self._buckets):
                budget = 1.0 - ob.target
                ts, bs = self._window(dq, now, self.short_window)
                tl, bl = self._window(dq, now, self.long_window)
                burn_s = (bs / ts / budget) if ts else 0.0
                burn_l = (bl / tl / budget) if tl else 0.0
                if burn_s >= self.burn_red and burn_l >= self.burn_red:
                    status = "red"
                elif burn_s >= self.burn_yellow \
                        and burn_l >= self.burn_yellow:
                    status = "yellow"
                else:
                    status = "green"
                out[ob.name] = {
                    "class": ob.qclass or "all",
                    "latencyMs": ob.latency_ms,
                    "target": ob.target,
                    "burnShort": round(burn_s, 3),
                    "burnLong": round(burn_l, 3),
                    "status": status,
                    "windowShortTotal": ts, "windowShortBad": bs,
                    "windowLongTotal": tl, "windowLongBad": bl,
                }
        return out

    def worst(self, now: Optional[float] = None) -> tuple[str, str]:
        """(status, reason) of the worst-burning objective — the health
        score's SLO input. Green objectives contribute no reason."""
        worst_status, reason = "green", ""
        for name, ob in self.evaluate(now).items():
            if _STATUS_LEVEL[ob["status"]] > _STATUS_LEVEL[worst_status]:
                worst_status = ob["status"]
                reason = (f"SLO {name} burning error budget at "
                          f"{ob['burnShort']:g}x (5m) / "
                          f"{ob['burnLong']:g}x (1h)")
        return worst_status, reason
