"""Fragment heat maps: per-(index, field, view, shard) data temperature.

ROADMAP items 3 (elastic resize) and 4 (tiered storage) both require
placement and prefetch to be *telemetry-informed* by per-fragment access
patterns, but the stack's residency hit/miss rates and churn counters are
aggregates — they say the cache is thrashing, not WHICH data is hot. The
reference keeps per-row access ranking alive in its cache layer (fragment
`top` caches); the hot/cold separation literature (the roaring papers'
array/bitmap/run split) is the same decision made per container from
observed use. This module is the measurement plane those decisions will
steer by:

* `HeatTracker`: a bounded table keyed by (index, field, view, shard) —
  the fragment coordinate every placement decision is made at. Each entry
  carries multi-half-life exponentially-decayed access counts split by
  read/write (1m / 10m / 1h half-lives: the short window ranks eviction,
  the long windows rank tiering), attributed device-ms (riding the
  profiler's dispatch-attribution discipline), host->device reload bytes,
  residency upload/eviction transition counts, and last-touch monotonic
  timestamps. Cold entries spill into a `~other` aggregate exactly like
  the UsageLedger's principal spill, so an unbounded fragment space
  (per-tenant indexes, time-quantum view fan-out) cannot OOM the server —
  totals stay exact, only per-fragment resolution of the spilled tail is
  lost.
* Charge sites thread through the executor's row-leaf reads, the
  DeviceResidency upload/evict transitions, plan-cache hits (a cached
  read still HEATS its operands — reuse is the strongest pin signal),
  and the write path on every replica that applies a mutation. Remote
  fan-out sub-requests execute on the owning node, so each node's
  tracker is charged for the fragments IT owns — the coordinator never
  absorbs the fleet's heat.
* Proof the signal is load-bearing: `[storage] eviction = heat` makes
  DeviceResidency evict coldest-by-heat instead of LRU (the roaring
  hot/cold split applied to HBM residency).

Disabled cost: one attribute check per charge site (the profiler's
nop-fast-path discipline). `PILOSA_TPU_HEAT=0` is the kill switch: no
tracker is built, every charge site short-circuits, and residency
eviction is forced back to `lru`.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

# the spill bucket: charges from fragments beyond the table bound land
# here (top-K-by-heat semantics — the coldest entry is merged out, never
# the data; totals stay exact)
SPILL = "~other"

# decay half-lives (seconds): short ranks eviction (what is hot NOW),
# long ranks tier assignment (what stays warm across a workload's day)
HALF_LIVES = (60.0, 600.0, 3600.0)

# cumulative per-fragment charge fields; snapshot/merge/exposition all
# iterate this one tuple so a new field cannot silently miss a surface
FIELDS = ("reads", "writes", "deviceMs", "h2dBytes", "uploads",
          "evictions")

# an entry counts as "hot" (heat.hot_fragments gauge, advisor pin set)
# when its composite score clears this; chosen so one access inside the
# 10m half-life window qualifies and a fragment idle for ~an hour does not
HOT_SCORE = 1e-3

# the score distribution's bucket bounds (log-decade, bounded label
# space: 7 labels regardless of fragment count) — the heat-distribution
# family scrapers alert on ("everything went cold" / "one decade holds
# the whole fleet")
DISTRIBUTION_BOUNDS = (1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0)

# models.view.VIEW_BSI_PREFIX, inlined so the attribution bridge below
# needs no models import (utils must stay importable under the model
# tree); the BSI leaf kinds carry no view name in their residency keys,
# and the executor's plane reads charge at the real bsig_<field> view —
# both sides must land on the same fragment coordinate
_BSI_VIEW_PREFIX = "bsig_"


def enabled() -> bool:
    """PILOSA_TPU_HEAT=0 kills tracking at construction AND forces
    residency eviction back to lru (read at Executor construction and
    re-checked by the eviction path per pass)."""
    return os.environ.get("PILOSA_TPU_HEAT", "1") != "0"


def _new_entry(now: float) -> dict:
    return {
        "reads": 0.0, "writes": 0.0, "deviceMs": 0.0, "h2dBytes": 0.0,
        "uploads": 0.0, "evictions": 0.0,
        # exponentially-decayed event counts per half-life: after hl
        # seconds with no touches the count halves (the EWMA decay math
        # pinned by tests/test_heat.py)
        "rEwma": [0.0] * len(HALF_LIVES),
        "wEwma": [0.0] * len(HALF_LIVES),
        "t": now,  # last decay time
        "lastRead": None, "lastWrite": None,
    }


def _decay(e: dict, now: float) -> None:
    dt = now - e["t"]
    if dt <= 0:
        return
    for i, hl in enumerate(HALF_LIVES):
        f = 0.5 ** (dt / hl)
        e["rEwma"][i] *= f
        e["wEwma"][i] *= f
    e["t"] = now


def _score(e: dict) -> float:
    """Composite heat: the sum of estimated access rates across windows,
    reads and writes alike (a write-hot fragment churns generations and
    is as placement-relevant as a read-hot one). Decayed count / half-life
    approximates events-per-second over that window, so short-window
    activity dominates — exactly the ranking eviction wants — while the
    long windows keep a steadily-warm fragment above a one-burst one."""
    return sum((e["rEwma"][i] + e["wEwma"][i]) / hl
               for i, hl in enumerate(HALF_LIVES))


def leaf_frag_keys(key) -> list[tuple]:
    """(index, field, view, shard) coordinates a residency leaf key
    covers — the attribution bridge between the residency manager's
    version-keyed entries and the tracker's fragment table. Best-effort
    by construction: synthetic leaves ("zeros") and unknown future kinds
    return [] and simply go unattributed rather than mis-charged."""
    if not isinstance(key, tuple) or not key:
        return []
    kind = key[0]
    try:
        if kind == "row" and len(key) >= 7:
            _, index, field, view, _row, shards, _gens = key[:7]
            return [(index, field, view, int(s)) for s in shards]
        if kind in ("sparse", "run") and len(key) >= 8:
            # hybrid sparse/run row leaf (parallel/residency.py
            # HybridManager): same fragment coverage as "row", one extra
            # slot-count field
            _, index, field, view, _row, shards, _slots, _gens = key[:8]
            return [(index, field, view, int(s)) for s in shards]
        if kind == "timerange" and len(key) >= 7:
            _, index, field, _row, views, shards, _gens = key[:7]
            return [(index, field, v, int(s))
                    for v in views for s in shards]
        if kind == "bsicmp" and len(key) >= 8:
            _, index, field, _op, _val, _depth, shards, _gens = key[:8]
            return [(index, field, _BSI_VIEW_PREFIX + field, int(s))
                    for s in shards]
        if kind == "bsiplanes" and len(key) >= 6:
            _, index, field, _depth, shards, _gens = key[:6]
            return [(index, field, _BSI_VIEW_PREFIX + field, int(s))
                    for s in shards]
        if kind == "pairs" and len(key) >= 6:
            _, index, field, view, shards, _gens = key[:6]
            return [(index, field, view, int(s)) for s in shards]
        if kind == "rows_slab" and len(key) >= 7:
            _, index, field, view, shards, _rows, _gens = key[:7]
            return [(index, field, view, int(s)) for s in shards]
    except (TypeError, ValueError):
        return []
    return []


class HeatTracker:
    """Bounded per-fragment temperature table + a since-cursor tick ring.

    Bound: at most `max_fragments` tracked entries. A new fragment
    arriving at capacity merges the lowest-score entry's cumulative
    charges into the SPILL aggregate (top-K by heat survives; totals
    stay exact). `sample_tick()` (driven by the telemetry sampler)
    appends aggregate summaries into a bounded ring served at
    `GET /debug/heat?since=` — the /debug/timeseries cursor contract."""

    def __init__(self, max_fragments: int = 4096, ring_size: int = 360):
        from pilosa_tpu.utils.telemetry import Ring
        self.enabled = True  # runtime toggle (bench A/B); the env kill
        # switch is read at Executor construction (no tracker is built)
        self.max_fragments = max(2, int(max_fragments))
        self._lock = threading.Lock()
        self._f: dict[tuple, dict] = {}
        self._other = dict.fromkeys(FIELDS, 0.0)  # the SPILL aggregate
        self.spilled_fragments = 0
        self.ring = Ring(ring_size)

    # -- charging (the hot path) -------------------------------------------

    def touch(self, index: str, field: str, view: str, shard: int,
              reads: int = 0, writes: int = 0, device_ms: float = 0.0,
              h2d_bytes: int = 0, uploads: int = 0, evictions: int = 0,
              now: Optional[float] = None) -> None:
        self.touch_many([(index, field, view, int(shard))], reads=reads,
                        writes=writes, device_ms=device_ms,
                        h2d_bytes=h2d_bytes, uploads=uploads,
                        evictions=evictions, now=now)

    def touch_many(self, keys: list, reads: int = 0, writes: int = 0,
                   device_ms: float = 0.0, h2d_bytes: int = 0,
                   uploads: int = 0, evictions: int = 0,
                   now: Optional[float] = None) -> None:
        """Charge every key under ONE lock acquisition (a query touching
        16 shards x 4 leaves must not pay 64 lock round trips). device_ms
        and h2d_bytes are TOTALS split evenly across the keys — the
        attribution convention of batched dispatch shares: a slab upload
        serves all its shards, so each is charged its seat."""
        if not self.enabled or not keys:
            return
        if now is None:
            now = time.monotonic()
        with self._lock:
            self._charge_locked(keys, now, reads, writes, device_ms,
                                h2d_bytes, uploads, evictions)

    def touch_groups(self, groups: list,
                     now: Optional[float] = None) -> None:
        """touch_many for several (keys, reads, device_ms) groups under
        ONE lock acquisition: a request's leaves over several fields,
        each field's fragments read as often as the request names it and
        given its share of the device time."""
        if not self.enabled:
            return
        if now is None:
            now = time.monotonic()
        with self._lock:
            for keys, reads, device_ms in groups:
                if keys:
                    self._charge_locked(keys, now, reads, 0, device_ms,
                                        0, 0, 0)

    def _charge_locked(self, keys: list, now: float, reads: int,
                       writes: int, device_ms: float, h2d_bytes: int,
                       uploads: int, evictions: int) -> None:
        share_ms = device_ms / len(keys)
        share_bytes = h2d_bytes / len(keys)
        for key in keys:
            e = self._f.get(key)
            if e is None:
                if len(self._f) >= self.max_fragments:
                    self._spill_locked(now)
                e = self._f[key] = _new_entry(now)
            _decay(e, now)
            if reads:
                e["reads"] += reads
                e["lastRead"] = now
                for i in range(len(HALF_LIVES)):
                    e["rEwma"][i] += reads
            if writes:
                e["writes"] += writes
                e["lastWrite"] = now
                for i in range(len(HALF_LIVES)):
                    e["wEwma"][i] += writes
            e["deviceMs"] += share_ms
            e["h2dBytes"] += share_bytes
            e["uploads"] += uploads
            e["evictions"] += evictions

    def _spill_locked(self, now: float) -> None:
        """At capacity: merge the lowest-score entry's cumulative fields
        into the SPILL aggregate (decayed heat state is discarded — a
        spilled fragment was cold by definition, and re-heating recreates
        its entry from scratch)."""
        victim_key = None
        victim_score = None
        for k, e in self._f.items():
            _decay(e, now)
            s = _score(e)
            if victim_score is None or s < victim_score \
                    or (s == victim_score and k < victim_key):
                victim_key, victim_score = k, s
        if victim_key is None:
            return
        victim = self._f.pop(victim_key)
        for f in FIELDS:
            self._other[f] += victim[f]
        self.spilled_fragments += 1

    # -- read side ----------------------------------------------------------

    def scores_for(self, keys: list, now: Optional[float] = None) -> list:
        """Heat scores for `keys` (0.0 for untracked), one lock
        acquisition — the residency manager's coldest-first eviction
        ranks its occupants through this."""
        if now is None:
            now = time.monotonic()
        out = []
        with self._lock:
            for key in keys:
                e = self._f.get(key)
                if e is None:
                    out.append(0.0)
                    continue
                _decay(e, now)
                out.append(_score(e))
        return out

    def totals(self) -> dict:
        """Exact sums over every fragment ever charged (spill included) —
        the heat/* counter families and the cross-surface audit anchor."""
        with self._lock:
            out = dict(self._other)
            for e in self._f.values():
                for f in FIELDS:
                    out[f] += e[f]
            return out

    @staticmethod
    def _entry_doc(key: tuple, e: dict, score: float,
                   now: float) -> dict:
        index, field, view, shard = key
        return {
            "index": index, "field": field, "view": view,
            "shard": int(shard),
            "score": round(score, 6),
            "readsPerS": round(e["rEwma"][0] / HALF_LIVES[0], 6),
            "writesPerS": round(e["wEwma"][0] / HALF_LIVES[0], 6),
            "reads": round(e["reads"], 3),
            "writes": round(e["writes"], 3),
            "deviceMs": round(e["deviceMs"], 3),
            "h2dBytes": round(e["h2dBytes"], 1),
            "uploads": round(e["uploads"], 1),
            "evictions": round(e["evictions"], 1),
            "lastReadAgeS": (round(now - e["lastRead"], 3)
                             if e["lastRead"] is not None else None),
            "lastWriteAgeS": (round(now - e["lastWrite"], 3)
                              if e["lastWrite"] is not None else None),
        }

    def snapshot(self, top: int = 20, now: Optional[float] = None) -> dict:
        """The /debug/heat document: `hot` (score desc) and `cold`
        (score asc, tracked-but-coolest — the eviction/tier-down
        candidates) lists bounded by `top` (0 = all tracked, in which
        case `cold` is omitted: `hot` already carries everything), exact
        totals, the score distribution (cumulative counts under
        DISTRIBUTION_BOUNDS — bounded labels), and the skew gauge
        (hottest / mean score: 1.0 = perfectly even, large = one
        fragment dominates — the rebalancing trigger)."""
        if now is None:
            now = time.monotonic()
        with self._lock:
            scored = []
            for k, e in self._f.items():
                _decay(e, now)
                scored.append((k, e, _score(e)))
            # deterministic order: score desc, then key asc — two
            # replays of one trace must produce byte-identical documents
            scored.sort(key=lambda t: (-t[2], t[0]))
            totals = dict(self._other)
            for _k, e, _s in scored:
                for f in FIELDS:
                    totals[f] += e[f]
            scores = [s for _k, _e, s in scored]
            mean = (sum(scores) / len(scores)) if scores else 0.0
            skew = (scores[0] / mean) if mean > 0 else 1.0
            dist = {}
            cum = 0
            for bound in DISTRIBUTION_BOUNDS:
                cum = sum(1 for s in scores if s <= bound)
                dist[f"{bound:g}"] = cum
            dist["+Inf"] = len(scores)
            hot_n = sum(1 for s in scores if s >= HOT_SCORE)
            hot = [self._entry_doc(k, e, s, now)
                   for k, e, s in (scored[:top] if top > 0 else scored)]
            cold = []
            if top > 0:
                cold = [self._entry_doc(k, e, s, now)
                        for k, e, s in sorted(
                            scored, key=lambda t: (t[2], t[0]))[:top]]
            return {
                "hot": hot,
                "cold": cold,
                "totals": {f: round(v, 3) for f, v in totals.items()},
                "trackedFragments": len(scored),
                "spilledFragments": self.spilled_fragments,
                "maxFragments": self.max_fragments,
                "hotFragments": hot_n,
                "skew": round(skew, 4),
                "distribution": dist,
            }

    def sample_tick(self, ts: Optional[float] = None,
                    now: Optional[float] = None) -> dict:
        """One aggregate summary into the ring (driven by the telemetry
        sampler) and returned for the heat.* gauge series. Ring-bounded,
        so heat history memory is fixed regardless of fragment count."""
        if now is None:
            now = time.monotonic()
        with self._lock:
            scores = []
            for e in self._f.values():
                _decay(e, now)
                scores.append(_score(e))
            mean = (sum(scores) / len(scores)) if scores else 0.0
            summary = {
                "hotFragments": sum(1 for s in scores if s >= HOT_SCORE),
                "skew": round(max(scores) / mean, 4)
                if mean > 0 else 1.0,
                "trackerEntries": len(scores),
            }
        self.ring.append(summary, ts=ts)
        return summary

    def since(self, cursor: int = 0, limit: int = 0) -> dict:
        return self.ring.since(cursor, limit)

    def clear(self) -> None:
        with self._lock:
            self._f.clear()
            self._other = dict.fromkeys(FIELDS, 0.0)
            self.spilled_fragments = 0


def merge_heat_docs(docs: dict) -> dict:
    """Merge per-node /debug/heat documents into the fleet view
    (GET /cluster/heat): per-fragment fields and scores SUM across nodes
    (two replicas each serving a fragment's reads make it twice as hot
    fleet-wide — the signal shard rebalancing wants), totals and spill
    counts sum, and the fleet skew is recomputed over the merged scores.
    `docs` maps node id -> that node's heat document."""
    merged: dict[tuple, dict] = {}
    totals = dict.fromkeys(FIELDS, 0.0)
    spilled = 0
    for doc in docs.values():
        for e in (doc.get("hot") or []):
            key = (e.get("index"), e.get("field"), e.get("view"),
                   int(e.get("shard", 0)))
            acc = merged.get(key)
            if acc is None:
                acc = merged[key] = {
                    "index": key[0], "field": key[1], "view": key[2],
                    "shard": key[3], "score": 0.0, "readsPerS": 0.0,
                    "writesPerS": 0.0, "nodes": 0,
                    **{f: 0.0 for f in FIELDS}}
            for f in FIELDS:
                acc[f] = round(acc[f] + float(e.get(f, 0.0)), 3)
            for f in ("score", "readsPerS", "writesPerS"):
                acc[f] = round(acc[f] + float(e.get(f, 0.0)), 6)
            acc["nodes"] += 1
        for f in FIELDS:
            totals[f] += float((doc.get("totals") or {}).get(f, 0.0))
        spilled += int(doc.get("spilledFragments", 0))
    ordered = sorted(merged.values(),
                     key=lambda e: (-e["score"], e["index"], e["field"],
                                    e["view"], e["shard"]))
    scores = [e["score"] for e in ordered]
    mean = (sum(scores) / len(scores)) if scores else 0.0
    return {
        "hot": ordered,
        "totals": {f: round(v, 3) for f, v in totals.items()},
        "trackedFragments": len(ordered),
        "spilledFragments": spilled,
        "hotFragments": sum(1 for s in scores if s >= HOT_SCORE),
        "skew": round(scores[0] / mean, 4) if mean > 0 else 1.0,
    }
