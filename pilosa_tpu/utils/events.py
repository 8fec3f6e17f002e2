"""Cluster flight recorder: typed, HLC-stamped structured event journal.

The stack can already say *how much* (stats + telemetry rings), *where
time went* (profiles/traces) and *what's hot* (heat maps) — but not
*what happened*: state transitions (drains, read fences, hint replays,
WAL truncations, quarantines, shed storms, topology churn) were scattered
across log lines whose wall-clock timestamps don't order across nodes.
Three pieces live here:

* `HybridLogicalClock`: Lamport-style HLC — a (physical-ms, logical)
  pair where the physical half tracks `max(local wall, anything seen)`
  and the logical half breaks ties. Every inter-node hop (internal RPC
  headers, gossip datagrams) piggybacks the sender's stamp and the
  receiver merges it, so cross-node event order is CAUSAL: an event a
  node records after hearing from a peer always sorts after the peer's
  event that caused it, even under badly skewed wall clocks.
* `EVENT_TYPES` + `EventJournal`: the typed registry (emitting an
  unregistered type raises — the lint rule `event-registry` keeps call
  sites honest) over a bounded per-node in-memory ring with SEPARATE
  severity lanes (a `log.warn` storm can never evict the lifecycle
  events an incident reconstruction needs), `since()` cursors on the
  `/debug/timeseries` discipline, and an optional durable spool.
* crash forensics: `register_crash_dump` + SIGQUIT handler spill every
  registered journal to `events.crash-<ts>.jsonl` next to its data dir,
  so the flight recorder survives the crash it recorded the approach of.

`PILOSA_TPU_EVENTS=0` is the kill switch (read per emit — operators and
the bench A/B flip it at runtime).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Callable, Optional

from pilosa_tpu.utils.tracing import TimedLock

# -- kill switch -------------------------------------------------------------


def enabled() -> bool:
    """PILOSA_TPU_EVENTS=0 kills all event recording (read per emit)."""
    return os.environ.get("PILOSA_TPU_EVENTS", "1") != "0"


# -- hybrid logical clock ----------------------------------------------------

# HTTP header piggybacking the sender's HLC on every internal RPC (and
# its response) — the gossip datagrams carry the same stamp in an `hlc`
# field. Merging at every receive site is what makes the merged cluster
# timeline causal instead of wall-clock.
HLC_HEADER = "X-Pilosa-HLC"


class HybridLogicalClock:
    """A (physical_ms, logical) hybrid logical clock (Kulkarni et al.):
    `now()` stamps a local event, `update(remote)` merges a received
    stamp. physical_ms never runs backwards (a stepped wall clock only
    stalls it; the logical counter keeps events ordered through the
    stall), and a merge lifts it to the remote's view — so causally
    later events always carry larger stamps, skew be damned."""

    def __init__(self, wall_ms: Optional[Callable[[], int]] = None):
        # injectable wall source: the skewed-clock tests give each node
        # a deliberately wrong wall and assert causality survives
        self._wall_ms = wall_ms or (
            lambda: int(time.time() * 1000))  # wall-clock: HLC physical half
        self._lock = TimedLock("events.hlc", threading.Lock())
        self._physical = 0
        self._logical = 0

    def now(self) -> tuple[int, int]:
        """Stamp one local event (send or record)."""
        wall = self._wall_ms()
        with self._lock:
            if wall > self._physical:
                self._physical = wall
                self._logical = 0
            else:
                self._logical += 1
            return self._physical, self._logical

    def update(self, remote) -> tuple[int, int]:
        """Merge a received stamp (an HLC pair / [ms, lc] list) and stamp
        the receive event. Garbage merges as a plain local tick."""
        try:
            r_p, r_l = int(remote[0]), int(remote[1])
        except (TypeError, ValueError, IndexError):
            return self.now()
        wall = self._wall_ms()
        with self._lock:
            if wall > self._physical and wall > r_p:
                self._physical = wall
                self._logical = 0
            elif r_p > self._physical:
                self._physical = r_p
                self._logical = r_l + 1
            elif r_p == self._physical:
                self._logical = max(self._logical, r_l) + 1
            else:
                self._logical += 1
            return self._physical, self._logical

    def peek(self) -> tuple[int, int]:
        with self._lock:
            return self._physical, self._logical


def encode_hlc(stamp: tuple[int, int]) -> str:
    """Wire form for the HTTP header / gossip field: "<ms>.<logical>"."""
    return f"{stamp[0]}.{stamp[1]}"


def decode_hlc(value) -> Optional[tuple[int, int]]:
    """Inverse of encode_hlc; None for absent/garbage (never raises —
    a malformed header from a hostile client must not break dispatch)."""
    if not value or not isinstance(value, str):
        return None
    head, _, tail = value.partition(".")
    try:
        return int(head), int(tail or 0)
    except ValueError:
        return None


# -- typed event registry ----------------------------------------------------

# severity lanes: each lane is its own bounded ring, so a storm in one
# (log lines under an error loop) can never evict the other (the
# lifecycle transitions an incident reconstruction needs)
LANE_LIFECYCLE = "lifecycle"
LANE_LOG = "log"
LANES = (LANE_LIFECYCLE, LANE_LOG)

# type -> (lane, description). The ONE registry: EventJournal.emit
# refuses unregistered types, the `event-registry` lint rule refuses
# non-literal types at call sites, and the inventory diff refuses types
# missing from the docs/operations.md glossary — the stats-registry
# discipline applied to events.
EVENT_TYPES: dict[str, tuple[str, str]] = {
    # node lifecycle
    "node.start": (LANE_LIFECYCLE, "server process opened its holder and "
                                   "began serving"),
    "node.stop": (LANE_LIFECYCLE, "server close() began"),
    "drain.start": (LANE_LIFECYCLE, "graceful drain began: new external "
                                    "queries shed, DRAINING broadcast"),
    "drain.complete": (LANE_LIFECYCLE, "drain finished: in-flight work "
                                       "settled, final snapshots landed"),
    "drain.abort": (LANE_LIFECYCLE, "drain cancelled; READY re-announced"),
    # peer view transitions (this node's observation of a peer)
    "peer.draining": (LANE_LIFECYCLE, "peer announced DRAINING; routing "
                                      "around it"),
    "peer.down": (LANE_LIFECYCLE, "peer marked down (liveness/gossip)"),
    "peer.up": (LANE_LIFECYCLE, "peer marked back up"),
    "peer.rejoined": (LANE_LIFECYCLE, "peer announced READY after a "
                                      "drain/outage; return-heal started"),
    # rejoin read fence
    "fence.armed": (LANE_LIFECYCLE, "local shards read-fenced pending "
                                    "parity verification"),
    "fence.lifted": (LANE_LIFECYCLE, "a fenced shard verified parity (or "
                                     "healed) and lifted"),
    "fence.expired": (LANE_LIFECYCLE, "fence timed out unverified; "
                                      "availability won, scrubber heals"),
    # durable hinted handoff
    "hint.append": (LANE_LIFECYCLE, "replica write skipped (target "
                                    "down/draining) queued to its hint "
                                    "log"),
    "hint.replay": (LANE_LIFECYCLE, "queued hints streamed to a returned "
                                    "peer"),
    "hint.drop": (LANE_LIFECYCLE, "hint dropped (byte/age cap, damage); "
                                  "anti-entropy must finish the heal"),
    # storage integrity
    "wal.truncated": (LANE_LIFECYCLE, "torn WAL tail truncated at open"),
    "snapshot.quarantined": (LANE_LIFECYCLE, "fragment snapshot failed "
                                             "integrity; quarantined and "
                                             "reopened empty"),
    "scrub.pass": (LANE_LIFECYCLE, "anti-entropy scrub pass completed"),
    # QoS overload control
    "qos.shed_storm.start": (LANE_LIFECYCLE, "shed/throttle rate crossed "
                                             "the storm threshold"),
    "qos.shed_storm.end": (LANE_LIFECYCLE, "shed storm subsided"),
    "qos.quota_debt": (LANE_LIFECYCLE, "a principal's quota bucket went "
                                       "into deep debt (rate-limited per "
                                       "principal)"),
    # device / compile health
    "xla.recompile_storm": (LANE_LIFECYCLE, "one kernel family compiled a "
                                            "storm of new shapes"),
    "health.transition": (LANE_LIFECYCLE, "this node's health score "
                                          "changed (green/yellow/red)"),
    # cluster shape
    "topology.change": (LANE_LIFECYCLE, "cluster topology fingerprint "
                                        "changed (membership, liveness, "
                                        "drain set)"),
    "ici.route_flip": (LANE_LIFECYCLE, "a memoized slice-local routing "
                                       "decision flipped under a new "
                                       "topology"),
    "resize.start": (LANE_LIFECYCLE, "cluster resize job started"),
    "resize.complete": (LANE_LIFECYCLE, "cluster resize job completed"),
    "resize.abort": (LANE_LIFECYCLE, "cluster resize job aborted"),
    # logger bridge (utils/logger.py warnf/errorf)
    "log.warn": (LANE_LOG, "a WARN log line (logger bridge)"),
    "log.error": (LANE_LOG, "an ERROR log line (logger bridge)"),
}


def event_lane(etype: str) -> str:
    return EVENT_TYPES[etype][0]


# -- the journal -------------------------------------------------------------


class EventJournal:
    """One node's flight-recorder ring: bounded per-lane deques under one
    ascending seq, every event stamped by the node's HLC. `since(cursor)`
    serves the `/debug/events` feed (each event crosses the wire once
    per poller, the `/debug/timeseries` discipline); an optional durable
    spool appends JSONL so events survive the process."""

    def __init__(self, node_id: str = "", ring_size: int = 2048,
                 clock: Optional[HybridLogicalClock] = None,
                 spool_path: str = "", spool_max_bytes: int = 0,
                 stats=None):
        self.node_id = node_id
        self.ring_size = max(1, int(ring_size))
        self.clock = clock or HybridLogicalClock()
        # the log lane is the storm-prone one; it gets its own (smaller)
        # budget so it can NEVER evict lifecycle events
        self._lanes: dict[str, collections.deque] = {
            LANE_LIFECYCLE: collections.deque(maxlen=self.ring_size),
            LANE_LOG: collections.deque(
                maxlen=max(1, self.ring_size // 4)),
        }
        self._lock = threading.Lock()
        self._seq = 0
        self.stats = stats
        # durable spool: append-only JSONL, hard byte cap with ONE
        # rotation (<path>.1) so the spool can never fill the disk
        self.spool_path = spool_path
        self.spool_max_bytes = int(spool_max_bytes)
        self._spool_bytes = 0
        self.spool_errors = 0
        self.emitted = 0
        self.reloaded = 0
        self.dropped_disabled = 0
        self.evicted: dict[str, int] = dict.fromkeys(LANES, 0)
        self.by_type: dict[str, int] = {}
        if spool_path and self.spool_max_bytes > 0:
            # a durable spool survives the process: reload its tail into
            # the ring at boot, so a drained-and-restarted node still
            # contributes its pre-restart lifecycle (drain.start, ...)
            # to the merged cluster timeline
            self._reload_spool()

    def _reload_spool(self) -> None:
        """Refill the ring from the spool's tail (previous process's
        events, original HLC stamps kept) and advance the clock past the
        newest reloaded stamp so new events always sort after them."""
        try:
            with open(self.spool_path, encoding="utf-8") as f:
                lines = f.readlines()
        except OSError:
            return
        last_hlc = None
        # the lanes bound what can be retained; parsing more is wasted
        for line in lines[-(self.ring_size + self.ring_size // 4):]:
            try:
                e = json.loads(line)
            except ValueError:
                continue  # torn tail line from a crash: skip
            lane_desc = EVENT_TYPES.get(
                e.get("type")) if isinstance(e, dict) else None
            if lane_desc is None:
                continue
            with self._lock:
                self._seq += 1
                e = dict(e, seq=self._seq)
                self._lanes[lane_desc[0]].append(e)
                self.reloaded += 1
            if e.get("hlc"):
                last_hlc = e["hlc"]
        if last_hlc is not None:
            self.clock.update(last_hlc)

    # -- emit ---------------------------------------------------------------

    def emit(self, etype: str, **fields) -> Optional[dict]:
        """Record one event. `etype` MUST be registered (ValueError
        otherwise — the typed-registry contract); trace id and principal
        auto-attach from the request context when present. Returns the
        event dict, or None when the kill switch is off."""
        lane_desc = EVENT_TYPES.get(etype)
        if lane_desc is None:
            raise ValueError(
                f"unregistered event type {etype!r} — add it to "
                "pilosa_tpu.utils.events.EVENT_TYPES (and the "
                "docs/operations.md glossary)")
        if not enabled():
            self.dropped_disabled += 1
            return None
        lane = lane_desc[0]
        stamp = self.clock.now()
        ev: dict = {
            "hlc": [stamp[0], stamp[1]],
            "ts": round(time.time(), 3),  # wall-clock: human-facing only
            "type": etype,
            "node": self.node_id,
        }
        trace = _current_trace()
        if trace:
            ev["trace"] = trace
        principal = _current_principal()
        if principal and "principal" not in fields:
            ev["principal"] = principal
        for k, v in fields.items():
            if v is not None:
                ev[k] = v
        with self._lock:
            self._seq += 1
            ev["seq"] = self._seq
            dq = self._lanes[lane]
            if len(dq) == dq.maxlen:
                self.evicted[lane] += 1
            dq.append(ev)
            self.emitted += 1
            self.by_type[etype] = self.by_type.get(etype, 0) + 1
        if self.stats is not None:
            # family "events" + a `type` label -> the unconditional
            # pilosa_events_total{type=...} Prometheus family
            self.stats.count(f"events,type:{etype}")
        if self.spool_path and self.spool_max_bytes > 0:
            self._spool(ev)
        return ev

    def _spool(self, ev: dict) -> None:
        line = json.dumps(ev, separators=(",", ":")) + "\n"
        with self._lock:
            try:
                if self._spool_bytes == 0:
                    try:
                        self._spool_bytes = os.path.getsize(self.spool_path)
                    except OSError:
                        self._spool_bytes = 0
                if self._spool_bytes + len(line) > self.spool_max_bytes:
                    # one-deep rotation: the previous spool survives as
                    # .1; total disk is bounded at 2x the cap
                    os.replace(self.spool_path, self.spool_path + ".1")
                    self._spool_bytes = 0
                with open(self.spool_path, "a", encoding="utf-8") as f:
                    f.write(line)
                self._spool_bytes += len(line)
            except OSError:
                self.spool_errors += 1

    # -- read ---------------------------------------------------------------

    def events(self, cursor: int = 0) -> list[dict]:
        """All retained events with seq > cursor, merged across lanes in
        seq order (one node's seq order IS its causal order)."""
        with self._lock:
            out = [e for dq in self._lanes.values() for e in dq
                   if e["seq"] > cursor]
        out.sort(key=lambda e: e["seq"])
        return out

    def since(self, cursor: int = 0, limit: int = 0,
              etype: Optional[str] = None,
              severity: Optional[str] = None) -> dict:
        """The /debug/events document: events newer than `cursor` (oldest
        first; newest `limit` when set; optionally filtered by type or
        lane). The returned `seq` is the next poll's cursor even when
        nothing qualified."""
        out = self.events(cursor)
        if etype:
            out = [e for e in out if e["type"] == etype]
        if severity:
            out = [e for e in out
                   if event_lane(e["type"]) == severity]
        if limit > 0:
            out = out[-limit:]
        with self._lock:
            seq = self._seq
        return {"seq": seq, "events": out}

    def snapshot(self) -> dict:
        """The events observability block (/debug/vars)."""
        with self._lock:
            return {
                "emitted": self.emitted,
                "reloaded": self.reloaded,
                "byType": dict(sorted(self.by_type.items())),
                "evicted": dict(self.evicted),
                "droppedDisabled": self.dropped_disabled,
                "ringSize": self.ring_size,
                "retained": {lane: len(dq)
                             for lane, dq in self._lanes.items()},
                "spoolPath": self.spool_path,
                "spoolBytes": self._spool_bytes,
                "spoolErrors": self.spool_errors,
            }

    def __len__(self) -> int:
        with self._lock:
            return sum(len(dq) for dq in self._lanes.values())

    # -- crash forensics ----------------------------------------------------

    def dump(self, path: str) -> int:
        """Spill the whole retained ring to a JSONL file (crash
        forensics; also the SIGQUIT operator surface). Returns events
        written; never raises — a failing dump during a crash must not
        mask the crash."""
        try:
            evs = self.events(0)
            with open(path, "w", encoding="utf-8") as f:
                for e in evs:
                    f.write(json.dumps(e, separators=(",", ":")) + "\n")
            return len(evs)
        except OSError:
            return 0


# -- cross-node ordering ------------------------------------------------------


def hlc_sort_key(ev: dict):
    """Total order for merged multi-node timelines: HLC first (the causal
    half), node id + seq as deterministic tiebreaks for genuinely
    concurrent events."""
    hlc = ev.get("hlc") or [0, 0]
    try:
        p, l = int(hlc[0]), int(hlc[1])
    except (TypeError, ValueError, IndexError):
        p, l = 0, 0
    return (p, l, str(ev.get("node", "")), int(ev.get("seq", 0)))


def merge_events(docs: dict[str, list[dict]]) -> list[dict]:
    """Merge per-node event lists into one HLC-sorted cluster timeline."""
    merged = [e for evs in docs.values() for e in evs]
    merged.sort(key=hlc_sort_key)
    return merged


# -- crash handler ------------------------------------------------------------

# every in-process journal registered for the SIGQUIT spill (tests run
# multi-node clusters in one process; each node spills next to its own
# data dir)
_CRASH_LOCK = threading.Lock()
_CRASH_JOURNALS: list[tuple[EventJournal, str]] = []
_CRASH_INSTALLED = False


def register_crash_dump(journal: EventJournal, directory: str) -> None:
    """Register a journal for crash spilling and install the SIGQUIT
    handler (first call, main thread only — signal module rules). The
    handler writes `events.crash-<ts>.jsonl` into `directory` for every
    registered journal; the process keeps running (SIGQUIT is the
    dump-your-state operator convention here, like SIGUSR1's stacks)."""
    global _CRASH_INSTALLED
    with _CRASH_LOCK:
        _CRASH_JOURNALS.append((journal, directory))
    if _CRASH_INSTALLED:
        return
    import signal
    if threading.current_thread() is not threading.main_thread():
        return  # a later main-thread registration will install it
    try:
        signal.signal(signal.SIGQUIT, _crash_signal_handler)
        _CRASH_INSTALLED = True
    except (ValueError, OSError, AttributeError):
        pass  # no SIGQUIT on this platform / restricted env


def unregister_crash_dump(journal: EventJournal) -> None:
    with _CRASH_LOCK:
        _CRASH_JOURNALS[:] = [(j, d) for j, d in _CRASH_JOURNALS
                              if j is not journal]


def spill_all_crash_dumps() -> list[str]:
    """Write every registered journal's ring to its data dir. Shared by
    the SIGQUIT handler and any fatal path that wants forensics."""
    out: list[str] = []
    ts = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
    with _CRASH_LOCK:
        targets = list(_CRASH_JOURNALS)
    for journal, directory in targets:
        path = os.path.join(directory, f"events.crash-{ts}.jsonl")
        if journal.dump(path):
            out.append(path)
    return out


def _crash_signal_handler(_signum, _frame) -> None:
    spill_all_crash_dumps()


# -- context helpers ----------------------------------------------------------


def _current_trace() -> Optional[str]:
    try:
        from pilosa_tpu.utils import tracing
        return tracing.current_trace_id.get()
    except Exception:  # noqa: BLE001 — recording must never raise
        return None


def _current_principal() -> Optional[str]:
    try:
        from pilosa_tpu.utils import accounting
        acct = accounting.current_account.get()
        return acct.principal if acct is not None else None
    except Exception:  # noqa: BLE001 — recording must never raise
        return None
