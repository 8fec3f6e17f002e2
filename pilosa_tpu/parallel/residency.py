"""HBM residency manager: device-side LRU cache of query leaves.

The reference keeps a per-fragment `rowCache` of materialized rows
(fragment.go:112,347-378) because row materialization is its hot allocation.
Here the expensive step is the host->HBM transfer of dense row slabs, so the
cache holds *device arrays*: each bitmap-call leaf (a row, a time-range
union, a BSI comparison result) stays resident in HBM keyed by its content
version, and repeat queries run entirely from HBM. Authoritative storage
stays host-side (SURVEY.md §7 "Mutation on device"): writes bump fragment
row generations, which change the leaf key — the device copy is a cache
with natural invalidation, never a source of truth.

Eviction is LRU by byte budget, the analog of the reference's bounded row
cache (lru/ + fragment.go rowCache); freed jax.Arrays release their HBM when
the last reference drops. With `[storage] eviction = heat` the victim is
instead the coldest occupant by the fragment heat map (utils/heat.py) —
the hot/cold-separation decision applied to HBM residency, and the proof
that the heat signal is load-bearing before tiering starts steering by it.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Optional

import jax
import numpy as np

from pilosa_tpu.utils import accounting, tracing
from pilosa_tpu.utils import profile as qprofile

# a quarter of a v5e chip's 16 GB of HBM; the rest is headroom for query
# intermediates (TopN recount slabs, GroupBy axis slabs, BSI masks)
DEFAULT_BUDGET_BYTES = 4 << 30


class DeviceResidency:
    def __init__(self, runner, budget_bytes: int = DEFAULT_BUDGET_BYTES):
        self.runner = runner
        self.budget = budget_bytes
        self._lru: "OrderedDict[tuple, jax.Array]" = OrderedDict()
        self._lock = tracing.TimedLock("residency.lru", threading.Lock())
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.epoch = 0  # bumped by clear(); fences in-flight misses
        self._building: dict = {}  # key -> Event of the thread building it
        # the single-flight wait for that thread, counted under _lock
        self._build_waits = tracing.WaitCounter("residency.build")
        # fragment heat map (utils/heat.py HeatTracker, set by the
        # Executor; None = untracked): uploads/evictions and h2d reload
        # bytes are charged per fragment coordinate, and `eviction =
        # "heat"` ranks victims coldest-first by it instead of LRU.
        # The env kill switch wins structurally: with PILOSA_TPU_HEAT=0
        # no tracker exists, so eviction falls back to lru.
        self.heat = None
        self.eviction = "lru"  # [storage] eviction: lru | heat
        self.heat_evictions = 0  # victims chosen by heat (not LRU order)

    def leaf(self, key: tuple, make: Callable[[], np.ndarray],
             put: Optional[Callable] = None) -> jax.Array:
        """Return the device array for `key`, uploading via `make()` on miss.

        `key` must encode content versions (fragment row generations), so a
        write to any underlying row produces a new key and the stale entry
        ages out by LRU.

        `make()` may return a host array (uploaded via the runner) or a
        jax.Array already composed on device (e.g. a BSI comparison mask) —
        the latter is cached as-is, avoiding a device->host->device round
        trip. What is cached needs only `nbytes` (a pairs entry is an
        object around its device array). `put`, when given, replaces the
        runner's default placement for host arrays (sparse hybrid leaves
        pad with the sentinel, not zero — parallel/mesh.py put_leaf's fill
        parameter)."""
        prof = qprofile.current_profile.get()  # None = profiling off
        waited_ms = None
        while True:
            with self._lock:
                if waited_ms is not None:
                    self._build_waits.add(waited_ms)
                arr = self._lru.get(key)
                building = None
                if arr is not None:
                    self._lru.move_to_end(key)
                    self.hits += 1
                else:
                    # single-flight: one thread builds a missing key, the
                    # others wait for it and then find it resident (a
                    # plane takes milliseconds to build and a field's
                    # pairs entry seconds; thirty request threads used to
                    # build the same one at once)
                    building = self._building.get(key)
                    if building is None:
                        self._building[key] = threading.Event()
                epoch = self.epoch
            if arr is not None:
                # recorded OUTSIDE the LRU lock: the hit path is the
                # hottest section in here and must not also serialize on
                # the profile's own lock while holding it
                if prof is not None:
                    prof.record_residency(hit=True)
                return arr
            if building is None:
                break
            t0 = time.perf_counter()
            building.wait()  # then look again: resident, or ours to build
            waited_ms = (time.perf_counter() - t0) * 1e3
        try:
            return self._build(key, make, put, prof, epoch)
        finally:
            with self._lock:
                self._building.pop(key).set()

    def _build(self, key: tuple, make, put, prof, epoch: int):
        """The miss path of leaf(): build, upload, account, insert."""
        with tracing.span("leaf.build"):
            host = make()
        uploaded = not isinstance(host, jax.Array)
        if uploaded:
            with tracing.span("leaf.upload", rep=key[0],
                              bytes=host.nbytes):
                arr = (put or self.runner.put_leaf)(host)
        else:
            arr = host
        if prof is not None:
            # host->device bytes count only real uploads: a mask already
            # composed on device (bsicmp results) costs no link transfer
            prof.record_residency(hit=False,
                                  nbytes=arr.nbytes if uploaded else 0)
        if uploaded:
            # same only-real-uploads rule for per-principal accounting:
            # the HBM bytes a caller moved over the host->device link
            acct = accounting.current_account.get()
            if acct is not None:
                acct.charge(hbm_bytes=arr.nbytes)
            # fragment heat: h2d reload bytes + an upload transition per
            # covered fragment (slab bytes split evenly across shards —
            # the per-seat attribution convention). Outside the LRU lock
            # like the profiler hook: the tracker has its own lock.
            tracker = self.heat
            if tracker is not None and tracker.enabled:
                from pilosa_tpu.utils import heat as _heat
                fkeys = _heat.leaf_frag_keys(key)
                if fkeys:
                    tracker.touch_many(fkeys, h2d_bytes=arr.nbytes,
                                       uploads=1)
        with self._lock:
            self.misses += 1
            if self.epoch != epoch:
                # clear() ran while make() was in flight (field/index
                # deleted): the data may be stale — serve it to this caller
                # but never cache it, or a recreated field reaching an
                # identical generation tuple could read deleted data
                return arr
            # patch_entries can have put this key meanwhile: account for
            # the entry this insert displaces or bytes drift upward forever
            displaced = self._lru.pop(key, None)
            if displaced is not None:
                self.bytes -= displaced.nbytes
            self._lru[key] = arr
            self.bytes += arr.nbytes
            self._evict_over_budget_locked(key)
        return arr

    def _evict_over_budget_locked(self, protect: tuple) -> None:
        """Evict until under budget. `lru` mode pops the least-recently-
        used entry; `heat` mode ranks every occupant by the summed heat
        of the fragments it covers and evicts the coldest (ties fall
        back to LRU order), never the just-inserted `protect` entry.
        Heat eviction only engages while a tracker exists AND is enabled
        AND the env gate is on — any kill switch forces plain lru."""
        from pilosa_tpu.utils import heat as _heat
        tracker = self.heat
        by_heat = (self.eviction == "heat" and tracker is not None
                   and tracker.enabled and _heat.enabled())
        while self.bytes > self.budget and len(self._lru) > 1:
            victim_key = None
            if by_heat:
                candidates = [k for k in self._lru if k != protect]
                flat: list = []
                spans: list[tuple[int, int]] = []
                for k in candidates:
                    fkeys = _heat.leaf_frag_keys(k)
                    spans.append((len(flat), len(fkeys)))
                    flat.extend(fkeys)
                scores = tracker.scores_for(flat)
                best = None
                for k, (off, n) in zip(candidates, spans):
                    s = sum(scores[off:off + n])
                    if best is None or s < best:
                        victim_key, best = k, s
            if victim_key is not None:
                old = self._lru.pop(victim_key)
                self.heat_evictions += 1
            else:
                victim_key, old = self._lru.popitem(last=False)
            self.bytes -= old.nbytes
            self.evictions += 1
            if tracker is not None and tracker.enabled:
                fkeys = _heat.leaf_frag_keys(victim_key)
                if fkeys:
                    # residency-transition history: the fragment left HBM
                    tracker.touch_many(fkeys, evictions=1)

    def patch_entries(self, matcher: Callable[[tuple], bool],
                      patcher: Callable) -> tuple[int, int]:
        """In-place batch write-through (ISSUE 16 ingest): rewrite every
        resident entry whose key `matcher` selects. `patcher(key, arr)`
        runs OUTSIDE the lock (it launches a device kernel) and returns
        (new_key, new_arr) — the patched array under its post-write
        generation key — or None to just drop the stale entry. Either
        way the OLD key is removed: matched entries carry pre-write
        generations, so they can never be hit again. A clear() landing
        mid-patch (index/field deletion) aborts the swap — the epoch
        fence, same as leaf(). Returns (patched, dropped)."""
        with self._lock:
            keys = [k for k in self._lru if matcher(k)]
            epoch = self.epoch
        patched = dropped = 0
        for k in keys:
            with self._lock:
                arr = self._lru.get(k)
            if arr is None:
                continue
            try:
                res = patcher(k, arr)
            except Exception:  # noqa: BLE001 — patching is an optimization
                res = None  # drop: the next read re-uploads correctly
            with self._lock:
                if self.epoch != epoch:
                    break
                old = self._lru.pop(k, None)
                if old is None:
                    continue
                self.bytes -= old.nbytes
                if res is None:
                    dropped += 1
                    continue
                new_key, new_arr = res
                displaced = self._lru.pop(new_key, None)
                if displaced is not None:
                    self.bytes -= displaced.nbytes
                self._lru[new_key] = new_arr
                self.bytes += new_arr.nbytes
                patched += 1
                self._evict_over_budget_locked(new_key)
        return patched, dropped

    def peek(self, key: tuple) -> Optional[jax.Array]:
        """The resident array for `key`, or None — WITHOUT hit/miss
        accounting (a representation probe by the hybrid manager is not
        a leaf read; counting it would distort the hit-rate telemetry
        the churn alerts key on). Touches LRU order: a probe that leads
        to an on-device materialization is about to read the entry."""
        with self._lock:
            arr = self._lru.get(key)
            if arr is not None:
                self._lru.move_to_end(key)
            return arr

    def probe(self, key: tuple) -> Optional[int]:
        """Resident byte size for `key`, or None — no hit/miss accounting
        AND no LRU touch: the EXPLAIN residency probe must observe the
        cache without perturbing eviction order (a query that is only
        being explained never reads the entry)."""
        with self._lock:
            arr = self._lru.get(key)
            return None if arr is None else arr.nbytes

    def probe_where(self, pred: Callable[[tuple], bool]) -> Optional[tuple]:
        """First (key, nbytes) whose key satisfies `pred`, or None — the
        EXPLAIN stale-generation probe (same key prefix, different
        generation tuple). Read-only like probe(): no accounting, no LRU
        reorder. O(entries) under the lock; EXPLAIN is not a hot path."""
        with self._lock:
            for key, arr in self._lru.items():
                try:
                    if pred(key):
                        return key, arr.nbytes
                except Exception:  # noqa: BLE001 — a malformed key must
                    continue  # not break the walk
            return None

    def entries_snapshot(self) -> list[tuple]:
        """[(key, nbytes)] of every resident entry — the GET /debug/hbm
        walk's raw material (aggregation happens outside the lock)."""
        with self._lock:
            return [(key, arr.nbytes) for key, arr in self._lru.items()]

    def clear(self) -> None:
        with self._lock:
            self._lru.clear()
            self.bytes = 0
            self.epoch += 1

    def snapshot(self) -> dict:
        with self._lock:
            # per-kind occupancy (key[0] is the leaf kind: "row", "bsicmp",
            # "bsiplanes", "rows_slab", ...): GroupBy axis slabs are the
            # largest residents, so operators diagnosing eviction churn or
            # cold GroupBy p50s need to see what actually holds the budget
            by_kind: dict = {}
            for key, arr in self._lru.items():
                kind = str(key[0]) if isinstance(key, tuple) and key else "?"
                k = by_kind.setdefault(kind, {"entries": 0, "bytes": 0})
                k["entries"] += 1
                k["bytes"] += arr.nbytes
            return {"entries": len(self._lru), "bytes": self.bytes,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "heatEvictions": self.heat_evictions,
                    "eviction": self.eviction, "by_kind": by_kind}


# ---------------------------------------------------------------------------
# A row's per-shard statistics, per write version of its view
# ---------------------------------------------------------------------------

# entries the memo keeps (a row over one shard set each; about a KiB at 64
# shards). Eviction only costs the next request of that row its six loops.
ROW_STATS_BOUND = 1 << 16


@dataclasses.dataclass(slots=True)
class RowStats:
    """What the read path asks of one row over one shard set, all of it a
    pure function of the stored bits: `gens` the per-shard generations (()
    where the view does not exist, as Executor._leaf_gens has it),
    `max_card` and `total_card` the largest and the summed per-shard
    cardinality, `frag_keys` the (index, field, view, shard) coordinates
    (one list a field, view and shard set, shared: do not mutate), and
    `run_stats` the (most intervals, longest run) of a shard, None until
    RowStatsMemo.run_stats is asked for it. `version` is the view's write
    version the entry was read under (None: no such view)."""

    version: Optional[int]
    gens: tuple
    max_card: int
    total_card: int
    frag_keys: list
    run_stats: Optional[tuple] = None


class RowStatsMemo:
    """Row statistics computed once per write version of the row's view
    (models/view.py View.version), so that a served call reads them with
    one dict probe and one integer comparison where the planner, the plan
    cache's key, the representation choice and the leaf lookup each
    walked every shard's fragment.

    An entry is stamped with the version read BEFORE its fragments. A
    write changes the bits, then its fragment's generation, then the
    view's version, and only then is acknowledged
    (storage/fragment.py _bump_generation): an entry that missed any of
    it carries an older version and is computed anew.

    The hit path takes no lock: each step is one call on an OrderedDict,
    atomic under the interpreter lock, and `hits` / `misses` are plain
    additions that may lose a count between threads."""

    def __init__(self, bound: int = ROW_STATS_BOUND):
        self.bound = max(1, int(bound))
        self._lru: "OrderedDict[tuple, RowStats]" = OrderedDict()
        self._frag_keys: dict[tuple, list] = {}
        # inserts and evictions only
        self._lock = tracing.TimedLock("residency.rowstats",
                                       threading.Lock())
        self.hits = 0
        self.misses = 0

    def frag_keys(self, index_name: str, field_name: str, view_name: str,
                  shards_t: tuple) -> list:
        """The heat tracker's coordinates of one field and view over a
        shard set, built once (shared: do not mutate)."""
        key = (index_name, field_name, view_name, shards_t)
        keys = self._frag_keys.get(key)
        if keys is None:
            if len(self._frag_keys) >= self.bound:
                self._frag_keys.clear()
            keys = self._frag_keys[key] = [
                (index_name, field_name, view_name, s) for s in shards_t]
        return keys

    def get(self, index, field_name: str, view_name: str, shards,
            row_id: int) -> RowStats:
        """The row's statistics over `shards`; a request builds
        tuple(shards) once and passes that down."""
        shards_t = shards if type(shards) is tuple else tuple(shards)
        f = index.field(field_name)
        view = f.view(view_name) if f is not None else None
        if view is None:
            return RowStats(None, (), 0, 0, self.frag_keys(
                index.name, field_name, view_name, shards_t), (0, 0))
        version = view.version  # FIRST: see the class docstring
        key = (index.name, field_name, view_name, row_id, shards_t)
        stats = self._lru.get(key)
        if stats is not None and stats.version == version:
            self.hits += 1
            try:
                self._lru.move_to_end(key)
            except KeyError:  # evicted since the probe: still this version
                pass
            return stats
        self.misses += 1
        gens = []
        max_card = total_card = 0
        for s in shards_t:
            frag = view.fragment(s)
            if frag is None:
                gens.append(0)
                continue
            gens.append(frag.row_generation(row_id))
            c = frag.row_cardinality(row_id)
            total_card += c
            if c > max_card:
                max_card = c
        stats = RowStats(version, tuple(gens), max_card, total_card,
                         self.frag_keys(index.name, field_name, view_name,
                                        shards_t))
        with self._lock:
            self._lru[key] = stats
            self._lru.move_to_end(key)
            while len(self._lru) > self.bound:
                self._lru.popitem(last=False)
        return stats

    def run_stats(self, index, field_name: str, view_name: str, shards,
                  row_id: int) -> tuple:
        """(most intervals, longest run) of a shard of the row, walked on
        the first ask and kept on the row's entry: only a row above the
        sparse threshold needs it. Should a write land between the entry
        and this walk, the entry's version is already an old one and the
        next request reads both anew."""
        stats = self.get(index, field_name, view_name, shards, row_id)
        if stats.run_stats is None:
            f = index.field(field_name)
            view = f.view(view_name) if f is not None else None
            n_iv = max_run = 0
            for s in (shards if view is not None else ()):
                frag = view.fragment(s)
                if frag is not None:
                    n, m = frag.row_run_stats(row_id)
                    n_iv = max(n_iv, n)
                    max_run = max(max_run, m)
            stats.run_stats = (n_iv, max_run)
        return stats.run_stats

    def clear(self) -> None:
        with self._lock:
            self._lru.clear()
            self._frag_keys.clear()

    def snapshot(self) -> dict:
        return {"rowStatsHits": self.hits, "rowStatsMisses": self.misses,
                "rowStatsEntries": len(self._lru)}


# ---------------------------------------------------------------------------
# Hybrid sparse/dense representation management
# ---------------------------------------------------------------------------

# default [query] sparse-threshold: rows at or below this many set bits per
# shard upload as padded sorted-index arrays (ops/bitvector.py sparse
# kernels) instead of dense planes. 4096 is the roaring array->bitmap
# flip (constants.ARRAY_MAX_SIZE) applied at shard granularity: a
# 4096-slot int32 row costs 16 KiB against the 128 KiB plane (8x), and
# smaller rows bucket down in power-of-two slots (a 100-bit row: 512 B,
# 256x). 0 disables — every row uploads dense.
DEFAULT_SPARSE_THRESHOLD = 4096

# default [query] run-threshold: rows ABOVE the sparse cardinality
# threshold upload as sorted [start, last] interval pairs (ops/bitvector.py
# run kernels) when their write-maintained interval count
# (storage/fragment.py row_run_stats) is at or below this. 2048 intervals
# cost 16 KiB against the 128 KiB dense plane (8x) — and the rows run
# containers exist for (existence/time-range rows, arXiv:1603.06549's
# TYPE_RUN regime) sit orders of magnitude below it. 0 disables: rows
# above the sparse threshold always upload dense.
DEFAULT_RUN_THRESHOLD = 2048

# smallest sparse allocation (slots); uploads bucket to powers of two so
# cardinality drift re-keys through a handful of XLA shapes, not one per row
SPARSE_SLOT_MIN = 8

# byte weight order of the three representations — transitions toward
# heavier count as promotions, toward lighter as demotions
_REP_ORDER = {"sparse": 0, "run": 1, "dense": 2}

# representation-memory bound: (index, field, view, row) -> last chosen
# representation, the hysteresis state. Eviction forgets the row's history
# (it re-decides from thresholds alone) — never correctness
REP_MEMORY_BOUND = 1 << 16


def hybrid_env_enabled() -> bool:
    """PILOSA_TPU_HYBRID=0 kills sparse uploads at the choice site (read
    per call: the emergency toggle needs no restart, and the parity fuzz
    flips it at runtime). Existing sparse residents keep serving — they
    are bit-correct — and age out by LRU as re-uploads come back dense."""
    return os.environ.get("PILOSA_TPU_HYBRID", "1") != "0"


class HybridManager:
    """Per-row representation chooser across the full roaring taxonomy
    (arXiv:1402.6407, 1603.06549) applied at shard granularity: sparse
    (padded sorted-index array) below the cardinality threshold, run
    (sorted [start, last] interval pairs) above it while the row's
    write-maintained interval count (storage/fragment.py row_run_stats)
    stays below the run threshold, dense plane otherwise — with
    promote/demote hysteresis so a row flapping around either threshold
    doesn't thrash re-uploads, and heat-informed demotion so a COLD
    dense row re-enters the cheaper representation.

    The decision is advisory and never affects results: all three
    representations evaluate bit-identically (ops/bitvector.eval_hybrid;
    the parity fuzz in tests/test_hybrid_fuzz.py churns rows across both
    thresholds in both directions). State here is only the hysteresis
    memory plus counters for /debug/vars `hybrid` and the
    pilosa_hybrid_total metric families."""

    def __init__(self, threshold: int = DEFAULT_SPARSE_THRESHOLD,
                 hysteresis: float = 0.25, heat=None,
                 run_threshold: int = DEFAULT_RUN_THRESHOLD):
        self.threshold = int(threshold)
        self.run_threshold = int(run_threshold)
        # the demote band: a dense row stays dense until its cardinality
        # (or interval count, for the run band) falls below
        # threshold*(1-hysteresis) OR its fragments go cold
        self.hysteresis = float(hysteresis)
        self.heat = heat  # utils/heat.py HeatTracker or None
        self._lock = threading.Lock()
        self._rep: "OrderedDict[tuple, str]" = OrderedDict()
        self.sparse_uploads = 0
        self.run_uploads = 0
        self.dense_uploads = 0
        self.promoted = 0      # transition to a heavier rep (_REP_ORDER)
        self.demoted = 0       # transition to a lighter rep
        self.run_transitions = 0  # transitions entering or leaving "run"
        self.materialized = 0  # sparse/run leaves expanded to device planes
        # launches that bridged a sparse list to a plane (alone or fused
        # with a bitwise op; ops/bitvector.py _bridge) and the slots they
        # carried
        self.plane_bridges = 0
        self.plane_bridge_slots = 0
        self.sparse_bytes_uploaded = 0
        self.run_bytes_uploaded = 0
        self.dense_bytes_uploaded = 0

    def active(self) -> bool:
        return self.threshold > 0 and hybrid_env_enabled()

    @staticmethod
    def pad_slots(cardinality: int) -> int:
        """Power-of-two padded slot count covering `cardinality` (the
        static XLA shape bucket; shape churn is bounded by log2 buckets)."""
        k = SPARSE_SLOT_MIN
        while k < cardinality:
            k <<= 1
        return k

    def _cold(self, frag_keys) -> bool:
        """True when every covered fragment scores below the heat
        tracker's hot cutoff — the signal that a band-resident dense row
        isn't earning its plane. No tracker (PILOSA_TPU_HEAT=0) means
        never-cold: hysteresis alone decides."""
        tracker = self.heat
        if tracker is None or not getattr(tracker, "enabled", False) \
                or not frag_keys:
            return False
        from pilosa_tpu.utils import heat as _heat
        try:
            scores = tracker.scores_for(list(frag_keys))
        except Exception:  # noqa: BLE001 — advisory signal only
            return False
        return max(scores, default=0.0) < _heat.HOT_SCORE

    def _transition(self, prev, max_card: int, frag_keys,
                    run_stats=None) -> str:
        """The hysteresis rule shared by the read-side choose() and the
        write-side observe(): crossing a threshold upward promotes
        immediately; inside a band a previously-heavier row keeps its rep
        while any covered fragment is hot, demoting only when cold or
        when the signal falls below the band floor. `run_stats` is the
        (interval count, max run length) pair from Fragment.row_run_stats,
        or None when the caller has no run statistics — in which case a
        row already run-resident stays run (the advisory signal is
        missing, not changed) and everything else decides sparse/dense."""
        lo = self.threshold * (1.0 - self.hysteresis)
        if max_card > self.threshold:
            # above the sparse cardinality band entirely: run vs dense,
            # decided by interval count against the run threshold
            n_iv = None if run_stats is None else int(run_stats[0])
            if n_iv is None or self.run_threshold <= 0:
                return "run" if prev == "run" else "dense"
            run_lo = self.run_threshold * (1.0 - self.hysteresis)
            if n_iv > self.run_threshold:
                return "dense"
            if prev == "dense" and n_iv > run_lo:
                return "run" if self._cold(frag_keys) else "dense"
            return "run"
        if prev in ("dense", "run") and max_card > lo:
            return "sparse" if self._cold(frag_keys) else prev
        return "sparse"

    def _remember(self, row_key: tuple, prev, rep: str) -> None:
        with self._lock:
            if prev is not None and prev != rep:
                if _REP_ORDER[rep] > _REP_ORDER.get(prev, 0):
                    self.promoted += 1
                else:
                    self.demoted += 1
                if prev == "run" or rep == "run":
                    self.run_transitions += 1
            self._rep[row_key] = rep
            self._rep.move_to_end(row_key)
            while len(self._rep) > REP_MEMORY_BOUND:
                self._rep.popitem(last=False)

    def choose(self, row_key: tuple, max_card: int,
               frag_keys=None, run_stats=None,
               peek: bool = False) -> tuple[str, int]:
        """(representation, padded slots) for one row leaf whose largest
        per-shard cardinality is `max_card` (hysteresis: _transition).
        Slots are interval-pair slots for "run" (padded from the interval
        count), index slots for "sparse", 0 for "dense". `peek=True`
        skips the hysteresis-memory update: EXPLAIN must report the exact
        choice the executor will make next WITHOUT advancing the state
        that choice depends on (the transition rule is a pure function of
        (prev, stats), so peek-then-choose returns the same rep)."""
        if not self.active():
            return "dense", 0
        with self._lock:
            prev = self._rep.get(row_key)
        rep = self._transition(prev, max_card, frag_keys, run_stats)
        if not peek:
            self._remember(row_key, prev, rep)
        if rep == "run":
            n_iv = 1 if run_stats is None else int(run_stats[0])
            return rep, self.pad_slots(max(n_iv, 1))
        return rep, self.pad_slots(max(int(max_card), 1))

    def observe(self, row_key: tuple, max_card: int,
                frag_keys=None, run_stats=None) -> None:
        """Write-side hysteresis tick (ISSUE 16 satellite): the batched
        ingest path calls this ONCE per touched row per applied batch —
        instead of re-evaluating threshold crossings mutation by mutation
        — so under sustained churn the representation memory advances at
        batch granularity with the exact same transition rule the read
        path applies. Rows with no history are left alone: the next
        read's choose() decides fresh, as it always did."""
        if not self.active():
            return
        with self._lock:
            prev = self._rep.get(row_key)
        if prev is None:
            return
        rep = self._transition(prev, max_card, frag_keys, run_stats)
        self._remember(row_key, prev, rep)

    def record_upload(self, rep: str, nbytes: int) -> None:
        with self._lock:
            if rep == "sparse":
                self.sparse_uploads += 1
                self.sparse_bytes_uploaded += int(nbytes)
            elif rep == "run":
                self.run_uploads += 1
                self.run_bytes_uploaded += int(nbytes)
            else:
                self.dense_uploads += 1
                self.dense_bytes_uploaded += int(nbytes)
        # h2d byte attribution per kernel family (utils/telemetry.py
        # KernelStats): leaf uploads are the dominant host->device
        # traffic, charged to the family that consumes the representation
        from pilosa_tpu.utils import telemetry as _telemetry
        if _telemetry.kernel_stats_enabled():
            fam = {"sparse": "sparse", "run": "run"}.get(rep, "bitwise")
            _telemetry.kernels.record_bytes(fam, h2d=int(nbytes))

    def record_materialize(self) -> None:
        with self._lock:
            self.materialized += 1

    def record_bridge(self, slots: int) -> None:
        with self._lock:
            self.plane_bridges += 1
            self.plane_bridge_slots += int(slots)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "enabled": self.active(),
                "threshold": self.threshold,
                "runThreshold": self.run_threshold,
                "hysteresis": self.hysteresis,
                "sparseUploads": self.sparse_uploads,
                "runUploads": self.run_uploads,
                "denseUploads": self.dense_uploads,
                "promoted": self.promoted,
                "demoted": self.demoted,
                "runTransitions": self.run_transitions,
                "materialized": self.materialized,
                "planeBridges": self.plane_bridges,
                "planeBridgeSlots": self.plane_bridge_slots,
                "sparseBytesUploaded": self.sparse_bytes_uploaded,
                "runBytesUploaded": self.run_bytes_uploaded,
                "denseBytesUploaded": self.dense_bytes_uploaded,
                "trackedRows": len(self._rep),
            }


class PlanCache:
    """Generation-keyed cross-query subexpression result cache.

    Where DeviceResidency caches query *leaves* (one row / mask per entry),
    this caches *evaluated subexpressions*: the dense device result of a
    whole bitmap call tree, or the scalar of a Count over one. Keys come
    from the planner (pilosa_tpu/planner.py): (index, canonical PQL of the
    planned subtree, shard set, per-leaf fragment row generations) — the
    same keying discipline as the residency leaves, so invalidation is
    free: any write bumps a generation, changes the key, and the stale
    entry ages out by LRU. Overlapping dashboard queries from many users
    therefore hit device-resident results instead of recomputing the
    shared subtree per query.

    Values are either jax.Arrays (dense [S', W] row results, charged at
    their real HBM bytes) or plain ints (Count results, charged at a
    nominal SCALAR_COST so a flood of distinct Counts still evicts).
    `enabled` flips at runtime (bench A/B, [query] plan knob) without
    tearing down the executor."""

    SCALAR_COST = 256  # nominal bytes per cached scalar entry

    DEFAULT_BUDGET_BYTES = 256 << 20

    def __init__(self, budget_bytes: int = DEFAULT_BUDGET_BYTES):
        self.budget = budget_bytes
        self.enabled = True
        self._lru: OrderedDict = OrderedDict()  # key -> (value, nbytes)
        self._lock = tracing.TimedLock("plancache", threading.Lock())
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.epoch = 0  # bumped by clear(); fences in-flight computes

    def get(self, key: tuple):
        """Cached value for `key`, or None (a miss; None is never a
        cached value — scalar zero counts are cached as int 0)."""
        if not self.enabled:
            return None
        with self._lock:
            entry = self._lru.get(key)
            if entry is None:
                self.misses += 1
                return None
            self._lru.move_to_end(key)
            self.hits += 1
            value = entry[0]
        # per-principal hit accounting OUTSIDE the LRU lock (the hit path
        # is hot and the ledger has its own lock): a hit is work the
        # caller reused instead of spending — the signal quota pricing
        # needs to avoid charging a dashboard for its neighbors' warmup
        acct = accounting.current_account.get()
        if acct is not None:
            acct.charge(plan_cache_hits=1)
        return value

    def put(self, key: tuple, value, nbytes: int, epoch: int = None) -> None:
        """Insert `value` (device array or int). `epoch`, when given, is
        the epoch the caller read before computing: a clear() that landed
        mid-compute (index/field deletion) means the value may describe
        deleted schema whose recreation could reach identical generation
        tuples — serve-don't-cache, the DeviceResidency fence."""
        if not self.enabled:
            return
        with self._lock:
            if epoch is not None and epoch != self.epoch:
                return
            displaced = self._lru.pop(key, None)
            if displaced is not None:
                self.bytes -= displaced[1]
            self._lru[key] = (value, nbytes)
            self.bytes += nbytes
            while self.bytes > self.budget and len(self._lru) > 1:
                _, (_, old_bytes) = self._lru.popitem(last=False)
                self.bytes -= old_bytes
                self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._lru.clear()
            self.bytes = 0
            self.epoch += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"entries": len(self._lru), "bytes": self.bytes,
                    "budget": self.budget, "enabled": self.enabled,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions}
