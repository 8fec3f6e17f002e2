"""Fragment: the (index, field, view, shard) storage unit.

Mirrors the reference fragment's responsibilities (fragment.go:87-134):
one roaring file + op-log WAL + snapshot compaction + row materialization +
anti-entropy block checksums — but split cleanly into a *host-side
authoritative store* (this module) and a *device query cache* (the executor's
HBM residency layer). Mutation never touches the device: random single-bit
writes are the wrong shape for XLA, so writes go to the host bitmap + WAL
(reference: fragment.go:382-497 setBit path) and invalidate row generations;
the executor re-materializes dirty rows on demand, exactly as the reference's
rowCache is invalidated on writes (fragment.go:435-440).

Storage lifecycle (reference: fragment.go:190-247 openStorage):
  open -> parse snapshot+op-log file -> attach op-log appender ->
  after MAX_OP_N ops, snapshot() rewrites the file atomically
  (fragment.go:1707-1781 via a .snapshotting temp file).

Row r of the shard occupies absolute bit positions [r*2^20, (r+1)*2^20)
(pos(), fragment.go:2420-2424).
"""

from __future__ import annotations

import fcntl
import functools
import hashlib
import io
import mmap
import os
import struct
import tarfile
import threading
from typing import Iterable, Optional

import numpy as np

from pilosa_tpu.constants import (
    CONTAINERS_PER_SHARD,
    HASH_BLOCK_SIZE,
    MAX_OP_N,
    SHARD_WIDTH,
)
from pilosa_tpu.storage.roaring import Bitmap

SNAPSHOT_EXT = ".snapshotting"
CACHE_EXT = ".cache"
LOCK_EXT = ".lock"

# (lock_file, mmap) pairs deliberately held past close() because zero-copy
# numpy views over the mapping are still exported (see Fragment.close):
# pinned here so refcounting can't close the fd behind our back. Each
# open() reaps entries whose views have since died (mmap closes cleanly),
# releasing their flocks; anything still referenced stays locked — at
# worst for the rest of the process, the views' maximum lifetime.
# _HELD_LOCKS_MU guards the list: a reap racing a close() must not drop
# a freshly appended entry (that would release a flock under live views).
_HELD_LOCKS: list = []
_HELD_LOCKS_MU = threading.Lock()


def _reap_held_locks() -> None:
    with _HELD_LOCKS_MU:
        alive = []
        for lock_file, mm in _HELD_LOCKS:
            try:
                mm.close()
            except BufferError:
                alive.append((lock_file, mm))
                continue
            lock_file.close()  # releases the flock
        _HELD_LOCKS[:] = alive


def _locked(method):
    """Serialize a mutating Fragment method under the per-fragment write
    lock (the reference's fragment.mu, fragment.go:76): the HTTP server is
    threaded, and an unsynchronized container read-modify-write loses
    concurrent single-bit updates. Readers stay lock-free — container
    swaps are atomic object-reference stores under the GIL, so a racing
    read sees the old or new container, never a torn one."""
    @functools.wraps(method)
    def wrapper(self, *args, **kwargs):
        with self.mu:
            return method(self, *args, **kwargs)
    return wrapper


def as_array(x, dtype) -> np.ndarray:
    """Coerce an iterable (or pass through an ndarray) to dtype — the
    shared input normalization for the bulk import paths."""
    return np.asarray(x if isinstance(x, np.ndarray) else list(x),
                      dtype=dtype)


def _aggregate_row_counts(rids: np.ndarray,
                          ns: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(unique row ids asc, summed counts) from per-container (row id,
    cardinality) pairs — one reduceat pass when already sorted (frozen
    stores), argsort first otherwise (dict iteration order)."""
    if rids.size == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    if rids.size > 1 and not np.all(rids[1:] >= rids[:-1]):
        order = np.argsort(rids, kind="stable")
        rids, ns = rids[order], ns[order]
    starts = np.flatnonzero(
        np.concatenate([[True], rids[1:] != rids[:-1]]))
    return (rids[starts].astype(np.int64),
            np.add.reduceat(ns.astype(np.int64), starts))


def pos(row_id: int, column: int) -> int:
    """Absolute bit position of (row, column-within-shard)."""
    return row_id * SHARD_WIDTH + (column % SHARD_WIDTH)


class Fragment:
    """Host-authoritative storage for one shard of one view of one field."""

    def __init__(self, path: str, index: str, field: str, view: str, shard: int,
                 wal_fsync: Optional[bool] = None):
        self.path = path
        self.index = index
        self.field = field
        self.view = view
        self.shard = shard
        # fsync per acked op. Default (off) matches the reference, which
        # writes through an unbuffered os.File but does not fsync
        # (roaring.go:977); "always" survives power loss, not just process
        # death, at ~100x write cost. Precedence (docs/operations.md):
        # PILOSA_TPU_WAL_FSYNC env (any non-empty value; "always" enables)
        # overrides the [storage] wal-fsync config plumbed down as the
        # `wal_fsync` parameter; unset both = off.
        env = os.environ.get("PILOSA_TPU_WAL_FSYNC", "")
        if env:
            wal_fsync = env == "always"
        elif wal_fsync is None:
            wal_fsync = False
        self.wal_fsync = wal_fsync
        # per-fragment write lock (fragment.mu, fragment.go:76); RLock:
        # bulk paths snapshot() while holding it
        self.mu = threading.RLock()
        self.storage = Bitmap()
        self.op_n = 0
        self._op_file = None
        self._lock_file = None
        self._mmap = None
        self.closed = True
        # Row generations: bumped on any mutation touching the row; the
        # device cache keys on (fragment key, row, generation) — the analog
        # of the reference's rowCache invalidation (fragment.go:435).
        self.generation = 0
        self._row_gen: dict[int, int] = {}
        # called after every generation bump: the owning View's write
        # version (models/view.py bump_version); None for a fragment
        # opened on its own
        self.on_generation = None
        # Floor for per-row generations: bulk mutations (roaring import,
        # resize tar restore) dirty every row at once; resetting per-row
        # generations to 0 would collide with the untouched-row key and
        # serve stale device-cache leaves, so they raise this floor instead.
        self._bulk_gen = 0
        # volatile: storage came from import_frozen and has not been
        # snapshotted — the WAL is detached and AUTO-snapshots are skipped
        # (a billion-row frozen corpus must not be rewritten as a side
        # effect of a small follow-up import); snapshot() clears it
        self._volatile = False
        # mutation events taken while volatile (acknowledged writes that
        # would be lost on restart until an explicit snapshot) — surfaced
        # in /debug/vars volatileFragments so the volatility is visible
        # to operators, not just a code comment
        self.volatile_mutations = 0
        # corruption recovery state: when open() finds a damaged snapshot
        # section it moves the file to <path>.corrupt-<ts> and reopens
        # empty; the scrubber rebuilds from a live replica and stamps
        # rebuilt_from. A torn WAL tail is milder: recovery truncates it
        # in place and records how much was dropped.
        self.quarantine_path: Optional[str] = None
        self.corruption_error: Optional[str] = None
        self.rebuilt_from: Optional[str] = None
        self.wal_truncated_bytes = 0
        self.wal_truncate_error: Optional[str] = None
        # Cached block checksums, invalidated per-block on writes
        # (fragment.go:1226-1305).
        self._block_checksums: dict[int, bytes] = {}
        # (generation, {row_id: count}) — see row_counts()
        self._row_counts_cache = None
        # (generation, ascending distinct row ids) — see row_ids()
        self._row_ids_cache = None
        # {row_id: (gen, n_intervals, max_run)} — see row_run_stats().
        # max_run < 0 marks "recompute on next read": a merge-add grew a
        # run by an amount a neighbor probe cannot see.
        self._row_run_stats: dict[int, tuple[int, int, int]] = {}

    # -- lifecycle ----------------------------------------------------------

    def open(self) -> "Fragment":
        """Open: flock + mmap + lazy parse (openStorage, fragment.go:190-247:
        mmap(PROT_READ) + flock + MADV_RANDOM + zero-copy unmarshal).

        The exclusive lock lives on a sidecar `<path>.lock` file that is
        never replaced — snapshot() os.replace()s the data file's inode, and
        locking the data file itself would open a window where two processes
        hold "the" lock on different inodes. A second opener fails fast
        instead of silently corrupting the data-dir. Container payloads stay
        in the mmap until first access (LazyContainer), so the *parse* cost
        at open is proportional to container metadata, not data bytes —
        though verifying the integrity trailer (below) is one sequential
        blake2b pass over the snapshot section, the price of catching
        bit-rot before serving from it.

        Crash/corruption recovery: a torn or corrupt WAL TAIL is truncated
        at the last valid record (un-acked damage must not be fatal —
        fragment.go reopens after crashes the same way); a damaged SNAPSHOT
        section (failed blake2b trailer, truncated containers) quarantines
        the file to `<path>.corrupt-<ts>` and reopens empty, leaving the
        anti-entropy scrubber to rebuild from a live replica. Either way the
        node comes up; only a second consecutive failure (disk errors on
        the fresh file) releases the lock and raises.
        """
        from pilosa_tpu.utils import failpoints

        _reap_held_locks()  # release flocks whose mmap views have died
        # fresh recovery report per open: this open's findings, not a
        # previous incarnation's (a rebuilt-then-reopened fragment is clean)
        self.quarantine_path = None
        self.corruption_error = None
        self.rebuilt_from = None
        self.wal_truncated_bytes = 0
        self.wal_truncate_error = None
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        self._lock_file = open(self.path + LOCK_EXT, "ab")
        try:
            fcntl.flock(self._lock_file.fileno(),
                        fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            self._lock_file.close()
            self._lock_file = None
            raise RuntimeError(
                f"fragment file locked by another process: {self.path}")
        for attempt in (0, 1):
            try:
                # Unbuffered: every acked op must reach the kernel before the
                # write returns (the reference appends through an os.File
                # syscall, roaring.go:977 writeOp — a userspace-buffered WAL
                # loses acked writes on crash, defeating its purpose).
                self._op_file = open(self.path, "ab", buffering=0)
                if os.path.getsize(self.path) == 0:
                    # Seed an empty snapshot (with integrity trailer) so the
                    # WAL has something to append to (openStorage marshals
                    # the empty bitmap into a fresh file, fragment.go:190).
                    self.storage.write_snapshot(self._op_file)
                    self._op_file.flush()
                failpoints.hit("storage.fragment.open")
                self._map()
                break
            except ValueError as e:
                # snapshot-section damage (CorruptionError trailer mismatch,
                # truncated container payloads, bad header): quarantine the
                # file and retry ONCE with a fresh empty one — the node must
                # come up, and the scrubber heals from replicas. Handles are
                # closed either way so a retry can't trip its own flock or
                # mask the parse error with a bogus "locked".
                if self._op_file is not None:
                    self._op_file.close()
                    self._op_file = None
                if attempt == 0:
                    self.corruption_error = str(e)
                    self.quarantine_path = self._quarantine()
                    self.storage = Bitmap()
                    continue
                self._lock_file.close()
                self._lock_file = None
                raise
            except Exception:
                # non-corruption failure (disk error, injected fault):
                # don't leak the lock/handles
                if self._op_file is not None:
                    self._op_file.close()
                    self._op_file = None
                self._lock_file.close()
                self._lock_file = None
                raise
        if self.storage.wal_error is not None:
            # torn WAL tail: every record before the tear replayed; drop
            # the damage so the next open is clean and appends are sane.
            # (The mmap spans the old length, but nothing reads past the
            # snapshot section, which always precedes the ops.)
            valid_end = self.storage.wal_valid_end
            self.wal_truncated_bytes = os.path.getsize(self.path) - valid_end
            self.wal_truncate_error = self.storage.wal_error
            os.truncate(self.path, valid_end)
        self.op_n = self.storage.op_n
        if self.op_n:
            # op-log replay can leave stale encodings (array grown past
            # ARRAY_MAX_SIZE etc.) — normalize like Containers.Repair
            # (roaring/roaring.go:106, 2093-2113); replay only touches the
            # mutated containers, so laziness survives
            self.storage.repair()
        self.storage.op_writer = self._op_file
        self.storage.op_sync = self.wal_fsync
        self.closed = False
        return self

    def _map(self, verify: bool = True) -> None:
        """(Re)map the file and lazy-parse it into self.storage.
        verify=False skips the trailer digest (the remap right after a
        snapshot wrote it — re-hashing the whole section there would
        double compaction I/O for nothing)."""
        with open(self.path, "rb") as f:
            mm = mmap.mmap(f.fileno(), 0, prot=mmap.PROT_READ)
        try:
            if hasattr(mm, "madvise"):
                mm.madvise(mmap.MADV_RANDOM)  # fragment.go:2391 madvise
            storage = Bitmap.from_bytes(mm, lazy=True, recover_wal=True,
                                        verify=verify)
        except Exception:
            try:
                mm.close()  # parse failed: drop the mapping
            except BufferError:
                # a memoryview in the propagating exception's traceback
                # still pins the mapping; refcounting reclaims it as soon
                # as the handler in open() consumes the exception
                pass
            raise
        self.storage = storage
        self._mmap = mm

    def _quarantine(self) -> str:
        """Move the corrupt data file aside to `<path>.corrupt-<ts>` —
        preserved for operator forensics (docs/operations.md runbook),
        out of the way of the fresh file the retry creates."""
        import time as _time
        ts = _time.strftime("%Y%m%d-%H%M%S")
        dest = f"{self.path}.corrupt-{ts}"
        i = 1
        while os.path.exists(dest):
            dest = f"{self.path}.corrupt-{ts}-{i}"
            i += 1
        os.replace(self.path, dest)
        return dest

    @property
    def needs_rebuild(self) -> bool:
        """True while this fragment was quarantined-and-emptied and no
        replica rebuild has completed yet (the scrubber's work list)."""
        return self.quarantine_path is not None and self.rebuilt_from is None

    def close(self) -> None:
        if self._op_file is not None:
            self._op_file.flush()
            self._op_file.close()  # releases the flock
            self._op_file = None
        self.storage.op_writer = None
        # close the mapping WITHOUT materializing: shutdown must not read
        # the whole file; later access to a still-lazy container of a
        # closed fragment raises loudly ("mmap closed"), never corrupts.
        # A frozen-parsed store holds numpy views over the mapping
        # (exported buffers): those make close() impossible — drop our
        # reference instead and let refcounting reclaim the mapping when
        # the last view dies (reads through live views stay valid).
        live_mm = None
        if self._mmap is not None:
            try:
                self._mmap.close()
            except BufferError:
                # frozen-parsed stores hold zero-copy numpy views over the
                # mapping themselves: drop OUR storage reference and retry
                # — then only views handed out to EXTERNAL consumers
                # (query results still referencing the flat arrays) keep
                # the mapping alive
                self.storage = Bitmap()
                try:
                    self._mmap.close()
                except BufferError:
                    live_mm = self._mmap
            self._mmap = None
        if self._lock_file is not None:
            if live_mm is not None:
                # HOLD the flock while views are live: releasing it would
                # let another process rewrite/truncate the snapshot under
                # still-referenced views (stale reads, or SIGBUS on
                # truncate). Reaped by a later open() once the last view
                # dies; held to process exit otherwise.
                with _HELD_LOCKS_MU:
                    _HELD_LOCKS.append((self._lock_file, live_mm))
                self._lock_file = None
            else:
                self._lock_file.close()  # releases the flock
                self._lock_file = None
        self.closed = True

    # -- mutation -----------------------------------------------------------

    def _bump_generation(self, rows=None) -> int:
        """The one place a generation grows: `rows` get the new
        generation, None dirties every row (the bulk floor). ORDER IS THE
        GUARANTEE: the stored bits changed before this call, the
        fragment's own generation grows here, the view's write version
        after it, and all three before the write is acknowledged. A
        reader (parallel/residency.py RowStatsMemo) takes the view's
        version FIRST and reads the fragments after, so what it stores
        under an older version is recomputed, never served. Bumping the
        view before the fragment would let a reader stamp the old
        generations with the new version and serve a stale count after
        an acknowledged write."""
        self.generation += 1
        gen = self.generation
        if rows is None:
            self._row_gen.clear()
            self._bulk_gen = gen
        else:
            for row_id in rows:
                self._row_gen[row_id] = gen
        notify = self.on_generation
        if notify is not None:
            notify()
        return gen

    def _touch(self, row_id: int) -> None:
        self._bump_generation((row_id,))
        self._block_checksums.pop(row_id // HASH_BLOCK_SIZE, None)
        if self._volatile:
            self.volatile_mutations += 1

    def row_generation(self, row_id: int) -> int:
        return max(self._row_gen.get(row_id, 0), self._bulk_gen)

    @_locked
    def set_bit(self, row_id: int, column: int) -> bool:
        """Set one bit; appends to the WAL and snapshots at MAX_OP_N
        (fragment.go:382-433 setBit + incrementOpN)."""
        prev_gen = self.row_generation(row_id)
        changed = self.storage.add(pos(row_id, column))
        if changed:
            self._touch(row_id)
            self._run_stats_update(row_id, column, prev_gen, added=True)
        self._increment_op_n()
        return changed

    @_locked
    def clear_bit(self, row_id: int, column: int) -> bool:
        prev_gen = self.row_generation(row_id)
        changed = self.storage.remove(pos(row_id, column))
        if changed:
            self._touch(row_id)
            self._run_stats_update(row_id, column, prev_gen, added=False)
        self._increment_op_n()
        return changed

    def contains(self, row_id: int, column: int) -> bool:
        return self.storage.contains(pos(row_id, column))

    def _increment_op_n(self) -> None:
        self.op_n += 1
        if self.op_n > MAX_OP_N:
            self._maybe_snapshot()

    @_locked
    def apply_batch(self, muts) -> tuple[list, int, int]:
        """Coalesced ingest apply (ISSUE 16): one batch of ordered
        (is_set, row_id, column) mutations becomes ONE sorted-dedup
        container merge per touched container, ONE generation bump, and
        ONE WAL group-commit (single framed write + single fsync via
        append_ops) instead of a write+fsync per bit.

        Per-mutation `changed` flags match what the sequential per-bit
        path would have returned: membership is probed once up front
        (contains_many) and then tracked through the batch in order.
        The WAL records only the NET effect per position — each position
        appears at most once, so replay is order-independent yet lands
        on the same final state; a set-then-clear of an absent bit logs
        nothing while both mutations still report changed=True, exactly
        as the per-bit path would. Returns (changed_flags, n_wal_ops,
        n_wal_appends)."""
        if not muts:
            return [], 0, 0
        positions = [pos(r, c) for _, r, c in muts]
        uniq = np.unique(np.asarray(positions, dtype=np.uint64))
        initial_mask = self.storage.contains_many(uniq)
        state = {int(p): bool(b)
                 for p, b in zip(uniq.tolist(), initial_mask.tolist())}
        initial = dict(state)
        changed = []
        changed_rows = set()
        n_changed = 0
        for (is_set, row_id, _col), p in zip(muts, positions):
            cur = state[p]
            ch = (not cur) if is_set else cur
            state[p] = bool(is_set)
            changed.append(ch)
            if ch:
                changed_rows.add(row_id)
                n_changed += 1
        net_adds = np.array(
            [p for p, s in state.items() if s and not initial[p]],
            dtype=np.uint64)
        net_removes = np.array(
            [p for p, s in state.items() if not s and initial[p]],
            dtype=np.uint64)
        if net_adds.size:
            self.storage.add_many(net_adds)
        if net_removes.size:
            self.storage.remove_many(net_removes)
        n_net = int(net_adds.size) + int(net_removes.size)
        wal_appends = 0
        if changed_rows:
            # one generation bump for the whole batch; every row that saw
            # a changed mutation gets the new generation (residency and
            # plan-cache keys invalidate exactly once per batch)
            self._bump_generation(changed_rows)
            for rid in changed_rows:
                self._block_checksums.pop(rid // HASH_BLOCK_SIZE, None)
                # run stats recompute lazily on the next planner read —
                # a batch's net effect can split/merge arbitrarily many runs
                self._row_run_stats.pop(rid, None)
            if self._volatile:
                self.volatile_mutations += n_changed
        if n_net and not self._volatile:
            if self.storage.op_writer is not None:
                # group commit: one framed multi-record write, one fsync
                self.storage.append_ops(net_adds, net_removes)
                wal_appends = 1
            self.op_n += n_net
            if self.op_n > MAX_OP_N:
                self._maybe_snapshot()
        return changed, n_net, wal_appends

    @_locked
    def set_row(self, row_id: int, columns: np.ndarray) -> None:
        """Whole-row replace (setRow, fragment.go:501-586). Bulk path: no WAL,
        snapshot responsibility is the caller's (bulk import batches rows)."""
        base = row_id * SHARD_WIDTH
        self.storage.remove_many(self.storage.slice(base, base + SHARD_WIDTH))
        cols = np.asarray(columns, dtype=np.uint64) % SHARD_WIDTH + np.uint64(base)
        self.storage.add_many(cols)
        self._touch(row_id)

    @_locked
    def clear_row(self, row_id: int) -> int:
        base = row_id * SHARD_WIDTH
        vals = self.storage.slice(base, base + SHARD_WIDTH)
        self.storage.remove_many(vals)
        if vals.size:
            self._touch(row_id)
        return int(vals.size)

    # -- BSI value mutation (fragment.go:597-660) ---------------------------

    @_locked
    def set_value(self, column: int, bit_depth: int, value: int) -> bool:
        """Write a BSI value: rows 0..bit_depth-1 are place values, row
        bit_depth is the not-null row (fragment.go:597-618,630)."""
        changed = False
        for i in range(bit_depth):
            if (value >> i) & 1:
                changed |= self.set_bit(i, column)
            else:
                changed |= self.clear_bit(i, column)
        changed |= self.set_bit(bit_depth, column)
        return changed

    @_locked
    def clear_value(self, column: int, bit_depth: int) -> bool:
        changed = False
        for i in range(bit_depth + 1):
            changed |= self.clear_bit(i, column)
        return changed

    def value(self, column: int, bit_depth: int) -> tuple[int, bool]:
        if not self.contains(bit_depth, column):
            return 0, False
        v = 0
        for i in range(bit_depth):
            if self.contains(i, column):
                v |= 1 << i
        return v, True

    # -- reads --------------------------------------------------------------

    def row_dense(self, row_id: int) -> np.ndarray:
        """Materialize a row as a dense uint32 bitvector (the OffsetRange
        slice, fragment.go:347-378 row())."""
        base = row_id * SHARD_WIDTH
        return self.storage.to_dense_words(base, base + SHARD_WIDTH)

    def row_columns(self, row_id: int) -> np.ndarray:
        """Set columns of a row as shard-local offsets."""
        base = row_id * SHARD_WIDTH
        return (self.storage.slice(base, base + SHARD_WIDTH) - np.uint64(base)).astype(np.int64)

    def row_count(self, row_id: int) -> int:
        base = row_id * SHARD_WIDTH
        return self.storage.count_range(base, base + SHARD_WIDTH)

    def _row_count_direct(self, row_id: int) -> int:
        """O(keys-per-row) count by probing the row's (container-aligned)
        key slots directly — no key-space scan."""
        kpr = CONTAINERS_PER_SHARD
        base = row_id * kpr
        get = self.storage.containers.get
        total = 0
        for j in range(kpr):
            c = get(base + j)
            if c is not None:
                total += c.n
        return total

    def rows_columns(self) -> tuple:
        """(rows, cols) int32 arrays of every set bit, in (row, column)
        order, cols shard-local. One pass over the store's sorted
        positions — flat arrays on a frozen store, a concatenation of
        containers otherwise — not one walk a row: what a field's pairs
        entry (executor._pairs_entry) is built from."""
        pos = self.storage.positions()
        shift = np.uint64(SHARD_WIDTH.bit_length() - 1)
        return ((pos >> shift).astype(np.int32),
                (pos & np.uint64(SHARD_WIDTH - 1)).astype(np.int32))

    @staticmethod
    def _frozen_row_arrays(store, kpr: int):
        """(row_ids, counts) sorted arrays from a frozen store's flat key
        layout — the shared vectorized base for row_counts / row_ids /
        rank-cache building at bulk-load scale."""
        keys, ns = store.key_and_count_arrays()
        return _aggregate_row_counts(keys // kpr, ns)

    def row_counts(self, row_ids) -> np.ndarray:
        """Vectorized exact counts for many rows (the TopN recount asks for
        ~n=1000 winners per query; per-row count_range walks the whole key
        space per call).

        One container-key pass builds a row->count map (rows are
        container-aligned, so a row's count is a plain sum of its
        containers' cardinalities; lazy containers never parse). The map
        is rebuilt only when a BULK mutation dirties every row; single-bit
        writes are absorbed by an overlay that re-probes just the mutated
        rows (per-row generations), so write-heavy workloads never pay a
        full O(containers) rebuild per query."""
        cached = self._row_counts_cache
        if cached is None or cached[0] != self._bulk_gen:
            kpr = CONTAINERS_PER_SHARD  # container keys per row
            store = self.storage.containers
            if getattr(store, "VECTORIZED_STORE", False):
                # frozen store: whole-corpus (row -> count) as two sorted
                # arrays, no Container materialization, no 1-entry-per-row
                # Python dict (at 1B rows a dict is >100 GB of objects)
                uids, sums = self._frozen_row_arrays(store, kpr)
                m = ("np", uids, sums)
            elif len(store):
                items = list(store.items())
                keys = np.fromiter((k for k, _ in items), np.int64,
                                   len(items))
                ns = np.fromiter((c.n for _, c in items), np.int64,
                                 len(items))
                uids, sums = _aggregate_row_counts(keys // kpr, ns)
                m = dict(zip(uids.tolist(), sums.tolist()))
            else:
                m = {}
            # (bulk gen, generation at build, base map, stale-row overlay)
            cached = (self._bulk_gen, self.generation, m, {})
            self._row_counts_cache = cached
        _, base_gen, m, overlay = cached
        rows_arr = np.asarray(row_ids, dtype=np.int64)
        out = np.zeros(rows_arr.size, dtype=np.int64)
        if isinstance(m, tuple):  # frozen: ONE vectorized lookup for all
            # rows (TopN recounts n=1000 winners per shard per query; a
            # per-row searchsorted loop dominated the 1B-row TopN p50)
            _, uids, sums = m
            if uids.size:
                idx = np.searchsorted(uids, rows_arr)
                idx_c = np.minimum(idx, uids.size - 1)
                hit = uids[idx_c] == rows_arr
                out[hit] = sums[idx_c[hit]]
        else:
            for x, r in enumerate(rows_arr.tolist()):
                out[x] = m.get(r, 0)
        # correct the (rare) rows mutated since the base map was built
        if self._row_gen:
            row_gen = self._row_gen.get
            for x, r in enumerate(rows_arr.tolist()):
                rg = row_gen(r, 0)
                if rg > base_gen:
                    og = overlay.get(r)
                    if og is not None and og[0] == rg:
                        out[x] = og[1]
                    else:
                        c = self._row_count_direct(r)
                        overlay[r] = (rg, c)
                        out[x] = c
        return out

    def row_cardinality(self, row_id: int) -> int:
        """Exact set-bit count of one row — the planner's per-operand
        statistic (pilosa_tpu/planner.py). Rides the row_counts cache
        (container-cardinality sums + per-row mutation overlay), so a
        planning pass over a many-operand query costs dict probes, not
        container walks; exactness per the current generation is what
        makes zero-cardinality short-circuits sound rather than
        heuristic."""
        return int(self.row_counts([row_id])[0])

    def row_runs(self, row_id: int) -> np.ndarray:
        """int64[n, 2] inclusive shard-local [start, last] intervals of a
        row, built DIRECTLY from its containers: run containers contribute
        their interval arrays verbatim (offset by container position),
        array/bitmap containers via the consecutive-diff break scan, and
        intervals adjacent across a container boundary merge. No dense
        plane is ever materialized — this is the storage->device upload
        path for run leaves (the device analog of the reference's
        runnable containers, roaring/roaring.go:56-62)."""
        kpr = CONTAINERS_PER_SHARD
        base = row_id * kpr
        get = self.storage.containers.get
        parts = []
        for j in range(kpr):
            c = get(base + j)
            if c is None or not c.n:
                continue
            iv = c._runs().astype(np.int64)
            if iv.shape[0]:
                parts.append(iv + (j << 16))
        if not parts:
            return np.empty((0, 2), dtype=np.int64)
        iv = np.concatenate(parts)
        if iv.shape[0] > 1:
            gap = iv[1:, 0] > iv[:-1, 1] + 1
            starts = iv[np.concatenate(([True], gap)), 0]
            lasts = iv[np.concatenate((gap, [True])), 1]
            iv = np.stack([starts, lasts], axis=1)
        return iv

    def row_run_stats(self, row_id: int) -> tuple[int, int]:
        """(interval count, max run length) of one row — the planner's run
        statistic (pilosa_tpu/planner.py choose_representation), cached
        per row generation like row_counts. Per-bit writes maintain the
        interval count incrementally with two neighbor probes (see
        _run_stats_update); a merge-add marks max_run for recompute, and
        bulk/batch writes drop the entry so this read rebuilds from the
        containers. max_run can transiently be an UPPER bound after
        clears (a split run keeps the old maximum until the next full
        rebuild) — the chooser only uses it as a coarse runniness signal,
        so overstating it briefly never affects correctness, only which
        faithful representation is picked."""
        gen = self.row_generation(row_id)
        entry = self._row_run_stats.get(row_id)
        if entry is not None and entry[0] == gen and entry[2] >= 0:
            return entry[1], entry[2]
        iv = self.row_runs(row_id)
        n = int(iv.shape[0])
        maxr = int((iv[:, 1] - iv[:, 0] + 1).max()) if n else 0
        self._row_run_stats[row_id] = (gen, n, maxr)
        return n, maxr

    def _run_stats_update(self, row_id: int, column: int, prev_gen: int,
                          added: bool) -> None:
        """Incremental run-stat maintenance for one changed bit: the
        interval-count delta is fully determined by the two neighbor
        bits (probed AFTER the write — the write never changes them).
        An isolated add creates a run (+1), an add touching one neighbor
        extends one (0), an add bridging two merges them (−1); clears
        are the mirror image. Only applies to an entry that was current
        for the row's pre-write generation; anything else recomputes
        lazily on the next row_run_stats read."""
        entry = self._row_run_stats.get(row_id)
        if entry is None:
            return
        if entry[0] != prev_gen:
            self._row_run_stats.pop(row_id, None)
            return
        col = column % SHARD_WIDTH
        left = col > 0 and self.storage.contains(
            pos(row_id, col - 1))
        right = col < SHARD_WIDTH - 1 and self.storage.contains(
            pos(row_id, col + 1))
        _, n, maxr = entry
        if added:
            n += 1 - int(left) - int(right)
            # isolated: a length-1 run; touching a neighbor: the grown
            # run's length is unknowable from two probes -> recompute
            maxr = max(maxr, 1) if not (left or right) else -1
        else:
            n += int(left) + int(right) - 1
        self._row_run_stats[row_id] = (
            self.row_generation(row_id), n, maxr)

    def max_row_id(self) -> int:
        m = self.storage.max()
        return 0 if m is None else m // SHARD_WIDTH

    def row_ids(self, start: int = 0, limit: Optional[int] = None) -> list[int]:
        """Distinct row ids with any set bit, ascending (rows(),
        fragment.go:2000-2138): walks container keys, not bits. The full
        ascending list is cached per generation — Rows/GroupBy call this
        per shard per query, and the dict store pays a full key sort per
        walk otherwise. Frozen stores keep the cache as a numpy array
        (a billion-row Python list is tens of GB of boxed ints)."""
        from bisect import bisect_left

        cached = self._row_ids_cache
        if cached is None or cached[0] != self.generation:
            kpr = CONTAINERS_PER_SHARD  # container keys per row
            store = self.storage.containers
            if getattr(store, "VECTORIZED_STORE", False):
                ids_arr = self._frozen_row_arrays(store, kpr)[0]
                cached = (self.generation, ids_arr)
            else:
                cached = (self.generation,
                          sorted({key // kpr for key in store}))
            self._row_ids_cache = cached
        ids = cached[1]
        if isinstance(ids, np.ndarray):
            if limit is not None or start:
                if start:
                    ids = ids[int(np.searchsorted(ids, start)):]
                return ids[:limit].tolist()
            # unlimited full walk: box once per generation and memoize —
            # frozen-scale callers should page with limit instead
            full = ids.tolist()
            self._row_ids_cache = (cached[0], full)
            return list(full)
        if start:
            ids = ids[bisect_left(ids, start):]
        return ids[:limit] if limit is not None else list(ids)

    def rows_for_column(self, column: int) -> list[int]:
        """Row ids with this column's bit set — the reference's mutex column
        probe (rowsVector.Get → rows(0, filterColumn(col)),
        fragment.go:2446-2455). The reference walks EVERY container through
        filterColumn (fragment.go:2016-2023, 2062-2106); here the candidate
        keys (key ≡ col>>16 mod keys-per-row) are selected with one
        vectorized mask over the store's key array and probed with one
        batched membership call — no per-key Python loop, so a single
        mutex set_bit against a frozen corpus-scale fragment stays in
        milliseconds."""
        col = column % SHARD_WIDTH
        keys_per_row = CONTAINERS_PER_SHARD
        sub, low = col >> 16, col & 0xFFFF
        store = self.storage.containers
        if getattr(store, "VECTORIZED_STORE", False):
            keys = store.key_and_count_arrays()[0]
        else:
            keys = np.fromiter(store.keys(), np.int64, len(store))
        cand = keys[keys % keys_per_row == sub]
        if cand.size == 0:
            return []
        positions = (cand.astype(np.uint64) << np.uint64(16)) | np.uint64(low)
        mask = self.storage.contains_many(positions)
        return np.sort(cand[mask] // keys_per_row).tolist()

    def bit_count(self) -> int:
        return self.storage.count()

    # -- bulk import (fragment.go:1445-1706) --------------------------------

    @_locked
    def bulk_import(self, row_ids: Iterable[int], columns: Iterable[int]) -> None:
        """Standard bulk set path: group by row, merge into each row, one
        snapshot at the end (bulkImportStandard, fragment.go:1458-1533)."""
        rows = np.asarray(list(row_ids), dtype=np.uint64)
        cols = np.asarray(list(columns), dtype=np.uint64)
        if rows.size != cols.size:
            raise ValueError("row/column length mismatch")
        positions = rows * np.uint64(SHARD_WIDTH) + cols % np.uint64(SHARD_WIDTH)
        self.storage.add_many(positions)
        for rid in np.unique(rows).tolist():
            self._touch(int(rid))
        self._maybe_snapshot()

    @_locked
    def bulk_clear(self, row_ids: Iterable[int], columns: Iterable[int]) -> None:
        """Bulk CLEAR path — the import endpoint's clear=true mode
        (handler.go:1002-1004 doClear -> ImportOptionsClear): remove the
        given bits, one snapshot at the end."""
        rows = np.asarray(list(row_ids), dtype=np.uint64)
        cols = np.asarray(list(columns), dtype=np.uint64)
        if rows.size != cols.size:
            raise ValueError("row/column length mismatch")
        positions = rows * np.uint64(SHARD_WIDTH) + cols % np.uint64(SHARD_WIDTH)
        self.storage.remove_many(positions)
        for rid in np.unique(rows).tolist():
            self._touch(int(rid))
        self._maybe_snapshot()

    @_locked
    def bulk_import_mutex(self, row_ids: Iterable[int], columns: Iterable[int]) -> None:
        """Mutex bulk set path: last write wins per column, and every other
        row's bit for a written column is cleared — preserving the
        one-row-per-column invariant under bulk load (bulkImportMutex,
        fragment.go:1535-1622). The reference probes the mutex vector per
        bit (a rows(filterColumn) container walk each); here the mutex
        invariant bounds total fragment bits by the column space, so ALL
        existing bits are materialized once (one array op) and the
        stale-row clears fall out of pure set algebra — O(bits + batch),
        no per-row or per-bit loop."""
        rows = np.asarray(list(row_ids), dtype=np.uint64)
        cols = np.asarray(list(columns), dtype=np.uint64) % np.uint64(SHARD_WIDTH)
        if rows.size != cols.size:
            raise ValueError("row/column length mismatch")
        if rows.size == 0:
            return
        # last write per column wins: first occurrence in the reversed
        # arrays is the last in import order
        ucols, ridx = np.unique(cols[::-1], return_index=True)
        target_rows = rows[::-1][ridx]  # aligned with ucols (sorted)
        # existing bits in any written column that point at a different row
        all_pos = self.storage.positions()
        all_cols = all_pos % np.uint64(SHARD_WIDTH)
        sel = np.isin(all_cols, ucols)
        cand_pos = all_pos[sel]
        want = target_rows[np.searchsorted(
            ucols, cand_pos % np.uint64(SHARD_WIDTH))]
        to_clear = cand_pos[cand_pos // np.uint64(SHARD_WIDTH) != want]
        add_pos = target_rows * np.uint64(SHARD_WIDTH) + ucols
        store = self.storage.containers
        if getattr(store, "VECTORIZED_STORE", False):
            # frozen store: a wide mutex rewrite touches ~one container per
            # bit, and the generic remove_many/add_many pay a Python loop
            # plus an overlay entry per container. The mutex invariant
            # bounds total bits by the column space, so rebuilding the flat
            # arrays from the final position set is pure O(bits) array math
            from pilosa_tpu.storage.frozen import FrozenContainers
            final = np.union1d(
                np.setdiff1d(all_pos, to_clear, assume_unique=True), add_pos)
            self.storage.containers = FrozenContainers.from_positions(final)
        else:
            if to_clear.size:
                self.storage.remove_many(to_clear)
            self.storage.add_many(add_pos)
        touched = np.unique(np.concatenate(
            [to_clear // np.uint64(SHARD_WIDTH), target_rows]))
        for rid in touched.tolist():
            self._touch(int(rid))
        self._maybe_snapshot()

    @_locked
    def bulk_import_values(self, columns: Iterable[int], values: Iterable[int],
                           bit_depth: int) -> None:
        """BSI bulk import (importValue, fragment.go:1624-1658). Plane
        masks are numpy shifts, not per-value Python loops (the BASELINE
        1B-column config is ~11 planes x 1M values per shard)."""
        cols = as_array(columns, np.uint64) % np.uint64(SHARD_WIDTH)
        vals = as_array(values, np.int64)
        if cols.size != vals.size:
            raise ValueError("column/value length mismatch")
        empty = not self.storage.any()
        add_positions = []
        clear_positions = []
        for i in range(bit_depth):
            bit_base = np.uint64(i * SHARD_WIDTH)
            mask = ((vals >> i) & 1).astype(bool)
            add_positions.append(cols[mask] + bit_base)
            if not empty:
                clear_positions.append(cols[~mask] + bit_base)
        add_positions.append(cols + np.uint64(bit_depth * SHARD_WIDTH))  # not-null
        if clear_positions:
            # zero-plane clears only matter when overwriting prior values —
            # on a fresh fragment there is nothing to clear
            self.storage.remove_many(np.concatenate(clear_positions))
        self.storage.add_many(np.concatenate(add_positions))
        for i in range(bit_depth + 1):
            self._touch(i)
        self._maybe_snapshot()

    @_locked
    def import_frozen(self, positions: np.ndarray,
                      presorted: bool = False) -> None:
        """BASELINE-scale bulk load: replace this (empty) fragment's
        storage with a frozen array-backed store built from shard-local
        bit positions in O(N log N) numpy (storage/frozen.py; the regime
        of fragment.go:1445 bulkImportStandard at 1B rows, where the
        per-container merge loop would cost hours of interpreter time).

        Volatile by design: nothing is written to the WAL or snapshot —
        the load is reproducible from its source, and an 8-GB-plus
        snapshot is exactly the cost this path exists to avoid. The WAL is
        therefore DETACHED for the frozen storage: post-freeze mutations
        COW onto the frozen base in memory but are NOT op-logged (an op
        record against the un-persisted base would replay on restart into
        an empty fragment — silently serving one op's worth of a
        billion-row corpus). Durability is opt-in via snapshot(), which
        persists the full storage and re-attaches the WAL."""
        if self.storage.any():
            raise ValueError("import_frozen requires an empty fragment")
        self.storage = Bitmap.frozen(positions, presorted=presorted)
        self.storage.op_writer = None  # volatile: see docstring
        self._volatile = True
        self._bump_generation()
        self._block_checksums.clear()
        self._row_counts_cache = None
        self._row_ids_cache = None
        self._row_run_stats.clear()

    @_locked
    def import_roaring(self, data: bytes, clear: bool = False) -> None:
        """Union (or clear) a pre-built roaring bitmap into storage in one op
        (importRoaring, fragment.go:1659-1706). The first import into an
        empty fragment costs no Python per container: the payload is
        parsed with numpy into the flat store (Bitmap.flat_store_from_bytes)
        and the snapshot below is written from its arrays, with the same
        durability (answered after the snapshot, read back on restart,
        later Sets to the WAL). Later imports and clear=true keep the
        container path."""
        flat = None
        if not clear and not self.storage.any():
            flat = Bitmap.flat_store_from_bytes(data)
        if flat is not None:
            # the Bitmap object stays, and with it the writer state
            self.storage.containers = flat
        elif clear:
            other = Bitmap.from_bytes(data)
            store = self.storage.containers
            if getattr(store, "VECTORIZED_STORE", False):
                # frozen storage: difference() would materialize + copy
                # the whole corpus; clear in place through the COW
                # overlay, touching only the INCOMING containers. The
                # storage object (and its detached-WAL volatility) is
                # preserved.
                for key, oc in other.containers.items():
                    mine = store.get(key)
                    if mine is None:
                        continue
                    res = mine.op(oc, "difference")
                    if res.n:
                        store[key] = res
                    else:
                        del store[key]
            else:
                # storage replaced: re-attach the WAL (with the configured
                # fsync mode — previously dropped here)
                self.storage = self.storage.difference(other)
                self.storage.op_sync = self.wal_fsync
                self.storage.op_writer = self._op_file
        else:
            # k-way in-place merge — the import hot path (fragment.go:1670
            # unions the incoming bitmap straight into storage); writer
            # state (including a frozen load's detached WAL) is preserved
            self.storage.union_in_place(Bitmap.from_bytes(data))
        self._bump_generation()  # all rows considered dirty
        self._block_checksums.clear()
        self._row_run_stats.clear()
        if self._volatile:
            # bulk writes bypass _touch: count them so /debug/vars'
            # volatileFragments reflects EVERY acknowledged-but-not-
            # durable write, not just the single-bit paths
            self.volatile_mutations += 1
        self._maybe_snapshot()

    # -- snapshot / WAL compaction (fragment.go:1707-1781) ------------------

    @_locked
    def _maybe_snapshot(self) -> None:
        """Auto-snapshot hook for the mutating paths: volatile (frozen)
        fragments skip it — their durability is opt-in via an explicit
        snapshot() call (see import_frozen)."""
        if not self._volatile:
            self.snapshot()

    def snapshot(self) -> None:
        from pilosa_tpu.utils import failpoints

        tmp = self.path + SNAPSHOT_EXT
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        if self._op_file is not None:
            self._op_file.flush()
            self._op_file.close()
            self._op_file = None
        try:
            # re-pick in-memory encodings (introduces run containers where
            # smallest — roaring.go:1594 Optimize before write); lazy entries
            # keep their already-optimal on-disk encoding
            self.storage.optimize()
            with open(tmp, "wb") as f:
                # still-lazy containers pass their raw payloads straight from
                # the old mmap — unread data is never parsed, only copied; the
                # optimize() above already picked encodings, so write skips a
                # second selection scan. The blake2b trailer makes any later
                # in-place damage detectable at open().
                self.storage.write_snapshot(
                    failpoints.wrap_writer("storage.snapshot.write", f),
                    optimized=True)
                f.flush()
                os.fsync(f.fileno())
            failpoints.hit("storage.snapshot.replace")
            os.replace(tmp, self.path)
        except Exception:
            # the write-then-rename protocol means a failure ANYWHERE here
            # leaves the old snapshot + WAL intact on disk: drop the partial
            # tmp file and re-attach the WAL so the fragment keeps serving
            # (and the next snapshot attempt starts clean)
            try:
                os.remove(tmp)
            except OSError:
                pass
            if not self.closed and self._op_file is None:
                self._op_file = open(self.path, "ab", buffering=0)
                self.storage.op_writer = self._op_file
                self.storage.op_sync = self.wal_fsync
            raise
        # the snapshot has landed: whatever happens below (dir fsync EIO,
        # reopen/remap failure), the WAL-attachment invariant must be
        # restored — a closed op_writer left dangling would fail every
        # later write with a misleading "closed file" error
        try:
            if self.wal_fsync:
                # fsync the directory so the rename itself survives power
                # loss (the file's fsync alone doesn't persist the dir entry)
                dfd = os.open(os.path.dirname(self.path) or ".", os.O_RDONLY)
                try:
                    os.fsync(dfd)
                finally:
                    os.close(dfd)
            if not self.closed:
                # the sidecar lock is held throughout — no ownership window
                self._op_file = open(self.path, "ab", buffering=0)
                # the trailer digest was computed by write_snapshot one
                # syscall ago: skip re-hashing the whole section on remap
                self._remap_after_snapshot()
        finally:
            if not self.closed:
                if self._op_file is None:
                    try:
                        self._op_file = open(self.path, "ab", buffering=0)
                    except OSError:
                        # can't reopen the WAL at all: POISON it so writes
                        # refuse loudly — op_writer=None alone would make
                        # _write_op ack writes while logging nothing
                        # (silent durability loss)
                        self.storage.wal_poisoned = True
                self.storage.op_writer = self._op_file
                self.storage.op_sync = self.wal_fsync
            self.op_n = 0
            self.storage.op_n = 0
        self._volatile = False  # persisted: WAL re-attached, durable again
        self.volatile_mutations = 0

    def _remap_after_snapshot(self) -> None:
        """Swap storage onto the freshly-written file (the reference remaps
        after snapshot, fragment.go:1737-1781): lazy entries re-point at the
        new mmap; already-materialized containers carry over as-is (their
        content was just written).

        The old mapping is NOT closed here: lock-free readers may still
        hold the old Bitmap and lazily materialize its containers from the
        old mmap mid-query. Dropping our references lets refcounting
        reclaim the mapping once the last such reader finishes — an
        explicit close would yield 'mmap closed or invalid' crashes on
        queries racing a snapshot."""
        from pilosa_tpu.storage.roaring import LazyContainer

        old = self.storage
        # fresh lazy parse of the new file; this process just computed the
        # trailer digest while writing it, so skip the re-verification
        self._map(verify=False)
        if getattr(old.containers, "VECTORIZED_STORE", False):
            # the snapshot just serialized base+overlay compacted; the
            # fresh parse covers everything, and walking a billion-entry
            # frozen store to "carry over" would materialize the corpus
            return
        for key, c in old.containers.items():
            if not isinstance(c, LazyContainer):
                self.storage.containers[key] = c
            elif c.materialized:
                self.storage.containers[key] = c._real

    # -- anti-entropy block checksums (fragment.go:1226-1443) ---------------

    def blocks(self) -> list[tuple[int, bytes]]:
        """Checksums of 100-row blocks; empty blocks omitted. The reference
        uses xxhash over (row, col) pairs (blockHasher fragment.go:2144);
        any stable digest works since both replicas run this code."""
        out = []
        max_block = self.max_row_id() // HASH_BLOCK_SIZE
        for blk in range(max_block + 1):
            chk = self._block_checksum(blk)
            if chk is not None:
                out.append((blk, chk))
        return out

    def _block_checksum(self, blk: int) -> Optional[bytes]:
        cached = self._block_checksums.get(blk)
        if cached is not None:
            return cached
        lo = blk * HASH_BLOCK_SIZE * SHARD_WIDTH
        hi = (blk + 1) * HASH_BLOCK_SIZE * SHARD_WIDTH
        vals = self.storage.slice(lo, hi)
        if vals.size == 0:
            return None
        h = hashlib.blake2b((vals - np.uint64(lo)).tobytes(), digest_size=16).digest()
        self._block_checksums[blk] = h
        return h

    def block_data(self, blk: int) -> tuple[np.ndarray, np.ndarray]:
        """(rows, cols) pairs of a block (blockData, fragment.go:1307)."""
        lo = blk * HASH_BLOCK_SIZE * SHARD_WIDTH
        hi = (blk + 1) * HASH_BLOCK_SIZE * SHARD_WIDTH
        vals = self.storage.slice(lo, hi)
        rows = (vals // np.uint64(SHARD_WIDTH)).astype(np.int64)
        cols = (vals % np.uint64(SHARD_WIDTH)).astype(np.int64)
        return rows, cols

    @_locked
    def merge_block_majority(self, blk: int, peer_positions: list,
                             majority_n: Optional[int] = None):
        """Majority-consensus merge of one 100-row block across ALL replicas
        at once (mergeBlock, fragment.go:1323-1443; driven per-replica-set by
        syncBlock, fragment.go:2271-2356).

        `peer_positions` holds one uint64 position array per peer replica
        (a peer with no data in the block contributes an empty array — it
        still votes). The target state is every (row, col) pair present on
        at least majorityN = (replicas+1)//2 replicas, local included. With
        one peer that degenerates to union (majorityN=1, no clears) — the
        same grace the reference gets from its 2-replica majority. With
        >=3 replicas, a bit cleared on a majority STAYS cleared (the stale
        replica clears it locally instead of resurrecting it cluster-wide),
        and minority stray bits are removed. Callers that know the
        CONFIGURED replica count pass `majority_n` explicitly so an
        unreachable replica can't shrink the threshold below the true
        majority (server._sync_fragment falls back to union — majority_n=1
        — whenever any configured replica didn't vote).

        Applies the local sets AND clears in place, then returns
        (n_local_sets, n_local_clears, deltas, durable) where deltas[i] is
        the (set_positions, clear_positions) pair the caller pushes to
        peer i (fragment.go:1407-1417 emits both directions per replica).
        `durable` reports whether the local changes are already persisted:
        small adoptions WAL-append as redo records (writeOp,
        roaring.go:977) instead of forcing the caller's per-pass snapshot
        — adopting 10 bits into a 125M-row shard must not rewrite the
        corpus — and volatile (frozen, un-snapshotted) fragments report
        durable=True because their whole contract is opt-in durability:
        a restart loses the base corpus too, and anti-entropy re-adopts
        from the peers that still hold the pairs. Only a large adoption
        on a WAL-attached fragment returns durable=False, asking the
        caller for one snapshot per sync pass.
        Vectorized as sorted position-array set algebra: a 100-row block can
        hold up to 100 * 2^20 pairs, and building Python tuple-sets of those
        froze anti-entropy at BASELINE scale."""
        local_rows, local_cols = self.block_data(blk)
        sw = np.uint64(SHARD_WIDTH)
        local_pos = local_rows.astype(np.uint64) * sw \
            + local_cols.astype(np.uint64)
        votes = [np.unique(np.asarray(p, dtype=np.uint64))
                 for p in peer_positions]
        votes.insert(0, local_pos)  # block_data is already sorted-unique
        if majority_n is None:
            majority_n = (len(votes) + 1) // 2
        uniq, counts = np.unique(np.concatenate(votes), return_counts=True)
        target = uniq[counts >= majority_n]
        deltas = []
        for posarr in votes:
            deltas.append((np.setdiff1d(target, posarr),
                           np.setdiff1d(posarr, target)))
        local_sets, local_clears = deltas[0]
        if local_sets.size:
            self.storage.add_many(local_sets)
        if local_clears.size:
            self.storage.remove_many(local_clears)
        durable = True
        n_changed = int(local_sets.size) + int(local_clears.size)
        if n_changed:
            changed = np.concatenate([local_sets, local_clears])
            for rid in np.unique(changed // sw):
                self._touch(int(rid))
            if self._volatile:
                pass  # volatile contract: durability is opt-in (docstring)
            elif (self.storage.op_writer is not None
                  and n_changed <= MAX_OP_N):
                self.storage.append_ops(local_sets, local_clears)
                self.op_n += n_changed
                if self.op_n > MAX_OP_N:
                    self._maybe_snapshot()  # bounds WAL growth as usual
            else:
                durable = False
        return (int(local_sets.size), int(local_clears.size), deltas[1:],
                durable)

    @_locked
    def merge_block(self, blk: int, peer_rows: np.ndarray, peer_cols: np.ndarray):
        """2-replica merge: with a single peer the majority threshold is 1,
        so this is the union merge (mergeBlock, fragment.go:1366 with
        len(sets)==2); returns (sets_for_peer_rows, sets_for_peer_cols,
        n_adopted) — the deltas the caller pushes back plus how many peer
        pairs were merged in locally."""
        sw = np.uint64(SHARD_WIDTH)
        peer_pos = np.asarray(peer_rows, dtype=np.uint64) * sw \
            + np.asarray(peer_cols, dtype=np.uint64)
        n_sets, _n_clears, deltas, _durable = self.merge_block_majority(
            blk, [peer_pos])
        peer_sets, _peer_clears = deltas[0]
        return ((peer_sets // sw).astype(np.int64),
                (peer_sets % sw).astype(np.int64),
                n_sets)

    # -- archive streaming for resize copies (fragment.go:1823-1998) --------

    def write_to_tar(self, fileobj) -> None:
        with tarfile.open(fileobj=fileobj, mode="w") as tar:
            data = self.storage.to_bytes()
            info = tarfile.TarInfo("data")
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))

    @_locked
    def read_from_tar(self, fileobj) -> None:
        with tarfile.open(fileobj=fileobj, mode="r") as tar:
            member = tar.getmember("data")
            data = tar.extractfile(member).read()
        self.storage = Bitmap.from_bytes(data)
        self.storage.op_writer = self._op_file
        self._bump_generation()
        self._block_checksums.clear()
        self._row_run_stats.clear()
        if self._volatile:
            self.volatile_mutations += 1  # see import_roaring
        self._maybe_snapshot()

    # -- identity -----------------------------------------------------------

    def key(self) -> tuple[str, str, str, int]:
        return (self.index, self.field, self.view, self.shard)

    def __repr__(self) -> str:
        return f"<Fragment {self.index}/{self.field}/{self.view}/{self.shard} bits={self.bit_count()}>"
