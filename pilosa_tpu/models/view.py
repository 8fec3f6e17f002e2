"""View: a named sub-bitmap of a field.

Reference: view.go — "standard" (view.go:34), time views "standard_YYYYMMDDHH"
(time.go:63-215) and BSI views "bsig_<field>" (view.go:36); a view owns
fragments by shard and creates them on demand (view.go:208-263).
"""

from __future__ import annotations

import itertools
import os
import threading
from typing import Optional

import numpy as np

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.models.cache import (
    CACHE_TYPE_NONE,
    CACHE_TYPE_RANKED,
    RankCache,
    load_cache,
    make_cache,
)
from pilosa_tpu.storage.fragment import Fragment

VIEW_STANDARD = "standard"
VIEW_BSI_PREFIX = "bsig_"

# write versions are drawn from one process-wide sequence (next() on it
# is one C call, atomic under the interpreter lock), so no two views, and
# no view and its recreation under the same name, ever show the same one
_WRITE_VERSIONS = itertools.count(1)


def view_path(field_path: str, name: str) -> str:
    return os.path.join(field_path, "views", name)


class View:
    def __init__(self, path: str, index: str, field: str, name: str,
                 track_rank: bool = False, cache_size: int = 50000,
                 cache_type: str = CACHE_TYPE_RANKED,
                 wal_fsync: Optional[bool] = None):
        self.path = path
        self.index = index
        self.field = field
        self.name = name
        # [storage] wal-fsync, plumbed holder->index->field->view->fragment
        # (None = fragment default; PILOSA_TPU_WAL_FSYNC env overrides)
        self.wal_fsync = wal_fsync
        self.fragments: dict[int, Fragment] = {}
        # serializes fragment creation: two HTTP threads racing
        # create_fragment_if_not_exists would both construct + open() the
        # same file, and the loser trips its sibling's flock
        self._frag_mu = threading.Lock()
        self.track_rank = track_rank and cache_type != CACHE_TYPE_NONE
        self.cache_size = cache_size
        self.cache_type = cache_type
        self.rank_caches: dict[int, RankCache] = {}
        # write version: grows whenever a fragment of this view bumps its
        # generation (Fragment._bump_generation, after the generation),
        # is created or is dropped. What is computed from the fragments
        # is stamped with the version read BEFORE them
        # (parallel/residency.py RowStatsMemo).
        self.version = next(_WRITE_VERSIONS)

    def bump_version(self) -> None:
        self.version = next(_WRITE_VERSIONS)

    # -- lifecycle ----------------------------------------------------------

    def open(self) -> "View":
        frag_dir = os.path.join(self.path, "fragments")
        if os.path.isdir(frag_dir):
            for fname in os.listdir(frag_dir):
                if fname.endswith((".cache", ".snapshotting", ".tmp",
                                   ".lock")):
                    continue
                try:
                    shard = int(fname)
                except ValueError:
                    continue
                self._open_fragment(shard)
        return self

    def flush_caches(self) -> int:
        """Persist rank caches without closing (fragment.FlushCache,
        fragment.go:1796-1821, driven by holder.monitorCacheFlush). Returns
        caches written."""
        n = 0
        for shard, frag in list(self.fragments.items()):
            cache = self.rank_caches.get(shard)
            if cache is not None:
                cache.save(frag.path + ".cache")
                n += 1
        return n

    def close(self) -> None:
        self.flush_caches()
        for frag in self.fragments.values():
            frag.close()
        self.fragments.clear()
        self.rank_caches.clear()
        self.bump_version()

    def _open_fragment(self, shard: int) -> Fragment:
        frag = Fragment(
            os.path.join(self.path, "fragments", str(shard)),
            self.index, self.field, self.name, shard,
            wal_fsync=self.wal_fsync,
        )
        frag.on_generation = self.bump_version
        frag.open()
        self.fragments[shard] = frag
        self.bump_version()
        if self.track_rank:
            cache_path = frag.path + ".cache"
            if os.path.exists(cache_path):
                self.rank_caches[shard] = load_cache(cache_path)
            else:
                self.rank_caches[shard] = self._rebuilt_rank_cache(frag)
        return frag

    def _rebuilt_rank_cache(self, frag: Fragment):
        """A fresh rank cache from the fragment's exact row counts: ONE
        vectorized key pass (Fragment.row_counts) — per-row row_count scans
        the whole key space per row on the dict store, quadratic in a
        10k-row field — under the fragment write lock, so a concurrent
        import into the same fragment can neither mutate the store mid-pass
        nor store an older count over a newer one."""
        cache = make_cache(self.cache_type, self.cache_size)
        with frag.mu:
            ids = frag.row_ids()
            cache.bulk_add(zip(ids, frag.row_counts(ids).tolist()))
        return cache

    # -- fragment routing ---------------------------------------------------

    def fragment(self, shard: int) -> Optional[Fragment]:
        return self.fragments.get(shard)

    def create_fragment_if_not_exists(self, shard: int) -> Fragment:
        frag = self.fragments.get(shard)
        if frag is None:
            with self._frag_mu:  # double-checked: creation is rare
                frag = self.fragments.get(shard)
                if frag is None:
                    frag = self._open_fragment(shard)
        return frag

    def shards(self) -> list[int]:
        return sorted(self.fragments)

    def delete_fragment(self, shard: int) -> None:
        """Drop a fragment and its files — post-resize GC of shards this node
        no longer owns (holderCleaner, holder.go:855-906)."""
        frag = self.fragments.pop(shard, None)
        if frag is None:
            return
        self.bump_version()
        frag.close()
        for p in (frag.path, frag.path + ".cache", frag.path + ".snapshotting",
                  frag.path + ".lock"):
            if os.path.exists(p):
                os.remove(p)
        self.rank_caches.pop(shard, None)

    # -- writes (global column space; view.setBit view.go:309) --------------

    def set_bit(self, row_id: int, column: int) -> bool:
        shard = column // SHARD_WIDTH
        frag = self.create_fragment_if_not_exists(shard)
        changed = frag.set_bit(row_id, column % SHARD_WIDTH)
        if changed:
            self._update_rank(shard, frag, row_id)
        return changed

    def clear_bit(self, row_id: int, column: int) -> bool:
        shard = column // SHARD_WIDTH
        frag = self.fragments.get(shard)
        if frag is None:
            return False
        changed = frag.clear_bit(row_id, column % SHARD_WIDTH)
        if changed:
            self._update_rank(shard, frag, row_id)
        return changed

    def _update_rank(self, shard: int, frag: Fragment, row_id: int) -> None:
        cache = self.rank_caches.get(shard)
        if cache is not None:
            # row_count walks at most 16 container keys — cheap enough to
            # keep cached counts exact (the reference recounts via rowCache,
            # fragment.go:435-440). The count-read + cache-store pair runs
            # under the fragment write lock: two racing writers could
            # otherwise store their reads out of order and pin a stale
            # count until the row's next write.
            with frag.mu:
                cache.add(row_id, frag.row_count(row_id))

    def refresh_rank_cache(self, shard: int) -> None:
        if not self.track_rank:
            return
        frag = self.fragments.get(shard)
        if frag is None:
            return
        self.rank_caches[shard] = self._rebuilt_rank_cache(frag)

    def load_frozen_fragment(self, shard: int, positions: np.ndarray,
                             presorted: bool = False) -> Fragment:
        """Bulk-load one shard's fragment from shard-local bit positions
        via the frozen store (fragment.import_frozen), building the rank
        cache VECTORIZED: per-row counts come from the frozen key layout
        and only the top cache_size rows enter the cache — equivalent to
        the reference's add-then-prune (cache.go Invalidate keeps the top
        cache_size by rank), but without iterating a billion rows in
        Python."""
        frag = self.create_fragment_if_not_exists(shard)
        frag.import_frozen(positions, presorted=presorted)
        if self.track_rank:
            from pilosa_tpu.constants import CONTAINERS_PER_SHARD

            cache = make_cache(self.cache_type, self.cache_size)
            uids, sums = frag._frozen_row_arrays(frag.storage.containers,
                                                 CONTAINERS_PER_SHARD)
            k = getattr(cache, "cache_size", self.cache_size)
            if uids.size > k:
                top = np.argpartition(-sums, k - 1)[:k]
                uids, sums = uids[top], sums[top]
            cache.bulk_add(zip(uids.tolist(), sums.tolist()))
            self.rank_caches[shard] = cache
        return frag
