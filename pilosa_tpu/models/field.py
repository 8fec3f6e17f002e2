"""Field: a typed sub-matrix of an index.

Reference: field.go — five types (set / int / time / mutex / bool,
field.go:53-59), functional options (field.go:90-174), views map, BSI groups
(field.go:1356-1437), time quantum (field.go:637-665), and the
available-shards bitmap persisted to `.available.shards` (field.go:255-317).

BSI encoding: an int field's values are stored in view "bsig_<field>" with
base = min; stored value = value - min; bit depth covers (max - min)
(bsiGroup, field.go:1364). Mutex/bool enforce one-row-per-column by
clear-then-set (mutexVector, fragment.go:2426-2485).
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import asdict, dataclass, field as dc_field
from datetime import datetime
from typing import Iterable, Optional

import numpy as np

from pilosa_tpu.constants import DEFAULT_CACHE_SIZE, SHARD_WIDTH
from pilosa_tpu.models import timequantum
from pilosa_tpu.models.row import Row
from pilosa_tpu.models.view import VIEW_BSI_PREFIX, VIEW_STANDARD, View, view_path
from pilosa_tpu.storage.roaring import Bitmap


class FieldType:
    SET = "set"
    INT = "int"
    TIME = "time"
    MUTEX = "mutex"
    BOOL = "bool"

    ALL = (SET, INT, TIME, MUTEX, BOOL)


@dataclass
class FieldOptions:
    type: str = FieldType.SET
    cache_type: str = "ranked"
    cache_size: int = DEFAULT_CACHE_SIZE
    min: int = 0
    max: int = 0
    time_quantum: str = ""
    keys: bool = False

    def validate(self) -> None:
        from pilosa_tpu.models.cache import _CACHE_TYPES
        if self.type not in FieldType.ALL:
            raise ValueError(f"invalid field type: {self.type}")
        if self.cache_type not in _CACHE_TYPES:
            raise ValueError(f"invalid cache type: {self.cache_type}")
        if self.type == FieldType.INT and self.max < self.min:
            raise ValueError("int field max must be >= min")
        if self.type == FieldType.TIME:
            timequantum.validate_quantum(self.time_quantum)
            if not self.time_quantum:
                raise ValueError("time field requires a time quantum")


class Field:
    def __init__(self, path: str, index: str, name: str,
                 options: Optional[FieldOptions] = None,
                 wal_fsync: Optional[bool] = None):
        self.path = path
        self.index = index
        self.name = name
        self.options = options or FieldOptions()
        # [storage] wal-fsync, plumbed down to every fragment of every view
        self.wal_fsync = wal_fsync
        self.views: dict[str, View] = {}
        # two concurrent first-writes must not both construct a View for
        # the same name: each would open (flock) the same fragment files
        self._view_mu = threading.Lock()
        # guards available_shards, shards_version and the file they are
        # saved to
        self._shards_mu = threading.Lock()
        self.available_shards = Bitmap()
        # bumped on every available-shards change: Index.available_shards
        # memoizes its union on the tuple of field versions (the query
        # path calls it per query; re-slicing the union each time was a
        # measurable share of serving CPU on 1-core hosts)
        self.shards_version = 0
        # row attr store (reference: field.go rowAttrStore, boltdb-backed)
        from pilosa_tpu.utils.attrstore import AttrStore
        self.row_attrs = AttrStore(os.path.join(self.path, ".row_attrs.db"))
        # fired on newly-available shards so the server can broadcast a
        # CreateShardMessage (view.go:208-263); (index, field, shard) ->
        self.on_shard_added = None

    # -- derived ------------------------------------------------------------

    @property
    def bsi_view_name(self) -> str:
        return VIEW_BSI_PREFIX + self.name

    @property
    def base(self) -> int:
        """BSI offset: stored value = actual - base (field.go:1364)."""
        return self.options.min

    @property
    def bit_depth(self) -> int:
        span = self.options.max - self.options.min
        return max(span.bit_length(), 1)

    def _track_rank(self) -> bool:
        return (self.options.type in (FieldType.SET, FieldType.MUTEX, FieldType.BOOL, FieldType.TIME)
                and self.options.cache_type != "none")

    # -- lifecycle ----------------------------------------------------------

    def open(self) -> "Field":
        os.makedirs(self.path, exist_ok=True)
        self.row_attrs.open()
        meta = os.path.join(self.path, ".meta")
        if os.path.exists(meta):
            with open(meta) as f:
                self.options = FieldOptions(**json.load(f))
        else:
            self.save_meta()
        avail = os.path.join(self.path, ".available.shards")
        if os.path.exists(avail):
            with open(avail, "rb") as f:
                data = f.read()
            if data:
                self.available_shards = Bitmap.from_bytes(data)
                self.shards_version += 1
        views_dir = os.path.join(self.path, "views")
        if os.path.isdir(views_dir):
            for vname in os.listdir(views_dir):
                self._open_view(vname)
        return self

    def close(self) -> None:
        for v in self.views.values():
            v.close()
        self.views.clear()
        self.row_attrs.close()

    def save_meta(self) -> None:
        os.makedirs(self.path, exist_ok=True)
        with open(os.path.join(self.path, ".meta"), "w") as f:
            json.dump(asdict(self.options), f)

    def _save_available_shards(self) -> None:
        with open(os.path.join(self.path, ".available.shards"), "wb") as f:
            self.available_shards.write_to(f)

    def _open_view(self, name: str) -> View:
        v = self.views.get(name)
        if v is None:
            with self._view_mu:  # double-checked: creation is rare
                v = self.views.get(name)
                if v is None:
                    v = View(view_path(self.path, name), self.index,
                             self.name, name,
                             track_rank=self._track_rank()
                             and not name.startswith(VIEW_BSI_PREFIX),
                             cache_size=self.options.cache_size,
                             cache_type=self.options.cache_type,
                             wal_fsync=self.wal_fsync).open()
                    self.views[name] = v
        return v

    def view(self, name: str = VIEW_STANDARD) -> Optional[View]:
        return self.views.get(name)

    def create_view_if_not_exists(self, name: str) -> View:
        return self._open_view(name)

    # -- shard tracking -----------------------------------------------------

    def add_available_shard(self, shard: int, quiet: bool = False) -> None:
        # the cheap test first, unlocked (every Set passes here); the test,
        # the add, the version and the save again under the lock: two
        # imports of different shards at once used to lose one of them
        # (Bitmap.add from two threads, and a save of the older bitmap
        # over the newer one), an acknowledged shard no query then served
        if self.available_shards.contains(shard):
            return
        with self._shards_mu:
            if self.available_shards.contains(shard):
                return
            self.available_shards.add(shard)
            self.shards_version += 1
            self._save_available_shards()
        if self.on_shard_added is not None and not quiet:
            self.on_shard_added(self.index, self.name, shard)

    def remove_available_shard(self, shard: int) -> None:
        with self._shards_mu:
            if self.available_shards.contains(shard):
                self.available_shards.remove(shard)
                self.shards_version += 1
                self._save_available_shards()

    def shards(self) -> list[int]:
        return [int(s) for s in self.available_shards.slice()]

    # -- write paths (field.go:803-1214) ------------------------------------

    def _views_for_write(self, timestamp: Optional[datetime]) -> list[str]:
        if self.options.type == FieldType.TIME:
            views = [] if timestamp is None else timequantum.views_by_time(
                VIEW_STANDARD, timestamp, self.options.time_quantum)
            return [VIEW_STANDARD] + views
        if timestamp is not None:
            if self.options.type == FieldType.SET:
                raise ValueError("timestamp given on non-time field")
            raise ValueError("timestamp given on non-time field")
        return [VIEW_STANDARD]

    def set_bit(self, row_id: int, column: int,
                timestamp: Optional[datetime] = None) -> bool:
        """SetBit (field.go:803): writes the standard view plus one time view
        per quantum unit; mutex/bool clear other rows first."""
        if self.options.type == FieldType.INT:
            raise ValueError(f"field {self.name} is an int field; use set_value")
        if self.options.type == FieldType.BOOL and row_id not in (0, 1):
            raise ValueError("bool field rows must be 0 (false) or 1 (true)")
        if self.options.type in (FieldType.MUTEX, FieldType.BOOL):
            self._clear_other_rows(row_id, column)
        changed = False
        for vname in self._views_for_write(timestamp):
            changed |= self.create_view_if_not_exists(vname).set_bit(row_id, column)
        self.add_available_shard(column // SHARD_WIDTH)
        return changed

    def clear_bit(self, row_id: int, column: int) -> bool:
        changed = False
        for v in self.views.values():
            if not v.name.startswith(VIEW_BSI_PREFIX):
                changed |= v.clear_bit(row_id, column)
        return changed

    def _clear_other_rows(self, row_id: int, column: int) -> None:
        """Mutex semantics: at most one row set per column (mutexVector
        clear-then-set, fragment.go:387-407). Uses the column probe
        (rows_for_column — fragment.go:2446-2455 rowsVector.Get) so cost is
        independent of how many rows the fragment holds."""
        shard = column // SHARD_WIDTH
        for v in self.views.values():
            if v.name.startswith(VIEW_BSI_PREFIX):
                continue
            frag = v.fragment(shard)
            if frag is None:
                continue
            for rid in frag.rows_for_column(column):
                if rid != row_id:
                    v.clear_bit(rid, column)

    def set_value(self, column: int, value: int) -> bool:
        """SetValue (field.go:951): store value - base in the BSI view,
        auto-expanding max like the reference does on import."""
        if self.options.type != FieldType.INT:
            raise ValueError(f"field {self.name} is not an int field")
        if value < self.options.min or value > self.options.max:
            raise ValueError(
                f"value {value} out of range [{self.options.min}, {self.options.max}]")
        v = self.create_view_if_not_exists(self.bsi_view_name)
        shard = column // SHARD_WIDTH
        frag = v.create_fragment_if_not_exists(shard)
        changed = frag.set_value(column % SHARD_WIDTH, self.bit_depth, value - self.base)
        self.add_available_shard(shard)
        return changed

    def value(self, column: int) -> tuple[int, bool]:
        v = self.views.get(self.bsi_view_name)
        if v is None:
            return 0, False
        frag = v.fragment(column // SHARD_WIDTH)
        if frag is None:
            return 0, False
        raw, ok = frag.value(column % SHARD_WIDTH, self.bit_depth)
        return (raw + self.base, True) if ok else (0, False)

    def clear_value(self, column: int) -> bool:
        v = self.views.get(self.bsi_view_name)
        if v is None:
            return False
        frag = v.fragment(column // SHARD_WIDTH)
        if frag is None:
            return False
        return frag.clear_value(column % SHARD_WIDTH, self.bit_depth)

    # -- bulk import (field.go:1058-1214) -----------------------------------

    def import_bits(self, row_ids: Iterable[int], columns: Iterable[int],
                    timestamps: Optional[Iterable[Optional[datetime]]] = None,
                    clear: bool = False) -> None:
        """Bulk import; clear=True removes the bits instead (the import
        endpoint's clear mode, http/handler.go:1002-1004)."""
        rows = list(row_ids)
        cols = list(columns)
        tss = list(timestamps) if timestamps is not None else [None] * len(rows)
        if not (len(rows) == len(cols) == len(tss)):
            raise ValueError("row/column/timestamp length mismatch")
        # group (view, shard) -> (rows, cols)
        groups: dict[tuple[str, int], tuple[list[int], list[int]]] = {}
        for r, c, ts in zip(rows, cols, tss):
            for vname in self._views_for_write(ts if self.options.type == FieldType.TIME else None):
                key = (vname, c // SHARD_WIDTH)
                g = groups.setdefault(key, ([], []))
                g[0].append(r)
                g[1].append(c % SHARD_WIDTH)
        mutex = self.options.type in (FieldType.MUTEX, FieldType.BOOL)
        for (vname, shard), (grows, gcols) in groups.items():
            view = self.create_view_if_not_exists(vname)
            frag = view.create_fragment_if_not_exists(shard)
            if clear:
                frag.bulk_clear(grows, gcols)
            elif mutex:
                frag.bulk_import_mutex(grows, gcols)
            else:
                frag.bulk_import(grows, gcols)
            view.refresh_rank_cache(shard)
            self.add_available_shard(shard)

    def import_rows_frozen(self, row_ids: np.ndarray,
                           columns: np.ndarray) -> None:
        """BASELINE-scale set-field bulk load through the frozen store:
        shard split and bit positions are pure numpy, each shard's
        fragment freezes in one shot, and rank caches build from the flat
        key layout instead of a per-row Python walk (see
        fragment.import_frozen / view.load_frozen_fragment). Standard
        view only — time/mutex/bool semantics need the mutating paths."""
        if self.options.type != FieldType.SET or self.options.time_quantum:
            raise ValueError(
                "import_rows_frozen supports plain set fields only")
        rows = np.asarray(row_ids, dtype=np.uint64)
        cols = np.asarray(columns, dtype=np.uint64)
        if rows.size != cols.size:
            raise ValueError("row/column length mismatch")
        w = np.uint64(SHARD_WIDTH)
        shards = (cols // w).astype(np.int64)
        positions = rows * w + cols % w
        order = np.lexsort((positions, shards))
        shards, positions = shards[order], positions[order]
        boundaries = np.flatnonzero(np.diff(shards)) + 1
        view = self.create_view_if_not_exists(VIEW_STANDARD)
        for g_shards, g_pos in zip(np.split(shards, boundaries),
                                   np.split(positions, boundaries)):
            shard = int(g_shards[0])
            view.load_frozen_fragment(shard, g_pos)
            self.add_available_shard(shard)

    def _values_shard_groups(self, columns: Iterable[int],
                             values: Iterable[int]):
        """Validate, dedup (LAST write per column wins — importValue
        semantics, fragment.go:1624: applying both duplicates would leave
        the bitwise OR of the values, a value never written) and split a
        BSI import into per-shard (shard, cols, vals) groups. Fully
        vectorized: a Python-loop grouping walks every (col, val) pair —
        at the BASELINE 1B-column scale that alone is hours."""
        from pilosa_tpu.storage.fragment import as_array

        cols = as_array(columns, np.uint64)
        vals = as_array(values, np.int64)
        if cols.size != vals.size:
            raise ValueError("column/value length mismatch")
        if vals.size and (int(vals.min()) < self.options.min
                          or int(vals.max()) > self.options.max):
            bad = vals[(vals < self.options.min) | (vals > self.options.max)]
            raise ValueError(f"value {int(bad[0])} out of range")
        if cols.size == 0:
            return []
        order = np.argsort(cols, kind="stable")
        cols, vals = cols[order], vals[order]
        if cols.size > 1:
            # after a stable sort the last duplicate is last in input order
            last = np.concatenate([cols[1:] != cols[:-1], [True]])
            cols, vals = cols[last], vals[last]
        shards = (cols // np.uint64(SHARD_WIDTH)).astype(np.int64)
        boundaries = np.flatnonzero(np.diff(shards)) + 1
        # eager list, not a generator: callers create the BSI view AFTER
        # this validates, so a rejected import leaves no ghost empty view
        return [(int(gcols[0] // np.uint64(SHARD_WIDTH)),
                 gcols % np.uint64(SHARD_WIDTH), gvals - self.base)
                for gcols, gvals in zip(np.split(cols, boundaries),
                                        np.split(vals, boundaries))]

    def import_values(self, columns: Iterable[int], values: Iterable[int]) -> None:
        """BSI bulk import through the mutating path (WAL-detached bulk
        merge + snapshot per touched fragment)."""
        groups = self._values_shard_groups(columns, values)
        view = self.create_view_if_not_exists(self.bsi_view_name)
        for shard, scols, svals in groups:
            frag = view.create_fragment_if_not_exists(shard)
            frag.bulk_import_values(scols, svals, self.bit_depth)
            self.add_available_shard(shard)

    def import_values_frozen(self, columns: Iterable[int],
                             values: Iterable[int]) -> None:
        """BASELINE-scale BSI bulk load through the frozen store — the
        deferred-durability analog of import_rows_frozen for INT fields:
        plane masks become one sorted position array per shard and each
        (empty) fragment freezes in one shot, skipping the per-container
        merge loops and per-batch snapshots of the mutating path
        (importValue, fragment.go:1624-1658 at 1B-column scale). Volatile
        like import_frozen: durability is opt-in via snapshot()."""
        if self.options.type != FieldType.INT:
            raise ValueError("import_values_frozen supports int fields only")
        groups = self._values_shard_groups(columns, values)
        view = self.create_view_if_not_exists(self.bsi_view_name)
        depth = self.bit_depth
        sw = np.uint64(SHARD_WIDTH)
        for shard, scols, svals in groups:
            # plane ranges are disjoint and scols is sorted-unique, so each
            # plane slice is already sorted — concatenation in plane order
            # IS the sorted position array (presorted skips a re-sort of
            # depth x |cols| positions per shard)
            planes = [scols[((svals >> i) & 1).astype(bool)]
                      + np.uint64(i) * sw for i in range(depth)]
            planes.append(scols + np.uint64(depth) * sw)  # not-null row
            view.load_frozen_fragment(shard, np.concatenate(planes),
                                      presorted=True)
            self.add_available_shard(shard)

    # -- reads --------------------------------------------------------------

    def row(self, row_id: int, view: str = VIEW_STANDARD) -> Row:
        """Whole-field row across shards (Field.Row, field.go:791)."""
        v = self.views.get(view)
        out = Row()
        if v is None:
            return out
        for shard in v.shards():
            frag = v.fragment(shard)
            cols = frag.row_columns(row_id)
            if cols.size:
                out.segments[shard] = cols.astype(np.uint64) + np.uint64(shard * SHARD_WIDTH)
        return out

    def row_time(self, row_id: int, start: datetime, end: datetime) -> Row:
        """Union of time views covering [start, end) (RowTime field.go:666)."""
        if self.options.type != FieldType.TIME:
            raise ValueError("row_time on non-time field")
        out = Row()
        for vname in timequantum.views_by_time_range(
                VIEW_STANDARD, start, end, self.options.time_quantum):
            out = out.union(self.row(row_id, view=vname))
        return out
