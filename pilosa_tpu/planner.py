"""Cost-based query planner: cardinality-ordered rewrites + plan-cache keys.

The executor historically evaluated PQL call trees exactly as written and
recomputed every subexpression from scratch per query. This module is the
pass between parse and execution that exploits the statistics storage
already maintains (per-row container-cardinality sums, fragment.py
row_cardinality; per-row write generations, fragment.py row_generation):

  * **Reorder** commutative Intersect/Union/Xor chains cheapest-first by
    estimated cardinality — the cardinality-ordered intersection of the
    roaring literature (Chambi/Lemire et al., arXiv:1402.6407; the
    skewed-intersection regime of arXiv:1401.6399). On the dense TPU
    engine every AND costs the same per word, so the *ordering* win here
    is canonicalization: `Intersect(A, B)` and `Intersect(B, A)` plan to
    the same tree and therefore the same plan-cache key, which is what
    makes the cross-query cache hit across users phrasing the same
    dashboard panel differently.
  * **Short-circuit** provably-empty branches. Cardinality estimates are
    upper bounds except where exact (a Row's maintained count, an unknown
    row key), and only *exact zeros over validated subtrees* rewrite:
    a zero-cardinality operand empties an Intersect, empty operands drop
    out of Union/Xor/Difference tails. The rewrite target is the
    canonical empty call, zero-arg `Union()` — the executor skips leaf
    materialization and the device dispatch entirely.
  * **Push reductions down.** `Count(bitmap)` and `TopN(src=bitmap)`
    shapes are marked `pushdown`: the executor evaluates them with fused
    count kernels / HBM-resident source rows (ops/bitvector.py
    intersect_chain_count_total, runner.row_leaves_dev), so no
    intermediate row bitmap is ever materialized on host — the profiler's
    plan node records hostRowBitmapBytes=0 as the verifiable contract.
  * **Choose device representation per operand.** The same exact
    cardinalities drive the hybrid sparse/dense container decision
    (choose_representation below): rows at or below [query]
    sparse-threshold bits per shard upload as padded sorted-index arrays
    with merge/gather-test kernels (ops/bitvector.py), dense rows
    keep full planes — recorded on the plan node like the ICI route.
  * **Key the cross-query plan cache.** subtree_cache_key() canonicalizes
    a planned subtree to (index, PQL text, shard set, per-leaf fragment
    row generations) — the same generation-keying discipline the
    residency layer uses for device leaves (parallel/residency.py), so
    invalidation is free: any write bumps a generation and changes the
    key.

Planning is advisory and defensive: any unexpected estimation failure
degrades to the written-order tree (never a new error), validation errors
the executor would raise still surface (a subtree containing an unknown
field is never planned away), and shared parsed ASTs are treated as
immutable — rewrites build fresh Call nodes (parse_string_cached shares
Query objects across threads).

Kill switches: PILOSA_TPU_PLANNER=0 disables planning, the
PILOSA_TPU_PLAN_CACHE=0 twin disables the cache (both also [query] config
knobs, cli/config.py).
"""

from __future__ import annotations

import contextvars
import threading
import time
from datetime import datetime
from typing import NamedTuple, Optional

from pilosa_tpu.models import timequantum
from pilosa_tpu.models.field import FieldType
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.pql import Call, Condition
from pilosa_tpu.utils.profile import truncate_pql
from pilosa_tpu.utils.tracing import TimedLock

# the plan node of the call currently executing (the profiler's "plan"
# entry): the executor sets it around dispatch so cache hit/miss events
# recorded deep in the evaluation (plan-cache lookups for subtrees) land
# in the same dict ?profile=true serializes. Fan-out pool submits run in
# copied contexts, so worker threads see the same dict.
current_plan: contextvars.ContextVar[Optional[dict]] = \
    contextvars.ContextVar("pilosa_current_plan", default=None)

# the ICI routing decision of the distributed call currently executing
# (executor._execute_distributed sets it around BOTH branches): plan_call
# copies it into the plan node, so ?profile=true and /debug/query-history
# show slice_local vs cross_slice alongside the operand order — and the
# fan-out pool's copied contexts propagate it to per-node planning.
current_route: contextvars.ContextVar[Optional[dict]] = \
    contextvars.ContextVar("pilosa_current_route", default=None)

BITMAP_CALLS = {"Row", "Union", "Intersect", "Difference", "Xor", "Not",
                "Range"}
COMMUTATIVE = ("Intersect", "Union", "Xor")
# calls the executor hands to plan_call (reads with bitmap operands)
PLANNED_CALLS = frozenset(BITMAP_CALLS | {"Count", "TopN", "Sum", "Min",
                                          "Max", "GroupBy"})

_EXPR_LIMIT = 96  # truncation for expr strings in plan/profile nodes


def empty_operand_error(call: Call):
    """The clean zero-operand error (`Intersect()` / `Difference()`):
    names the offending PQL fragment and its source position instead of
    the old bare "currently not supported"."""
    from pilosa_tpu.executor import ExecutionError
    where = (f" at PQL offset {call.pos}" if getattr(call, "pos", None)
             is not None else "")
    return ExecutionError(
        f"{call.name}() requires at least one bitmap operand{where} "
        f"(offending fragment: {call.to_pql()})")


def empty_call(like: Optional[Call] = None) -> Call:
    """The canonical provably-empty bitmap call: zero-arg Union() (already
    legal PQL — executor.go:1446 folds no children into an empty row)."""
    return Call("Union", pos=getattr(like, "pos", None))


def is_empty_call(c: Call) -> bool:
    return c.name == "Union" and not c.children and not c.args


class Estimate(NamedTuple):
    """Cardinality estimate of one subtree over the query's shard set.

    `count` is an upper bound (None = unknown); `exact` marks it exactly
    right for the current generations — the gate for zero short-circuits.
    `valid` marks the subtree as one the executor would evaluate without a
    validation error; rewrites only ever *skip executing* subtrees that
    are valid, so planning never swallows a "field not found"."""

    count: Optional[int]
    exact: bool
    valid: bool


UNKNOWN = Estimate(None, False, False)
ZERO = Estimate(0, True, True)


def _exact_zero(e: Estimate) -> bool:
    return e.exact and e.valid and e.count == 0


class QueryPlanner:
    """Per-executor planning pass + counters (/debug/vars `planner`,
    /metrics planner/{reorders,pushdowns,shortCircuits})."""

    def __init__(self, executor):
        self.executor = executor
        self.enabled = True
        self._lock = TimedLock("planner", threading.Lock())
        self.plans = 0
        self.reorders = 0
        self.pushdowns = 0
        self.short_circuits = 0

    # ------------------------------------------------------------- entry

    def plan_call(self, index, call: Call, shards) -> tuple[Call, dict]:
        """Plan one top-level call: returns (planned call, plan info dict).
        The input tree is never mutated (parsed ASTs are shared); the plan
        info dict is what the profiler serializes as the call's `plan`
        node and what the executor appends cache events to."""
        info = {"call": call.name, "reorders": 0, "shortCircuits": 0,
                "pushdown": False, "order": None, "estimates": [],
                "cache": [], "hostRowBitmapBytes": 0}
        route = current_route.get()
        if route is not None:
            # the ICI slice-local-vs-cross-slice decision rides the plan
            # node (the `route` entry on ?profile=true); the evaluated
            # subexpressions themselves stay cached under the existing
            # generation-keyed plan-cache keys regardless of route, so a
            # query flipping between routes reuses one cache
            info["route"] = dict(route)
        if not self.enabled:
            return call, info
        from pilosa_tpu.executor import ExecutionError
        try:
            planned = self._plan_top(index, call, tuple(shards), info)
        except ExecutionError:
            raise  # intended clean errors (zero-operand Intersect)
        except Exception:  # noqa: BLE001 — planning must never break a
            # query: any estimation surprise degrades to written order
            return call, info
        with self._lock:
            self.plans += 1
            self.reorders += info["reorders"]
            self.short_circuits += info["shortCircuits"]
            if info["pushdown"]:
                self.pushdowns += 1
        return planned, info

    def snapshot(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled, "plans": self.plans,
                    "reorders": self.reorders, "pushdowns": self.pushdowns,
                    "shortCircuits": self.short_circuits}

    # ------------------------------------------------------- tree rewrite

    def _plan_top(self, index, call: Call, shards, info) -> Call:
        memo = {}  # per-plan existence-count memo
        if call.name in BITMAP_CALLS:
            new, _ = self._plan_bitmap(index, call, shards, info, memo)
            return new
        if call.name == "Count" and len(call.children) == 1:
            child, _ = self._plan_bitmap(index, call.children[0], shards,
                                         info, memo)
            if child.children or is_empty_call(child):
                # the count reduction runs fused on device (or is skipped
                # outright for a provably-empty operand) — no intermediate
                # row bitmap crosses to host
                info["pushdown"] = True
            if child is call.children[0]:
                return call
            return Call(call.name, call.args, [child], pos=call.pos)
        if call.name in ("TopN", "Sum", "Min", "Max") and call.children:
            child, _ = self._plan_bitmap(index, call.children[0], shards,
                                         info, memo)
            if call.name == "TopN" and (child.children
                                        or is_empty_call(child)):
                # src rows stay HBM-resident (row_leaves_dev); ranking
                # fetches int32 count vectors only
                info["pushdown"] = True
            if child is call.children[0]:
                return call
            return Call(call.name, call.args,
                        [child] + list(call.children[1:]), pos=call.pos)
        if call.name == "GroupBy":
            changed = False
            children = []
            for ch in call.children:
                if ch.name in BITMAP_CALLS:  # the positional filter
                    new, _ = self._plan_bitmap(index, ch, shards, info,
                                               memo)
                    changed |= new is not ch
                    children.append(new)
                else:
                    children.append(ch)
            args = call.args
            filt = args.get("filter")
            if isinstance(filt, Call) and filt.name in BITMAP_CALLS:
                new, _ = self._plan_bitmap(index, filt, shards, info, memo)
                if new is not filt:
                    args = dict(args)
                    args["filter"] = new
                    changed = True
            if not changed:
                return call
            return Call(call.name, args, children, pos=call.pos)
        return call

    def _plan_bitmap(self, index, c: Call, shards, info,
                     memo) -> tuple[Call, Estimate]:
        new, est = self._plan_bitmap_inner(index, c, shards, info, memo)
        self._note(info, new, est)
        return new, est

    def _plan_bitmap_inner(self, index, c: Call, shards, info,
                           memo) -> tuple[Call, Estimate]:
        if c.name == "Row":
            return c, self._row_estimate(index, c, shards)
        if c.name == "Range":
            return c, UNKNOWN
        if c.name == "Not":
            if len(c.children) != 1:
                return c, UNKNOWN
            child, ce = self._plan_bitmap(index, c.children[0], shards,
                                          info, memo)
            ex_count = self._existence_count(index, shards, memo)
            if ex_count is None:
                est = UNKNOWN
            elif _exact_zero(ce):
                # Not(empty) = existence, exactly
                est = Estimate(ex_count, True, ce.valid)
            elif ex_count == 0 and ce.valid:
                est = Estimate(0, True, True)  # no columns: Not is empty
            elif ce.count is not None:
                est = Estimate(max(ex_count - ce.count, 0), False, ce.valid)
            else:
                est = Estimate(ex_count, False, False)
            if child is c.children[0]:
                return c, est
            return Call("Not", c.args, [child], pos=c.pos), est
        if c.name == "Difference":
            if not c.children:
                raise empty_operand_error(c)
            pairs = [self._plan_bitmap(index, ch, shards, info, memo)
                     for ch in c.children]
            first_est = pairs[0][1]
            all_valid = all(e.valid for _, e in pairs)
            if _exact_zero(first_est) and all_valid:
                info["shortCircuits"] += 1
                return empty_call(c), ZERO
            kept = [pairs[0]]
            for p in pairs[1:]:
                if _exact_zero(p[1]):
                    info["shortCircuits"] += 1  # a &~ empty = a
                else:
                    kept.append(p)
            est = Estimate(first_est.count,
                           first_est.exact and len(kept) == 1, all_valid)
            children = [ch for ch, _ in kept]
            if (len(children) == len(c.children)
                    and all(a is b for a, b in zip(children, c.children))):
                return c, est
            return Call(c.name, c.args, children, pos=c.pos), est
        if c.name in COMMUTATIVE:
            if c.name == "Intersect" and not c.children:
                raise empty_operand_error(c)
            pairs = [self._plan_bitmap(index, ch, shards, info, memo)
                     for ch in c.children]
            all_valid = all(e.valid for _, e in pairs)
            if c.name == "Intersect":
                if all_valid and any(_exact_zero(e) for _, e in pairs):
                    info["shortCircuits"] += 1
                    return empty_call(c), ZERO
            else:  # Union / Xor: empty operands are identity elements
                kept = []
                for p in pairs:
                    if _exact_zero(p[1]):
                        info["shortCircuits"] += 1
                    else:
                        kept.append(p)
                if not kept:
                    return empty_call(c), ZERO
                pairs = kept
            # cheapest-first + deterministic text tiebreak: the reorder
            # that matters on dense kernels is CANONICAL ordering — every
            # permutation of the same operands shares one plan-cache key
            ordered = sorted(
                pairs, key=lambda p: (p[1].count if p[1].count is not None
                                      else float("inf"), p[0].to_pql()))
            if [p[0] for p in ordered] != [p[0] for p in pairs]:
                info["reorders"] += 1
            pairs = ordered
            info["order"] = [truncate_pql(ch.to_pql(), _EXPR_LIMIT)
                             for ch, _ in pairs]
            known = [e.count for _, e in pairs if e.count is not None]
            if c.name == "Intersect":
                count = min(known) if known else None
                exact = all_valid and any(_exact_zero(e) for _, e in pairs)
            else:
                count = sum(known) if known else None
                exact = (all(e.exact for _, e in pairs)
                         and all(e.count == 0 for _, e in pairs))
            est = Estimate(count, exact, all_valid)
            children = [ch for ch, _ in pairs]
            if (len(children) == len(c.children)
                    and all(a is b for a, b in zip(children, c.children))):
                return c, est
            return Call(c.name, c.args, children, pos=c.pos), est
        return c, UNKNOWN

    # -------------------------------------------------------- estimation

    def _row_estimate(self, index, c: Call, shards) -> Estimate:
        ex = self.executor
        try:
            field_name = c.field_arg()
            f = index.field(field_name)
            if f is None:
                return UNKNOWN  # executor raises "field not found"
            row_val = c.args[field_name]
            row_id = ex._translate_row(index, f, row_val, create=False)
            if row_id is None:
                return ZERO  # unknown key: provably empty, no id minted
            if f.options.type == FieldType.BOOL and isinstance(row_val,
                                                               bool):
                row_id = 1 if row_val else 0
            return Estimate(self._row_cardinality(
                index, field_name, VIEW_STANDARD, shards, row_id),
                True, True)
        except Exception:  # noqa: BLE001 — estimation is advisory
            return UNKNOWN

    def _row_cardinality(self, index, field_name: str, view_name: str,
                         shards, row_id: int) -> int:
        return self.executor.row_stats.get(
            index, field_name, view_name, shards, row_id).total_card

    def _existence_count(self, index, shards, memo) -> Optional[int]:
        if "ex" not in memo:
            from pilosa_tpu.constants import EXISTENCE_FIELD_NAME
            if index.existence_field() is None:
                memo["ex"] = None
            else:
                try:
                    memo["ex"] = self._row_cardinality(
                        index, EXISTENCE_FIELD_NAME, VIEW_STANDARD,
                        shards, 0)
                except Exception:  # noqa: BLE001
                    memo["ex"] = None
        return memo["ex"]

    @staticmethod
    def _note(info, call: Call, est: Estimate) -> None:
        if len(info["estimates"]) >= 48:
            return
        info["estimates"].append({
            "expr": truncate_pql(call.to_pql(), _EXPR_LIMIT),
            "est": est.count, "exact": est.exact})


# --------------------------------------------------------------- cache keys


class _Uncacheable(Exception):
    pass


def subtree_cache_key(executor, index, call: Call,
                      shards) -> Optional[tuple]:
    """Canonical plan-cache key of a bitmap subtree, or None when the
    subtree cannot be safely keyed (unparseable shape, a leaf kind without
    generation coverage). The key is (index, canonical PQL, shard tuple,
    per-leaf generation fingerprint) — generations are read fresh from the
    fragments on every lookup, so a write anywhere under the subtree
    produces a different key and invalidation costs nothing."""
    gens: list = []
    shards_t = tuple(shards)

    def leaf(field: str, view: str, row_id: int) -> None:
        gens.append(("r", field, view,
                     executor.row_stats.get(index, field, view, shards_t,
                                            row_id).gens))

    def walk(c: Call) -> None:
        if c.name == "Row":
            field_name = c.field_arg()
            f = index.field(field_name)
            if f is None:
                raise _Uncacheable
            row_val = c.args[field_name]
            row_id = executor._translate_row(index, f, row_val,
                                             create=False)
            if row_id is None:
                # unknown key: empty row today. Once a write mints the key
                # the translate above resolves and the key changes — the
                # stale entry is unreachable, exactly like a bumped gen.
                gens.append(("nokey", field_name))
                return
            if f.options.type == FieldType.BOOL and isinstance(row_val,
                                                               bool):
                row_id = 1 if row_val else 0
            leaf(field_name, VIEW_STANDARD, row_id)
            return
        if c.name == "Range":
            if "_start" in c.args or "_end" in c.args:
                field_name = c.field_arg()
                f = index.field(field_name)
                if f is None:
                    raise _Uncacheable
                row_id = executor._translate_row(index, f,
                                                 c.args[field_name],
                                                 create=False)
                if row_id is None:
                    gens.append(("nokey", field_name))
                    return
                start, end = c.args.get("_start"), c.args.get("_end")
                if not (isinstance(start, datetime)
                        and isinstance(end, datetime)):
                    raise _Uncacheable
                for v in timequantum.views_by_time_range(
                        VIEW_STANDARD, start, end, f.options.time_quantum):
                    leaf(field_name, v, row_id)
                return
            cond_field = cond = None
            for k, v in c.args.items():
                if isinstance(v, Condition):
                    cond_field, cond = k, v
            if cond is None:
                raise _Uncacheable
            f = index.field(cond_field)
            if f is None or f.options.type != FieldType.INT:
                raise _Uncacheable
            depth = f.bit_depth
            gens.append(("bsi", cond_field, depth, f.base, tuple(
                executor._leaf_gens(index, cond_field, f.bsi_view_name,
                                    shards_t, r)
                for r in range(depth + 1))))
            return
        if c.name == "Not":
            from pilosa_tpu.constants import EXISTENCE_FIELD_NAME
            if index.existence_field() is None:
                raise _Uncacheable
            leaf(EXISTENCE_FIELD_NAME, VIEW_STANDARD, 0)
            for ch in c.children:
                walk(ch)
            return
        if c.name in ("Union", "Intersect", "Difference", "Xor"):
            for ch in c.children:
                walk(ch)
            return
        raise _Uncacheable

    try:
        walk(call)
    except Exception:  # noqa: BLE001 — uncacheable shapes just miss
        return None
    return (index.name, call.to_pql(), shards_t, tuple(gens))


def record_cache_event(call: Call, hit: bool) -> None:
    """Append a cache hit/miss event to the executing call's plan node
    (?profile=true `plan.cache`); nop when no plan is being recorded."""
    plan = current_plan.get()
    if plan is None:
        return
    events = plan.get("cache")
    if events is not None and len(events) < 48:
        events.append({"expr": truncate_pql(call.to_pql(), _EXPR_LIMIT),
                       "hit": hit})


# ------------------------------------------------- hybrid representation

def choose_representation(executor, index, call: Optional[Call],
                          field_name: str, view_name: str, shards,
                          row_id: int, peek: bool = False,
                          stats_out: Optional[dict] = None
                          ) -> tuple[str, int, tuple]:
    """The planner's per-operand container decision (the hybrid
    sparse/dense tentpole): from the same exact write-maintained
    cardinalities the reorder pass reads (storage/fragment.py
    row_cardinality, via the row_counts cache — dict probes, not
    container walks), pick the device representation for one row leaf
    and record it on the executing plan node, so ?profile=true and
    /debug/query-history show WHY a leaf uploaded as a 512-byte index
    array instead of a 128 KiB plane (the `route`-node discipline of the
    ICI router applied to representation).

    Returns (rep, padded slots, per-shard generations) — the generations
    ride along because both the decision and the residency key need them.
    What is read of the row comes from the executor's RowStatsMemo, a
    walk over the shards once per write version of the view; the choice
    itself is made anew every call, and its hysteresis/heat state lives
    in the executor's HybridManager (both parallel/residency.py).

    `peek=True` is the EXPLAIN mode: the exact same decision WITHOUT
    advancing the hysteresis memory (HybridManager.choose peek), so
    explain-then-execute reports and then uses the same representation.
    `stats_out`, when given, receives the sizing statistics the decision
    read (maxShardCardinality, runIntervals) for the explain tree."""
    stats = executor.row_stats.get(index, field_name, view_name, shards,
                                   row_id)
    gens = stats.gens
    hyb = getattr(executor, "hybrid", None)
    if hyb is None or not hyb.active():
        if stats_out is not None:
            stats_out.update(maxShardCardinality=None, runIntervals=None)
        return "dense", 0, gens
    max_card = stats.max_card
    run_stats = None
    if max_card > hyb.threshold and hyb.run_threshold > 0:
        # above the sparse band: the run-vs-dense decision needs the
        # write-maintained interval statistics (storage/fragment.py
        # row_run_stats), the most over the shards: the padded run leaf
        # must cover the interval-richest shard. Read on the memo's
        # first ask for this row, kept with its entry after.
        run_stats = executor.row_stats.run_stats(
            index, field_name, view_name, shards, row_id)
    rep, slots = hyb.choose(
        (index.name, field_name, view_name, row_id), max_card,
        frag_keys=stats.frag_keys, run_stats=run_stats, peek=peek)
    if stats_out is not None:
        stats_out.update(
            maxShardCardinality=int(max_card),
            runIntervals=int(run_stats[0]) if run_stats else 0)
    plan = current_plan.get()
    if plan is not None and call is not None:
        reps = plan.setdefault("hybrid", [])
        if len(reps) < 48:
            reps.append({"expr": truncate_pql(call.to_pql(), _EXPR_LIMIT),
                         "rep": rep, "maxShardCardinality": int(max_card),
                         "slots": slots,
                         "runIntervals":
                             int(run_stats[0]) if run_stats else 0})
    return rep, slots, gens


# --------------------------------------------------------- calibration ring


class CalibrationRing:
    """Est-vs-actual cost-model calibration (`planner.calibration`).

    Every executed PROFILED query feeds one entry per planned call
    (api.query_results): the planner's cardinality estimate for the call
    next to the count the execution actually returned, plus the query's
    real host->device bytes. EXPLAIN predicts from the same estimates,
    so drift visible here is drift in everything the planner decides —
    operand order, short circuits, representation sizing — surfaced
    BEFORE it misplans badly enough to show up as latency. Snapshot
    rides /debug/vars `planner.calibration`; the aggregate mean absolute
    relative error is the one number to watch (docs/operations.md
    "Device observability" → calibration tuning)."""

    def __init__(self, size: int = 256):
        import collections
        self._lock = threading.Lock()
        self._buf: "collections.deque[dict]" = collections.deque(
            maxlen=max(1, int(size)))
        self.recorded = 0
        self.compared = 0          # entries where est AND actual exist
        self.abs_rel_err_sum = 0.0
        self.max_abs_rel_err = 0.0

    def record(self, entry: dict) -> None:
        est, actual = entry.get("est"), entry.get("actual")
        if est is not None and actual is not None:
            # relative error against the actual (floor 1 so exact-zero
            # actuals don't divide out): >0 = overestimate
            err = (float(est) - float(actual)) / max(float(actual), 1.0)
            entry = dict(entry, relErr=round(err, 4))
        with self._lock:
            self._buf.append(entry)
            self.recorded += 1
            if "relErr" in entry:
                self.compared += 1
                a = abs(entry["relErr"])
                self.abs_rel_err_sum += a
                self.max_abs_rel_err = max(self.max_abs_rel_err, a)

    def snapshot(self, limit: int = 32) -> dict:
        with self._lock:
            # limit=0 is summary-only (the EXPLAIN response rides the
            # aggregates; /debug/vars carries the recent entries)
            entries = list(self._buf)[-int(limit):] if limit > 0 else []
            return {
                "size": self._buf.maxlen,
                "recorded": self.recorded,
                "compared": self.compared,
                "meanAbsRelErr": round(
                    self.abs_rel_err_sum / self.compared, 4)
                if self.compared else None,
                "maxAbsRelErr": round(self.max_abs_rel_err, 4)
                if self.compared else None,
                "entries": list(reversed(entries)),
            }

    def reset(self) -> None:
        with self._lock:
            self._buf.clear()
            self.recorded = 0
            self.compared = 0
            self.abs_rel_err_sum = 0.0
            self.max_abs_rel_err = 0.0


# process-global, like executor counters: one ring per process — remote
# sub-requests calibrate on their own nodes
calibration = CalibrationRing()


def record_calibration(prof, calls, results) -> None:
    """Feed the calibration ring from one executed profiled query:
    pairs each plan node the profiler captured (prof.plans, appended in
    call order for planned calls only) with the call's actual result.
    Scalar results (Count / pushdown counts) calibrate the cardinality
    estimate directly; other result shapes record the estimate alone so
    the ring still shows what the planner believed. Never raises — the
    feed rides api.query_results' finally block."""
    try:
        plans = list(prof.plans)
        if not plans:
            return
        planned = [(c, r) for c, r in zip(calls, results)
                   if c.name in PLANNED_CALLS]
        h2d = int(prof.h2d_bytes)
        for plan, (call, result) in zip(plans, planned):
            ests = plan.get("estimates") or []
            est = ests[0].get("est") if ests else None
            actual = None
            if isinstance(result, bool):
                actual = None
            elif isinstance(result, (int, float)):
                actual = int(result)
            calibration.record({
                "ts": round(time.time(), 3),  # wall-clock: export ts
                "call": plan.get("call"),
                "expr": ests[0].get("expr") if ests else None,
                "exact": ests[0].get("exact") if ests else None,
                "est": est,
                "actual": actual,
                "h2dBytes": h2d,
                "elapsedMs": prof.elapsed_ms or None,
            })
            h2d = 0  # query-level bytes ride the first entry only
    except Exception:  # noqa: BLE001 — calibration must never break a
        pass  # query's response path
