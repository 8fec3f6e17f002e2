"""Multi-tenant QoS plane: quotas, priority-aware admission, load shedding.

At "millions of users" scale the cluster dies by overload, not by bugs.
Every signal needed to act was already sampled — fan-out pool occupancy,
batcher queue depth/wait (utils/telemetry.py), per-principal spend
(utils/accounting.py UsageLedger), the shared health_score — but nothing
acted on any of it. This module closes the loop from observed load to
enforced policy. Four cooperating pieces:

* **Per-principal quotas** — token buckets (queries/s, device-ms/s,
  RPC+h2d bytes/s) whose device/byte consumption is *refilled against the
  UsageLedger aggregates*: admission withdraws the principal's measured
  spend since its last request, so the quota charges what the hardware
  actually did (batch-smeared and all), not an up-front estimate. A
  principal in debt gets `429 + Retry-After` until the bucket drains back
  above zero. Configured by a `[qos]` section: defaults plus per-principal
  overrides.

* **Priority classes** — `interactive` > `batch` > `internal` — carried on
  the `X-Pilosa-Priority` header and the per-entry coalescer envelope
  field (exactly like `traceId` / `principal`), installed on a contextvar.
  Respected as *ordering*: ContinuousBatcher cuts (when the queue exceeds
  one batch, higher priority rides the next dispatch), NodeCoalescer
  envelope assembly (same mechanism, inherited), and fan-out pool
  submission (PriorityPool below). An abusive batch tenant therefore
  queues BEHIND interactive traffic instead of ahead of it.

* **Deadline-aware admission + load shedding** — each query carries a
  deadline budget (client header / `?timeout=` / the `[qos]`
  default-deadline). The admission controller rejects EARLY with
  `503 + Retry-After` when the estimated wait (batcher queue-wait EWMA +
  per-class device-cost EWMA scaled by fan-out occupancy) already exceeds
  the remaining budget, or when the shared health_score is red — a doomed
  query never reaches the device. Remotes inherit the shrinking deadline
  through the envelope, and an entry that arrives expired is shed
  remotely before any device dispatch.

* **Observability ride-along** — `qos/*` counters (admitted / shed /
  throttled per priority, principal and shed-reason) on /debug/vars,
  unconditional Prometheus families on /metrics, `qos.*` telemetry ring
  gauges, a `qos` node on profiled queries, and a dashboard panel.

Modes (`[qos] mode`): `off` (default — zero behavior change), `observe`
(every would-shed/would-throttle decision is counted and logged, nothing
rejected: the safe rollout step), `enforce`. `PILOSA_TPU_QOS=0` is the
env kill switch over everything including the priority plumbing.

Disabled cost: one env check (+ one ContextVar.get on priority sites).
"""

from __future__ import annotations

import contextvars
import itertools
import math
import os
import threading
import time
from concurrent.futures import Future
from typing import Optional

from pilosa_tpu.utils import threads as _threads

PRIORITY_HEADER = "X-Pilosa-Priority"

# priority name -> level; LOWER level = more urgent (sort order and
# PriorityQueue order agree). `internal` is scrub/anti-entropy/background.
PRIORITIES = {"interactive": 0, "batch": 1, "internal": 2}
# untagged work (background threads, direct api calls) sorts as internal:
# it must never queue ahead of tagged user traffic
DEFAULT_LEVEL = PRIORITIES["internal"]

MODES = ("off", "observe", "enforce")

# shed-reason glossary (docs/operations.md): every rejection counts under
# exactly one of these, and the Prometheus families emit all of them
# unconditionally so a scrape never sees a missing series
SHED_REASONS = ("deadline", "estimatedWait", "estimatedCost", "healthRed",
                "deadlineRemote", "draining")
THROTTLE_REASONS = ("queriesPerS", "deviceMsPerS", "bytesPerS")

# Retry-After ceiling: backpressure is a hint, not a ban — a throttled
# principal re-probes within this bound even when its debt says longer
RETRY_AFTER_MAX_S = 30.0


def enabled() -> bool:
    """PILOSA_TPU_QOS=0 kills the whole plane — admission, priority
    plumbing, priority pools (read per call: runtime toggle)."""
    return os.environ.get("PILOSA_TPU_QOS", "1") != "0"


# the priority class of the request being served, or None (= untagged).
# Fan-out pool submits run in copied contexts (the qctx/profile/accounting
# discipline), so every thread serving a request sees its priority.
current_priority: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("pilosa_qos_priority", default=None)


def priority_level(name: Optional[str]) -> int:
    """Sort level of a priority name; unknown/None -> internal."""
    return PRIORITIES.get(name, DEFAULT_LEVEL) if name else DEFAULT_LEVEL


def current_level() -> int:
    """The current request's priority level (the batcher/pool sort key).
    One env check + one ContextVar.get — the nop fast path."""
    if not enabled():
        return DEFAULT_LEVEL
    return priority_level(current_priority.get())


# ---------------------------------------------------------------------------
# Token bucket
# ---------------------------------------------------------------------------


class TokenBucket:
    """Rate-limit bucket that tolerates debt.

    Admission-time charges (`take(1)` per query) and ledger-feedback
    charges (the principal's measured device-ms/bytes since its last
    request) both withdraw; balance refills at `rate`/s up to `burst`.
    Because ledger feedback charges AFTER the work ran, the balance can go
    negative — that debt is exactly the backpressure signal: `wait_for(n)`
    says how long until `n` tokens are available again."""

    __slots__ = ("rate", "burst", "tokens", "_t")

    def __init__(self, rate: float, burst: float):
        self.rate = float(rate)
        self.burst = max(float(burst), self.rate)
        self.tokens = self.burst
        self._t = time.monotonic()

    def _refill(self, now: float) -> None:
        dt = now - self._t
        if dt > 0:
            self.tokens = min(self.burst, self.tokens + dt * self.rate)
            self._t = now

    def take(self, n: float, now: Optional[float] = None) -> None:
        """Withdraw unconditionally (may go into debt)."""
        self._refill(time.monotonic() if now is None else now)
        self.tokens -= n

    def wait_for(self, n: float = 0.0,
                 now: Optional[float] = None) -> float:
        """Seconds until the balance reaches `n` (0 when already there)."""
        self._refill(time.monotonic() if now is None else now)
        deficit = n - self.tokens
        if deficit <= 0:
            return 0.0
        return deficit / self.rate if self.rate > 0 else RETRY_AFTER_MAX_S


# ---------------------------------------------------------------------------
# Priority-aware thread pool (fan-out submission ordering)
# ---------------------------------------------------------------------------

_SHUTDOWN_LEVEL = 1 << 30


class PriorityPool:
    """ThreadPoolExecutor lookalike whose work queue is priority-ordered.

    `submit()` reads the caller's priority class off the contextvar at
    submit time (the submitting thread is the request thread — pool
    workers run copied contexts), so under a saturated pool an abusive
    batch tenant's fan-out RPCs queue behind interactive traffic. FIFO
    within a class (a monotone sequence number breaks ties), so with one
    class the behavior is exactly the executor it replaces. Exposes
    `_max_workers` / `_threads` / `_work_queue` so
    Executor.fanout_pool_stats reads it unchanged."""

    def __init__(self, max_workers: int, thread_name_prefix: str = "qos"):
        import queue as _queue
        self._max_workers = max(1, int(max_workers))
        self._prefix = thread_name_prefix
        self._work_queue: "_queue.PriorityQueue" = _queue.PriorityQueue()
        self._threads: list[threading.Thread] = []
        self._lock = threading.Lock()
        self._seq = itertools.count()
        self._shutdown = False

    def submit(self, fn, /, *args, **kwargs) -> Future:
        fut: Future = Future()
        with self._lock:
            if self._shutdown:
                raise RuntimeError("cannot schedule new futures after "
                                   "shutdown")
            self._work_queue.put((current_level(), next(self._seq),
                                  fut, fn, args, kwargs))
            # grow like ThreadPoolExecutor: one worker per submit until
            # the cap; idle workers park on the queue forever after
            if len(self._threads) < self._max_workers:
                # NOTE: worker threads deliberately copy the POOL's boot
                # context, not the submitter's — per-task context rides
                # each submit (utils.threads.submit_ctx / the explicit
                # copy_context().run form, enforced by pilosa-lint)
                self._threads.append(_threads.spawn(
                    self._worker,
                    name=f"{self._prefix}_{len(self._threads)}"))
        return fut

    def _worker(self) -> None:
        while True:
            level, _seq, fut, fn, args, kwargs = self._work_queue.get()
            if level >= _SHUTDOWN_LEVEL:
                # re-post so every sibling worker sees the sentinel
                self._work_queue.put((level, _seq, None, None, (), {}))
                return
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn(*args, **kwargs))
            except BaseException as e:  # noqa: BLE001 — deliver to waiter
                fut.set_exception(e)

    def shutdown(self, wait: bool = True,
                 cancel_futures: bool = False) -> None:
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
            if cancel_futures:
                import queue as _queue
                while True:
                    try:
                        item = self._work_queue.get_nowait()
                    except _queue.Empty:
                        break
                    if item[0] < _SHUTDOWN_LEVEL and item[2] is not None:
                        item[2].cancel()
            self._work_queue.put((_SHUTDOWN_LEVEL, next(self._seq),
                                  None, None, (), {}))
            threads = list(self._threads)
        if wait:
            for t in threads:
                t.join(timeout=5.0)


# ---------------------------------------------------------------------------
# Admission controller
# ---------------------------------------------------------------------------


class Rejection:
    """One admission verdict that ends in a rejection: maps to
    `429 + Retry-After` (quota) or `503 + Retry-After` (shed)."""

    __slots__ = ("status", "retry_after", "reason", "message")

    def __init__(self, status: int, retry_after: float, reason: str,
                 message: str):
        self.status = status
        self.retry_after = max(0.0, min(retry_after, RETRY_AFTER_MAX_S))
        self.reason = reason
        self.message = message


class _PrincipalState:
    __slots__ = ("qps", "device", "bytes", "prev_device_ms", "prev_bytes",
                 "last_seen")

    def __init__(self, limits: dict, burst_s: float):
        self.qps = (TokenBucket(limits["queries_per_s"],
                                limits["queries_per_s"] * burst_s)
                    if limits["queries_per_s"] > 0 else None)
        self.device = (TokenBucket(limits["device_ms_per_s"],
                                   limits["device_ms_per_s"] * burst_s)
                       if limits["device_ms_per_s"] > 0 else None)
        self.bytes = (TokenBucket(limits["bytes_per_s"],
                                  limits["bytes_per_s"] * burst_s)
                      if limits["bytes_per_s"] > 0 else None)
        self.prev_device_ms = 0.0
        self.prev_bytes = 0.0
        self.last_seen = time.monotonic()


_LIMIT_KEYS = ("queries_per_s", "device_ms_per_s", "bytes_per_s")


class QosPlane:
    """The per-node QoS control plane: admission verdicts + counters.

    One instance per Server, wired to the executor (load signals), the
    UsageLedger (quota feedback) and the node health function. All public
    entry points are cheap and lock-bounded — admit() runs on the HTTP
    dispatch hot path before parse."""

    # load-signal refresh floor: admission reads batcher/pool counters at
    # most this often, so a request burst costs dict lookups, not N
    # snapshot walks
    SIGNAL_REFRESH_S = 0.25
    # health cache TTL: health_fn walks telemetry state; a red node sheds
    # for at least this long between re-checks
    HEALTH_TTL_S = 1.0
    # EWMA smoothing for queue-wait / per-class service cost
    EWMA_ALPHA = 0.3
    # shed-storm detection (flight-recorder events, utils/events.py):
    # STORM_N rejections inside STORM_WINDOW_S is the onset; a window
    # with no rejections ends it. One event per edge, never per shed.
    STORM_WINDOW_S = 5.0
    STORM_N = 20
    # deep quota debt: a 429 whose Retry-After reaches this marks the
    # principal as in debt (rate-limited to one event per principal per
    # DEBT_EMIT_INTERVAL_S so an abusive tenant can't storm the journal)
    QUOTA_DEBT_S = 5.0
    DEBT_EMIT_INTERVAL_S = 60.0

    def __init__(self, mode: str = "off",
                 default_priority: str = "interactive",
                 default_deadline: float = 0.0,
                 queries_per_s: float = 0.0,
                 device_ms_per_s: float = 0.0,
                 bytes_per_s: float = 0.0,
                 burst_s: float = 2.0,
                 max_principals: int = 256,
                 principals: Optional[dict] = None,
                 executor=None, ledger=None, health_fn=None, logger=None):
        if mode not in MODES:
            raise ValueError(
                f"invalid [qos] mode {mode!r} (expected off | observe | "
                "enforce)")
        if default_priority not in PRIORITIES:
            raise ValueError(
                f"invalid [qos] default-priority {default_priority!r} "
                f"(expected one of {', '.join(PRIORITIES)})")
        if burst_s <= 0:
            raise ValueError("[qos] burst must be > 0 (seconds of rate)")
        self.mode = mode
        self.default_priority = default_priority
        self.default_deadline = max(0.0, float(default_deadline))
        self.burst_s = float(burst_s)
        self.max_principals = max(2, int(max_principals))
        self.defaults = {"queries_per_s": float(queries_per_s),
                         "device_ms_per_s": float(device_ms_per_s),
                         "bytes_per_s": float(bytes_per_s)}
        # per-principal overrides: {principal: {queries_per_s?, ...,
        # priority?}} — TOML keys arrive hyphenated, normalize once
        self.overrides: dict[str, dict] = {}
        for pname, over in (principals or {}).items():
            norm = {str(k).replace("-", "_"): v
                    for k, v in dict(over).items()}
            bad = set(norm) - set(_LIMIT_KEYS) - {"priority"}
            if bad:
                raise ValueError(
                    f"invalid [qos.principals.{pname!r}] key(s): "
                    f"{', '.join(sorted(bad))}")
            pr = norm.get("priority")
            if pr is not None and pr not in PRIORITIES:
                raise ValueError(
                    f"invalid [qos.principals.{pname!r}] priority {pr!r}")
            self.overrides[str(pname)] = norm
        self.executor = executor
        self.ledger = ledger
        self.health_fn = health_fn
        self.logger = logger
        # flight-recorder journal (utils/events.py, set by Server):
        # shed-storm onset/end + deep quota debt become timeline events
        self.journal = None
        import collections as _collections
        self._storm_times: "_collections.deque" = _collections.deque()
        self.storm_active = False
        self._storm_started = 0.0
        self._storm_total = 0
        self.storms = 0
        self._debt_last_emit: dict[str, float] = {}
        self._lock = threading.Lock()
        self._principals: dict[str, _PrincipalState] = {}
        # counters — every surface iterates these dicts, and /metrics
        # emits the full reason/priority key space unconditionally
        self.admitted = dict.fromkeys(PRIORITIES, 0)
        self.shed = dict.fromkeys(SHED_REASONS, 0)
        self.throttled = dict.fromkeys(THROTTLE_REASONS, 0)
        self.would_shed = dict.fromkeys(SHED_REASONS, 0)
        self.would_throttled = dict.fromkeys(THROTTLE_REASONS, 0)
        self._per_principal: dict[str, dict] = {}  # bounded: see _pp
        # load-signal state (estimated_wait_ms)
        self._sig_t = 0.0
        self._sig_prev: tuple = (0.0, 0)  # cumulative (wait_ms, waited)
        self.wait_ewma_ms = 0.0
        self.queue_pressure = 0.0  # (batcher depth + fanout queued)/slots
        # per-class device-cost EWMA (the planner-cost proxy admission can
        # afford pre-parse; post-parse the class-resolved value is used)
        self._class_cost_ms: dict[str, float] = {}
        self._health: tuple[float, str] = (0.0, "green")

    # -- priority resolution ------------------------------------------------

    def priority_for(self, header_value: Optional[str],
                     principal: Optional[str]) -> str:
        """Request priority: a valid header wins; else the principal's
        [qos.principals] override; else the [qos] default class. An
        unknown header value falls through (never an error — a typo'd
        client must not 400 its own traffic)."""
        if header_value:
            hv = header_value.strip().lower()
            if hv in PRIORITIES:
                return hv
        if principal:
            over = self.overrides.get(principal)
            if over and over.get("priority"):
                return over["priority"]
        return self.default_priority

    # -- quota state --------------------------------------------------------

    def _limits_for(self, principal: str) -> dict:
        over = self.overrides.get(principal)
        if not over:
            return self.defaults
        return {k: float(over.get(k, self.defaults[k]))
                for k in _LIMIT_KEYS}

    def _state_locked(self, principal: str) -> _PrincipalState:
        st = self._principals.get(principal)
        if st is None:
            if len(self._principals) >= self.max_principals:
                # evict the longest-idle bucket set: quota state is
                # reconstructible (the ledger keeps the history), so a
                # bounded table just restarts an evictee at full burst
                victim = min(self._principals,
                             key=lambda k: self._principals[k].last_seen)
                del self._principals[victim]
            st = self._principals[principal] = _PrincipalState(
                self._limits_for(principal), self.burst_s)
            if self.ledger is not None:
                cur = self.ledger.peek(principal)
                if cur is not None:
                    # don't charge history from before this plane existed
                    st.prev_device_ms = cur["deviceMs"]
                    st.prev_bytes = cur["rpcBytes"] + cur["hbmBytes"]
        st.last_seen = time.monotonic()
        return st

    # -- load signals -------------------------------------------------------

    def _refresh_signals(self, now: float) -> None:
        """Update the queue-wait EWMA and queue-pressure ratio from the
        executor's cumulative counters (rate-limited; dict reads only)."""
        if now - self._sig_t < self.SIGNAL_REFRESH_S:
            return
        self._sig_t = now
        ex = self.executor
        if ex is None:
            return
        wait_total, waited, depth = 0.0, 0, 0
        for attr in ("batcher", "sum_batcher", "minmax_batcher"):
            b = getattr(ex, attr, None)
            if b is None:
                continue
            wait_total += b.wait_ms_total
            waited += b.waited
            depth += b.queue_depth()
        pw, pn = self._sig_prev
        dn = waited - pn
        if dn > 0:
            avg = max(0.0, wait_total - pw) / dn
            self.wait_ewma_ms += self.EWMA_ALPHA * (avg - self.wait_ewma_ms)
        self._sig_prev = (wait_total, waited)
        try:
            ps = ex.fanout_pool_stats()
            queued = ps["queued"]
            slots = max(1, ps["size"])
        except Exception:  # noqa: BLE001 — signals must never fail admit
            queued, slots = 0, 1
        self.queue_pressure = (depth + queued) / slots

    def observe_service(self, qclass: str, elapsed_ms: float) -> None:
        """Completed-query cost observation (called where the SLO tracker
        observes): feeds the per-class cost EWMA the shed estimate uses."""
        cur = self._class_cost_ms.get(qclass)
        self._class_cost_ms[qclass] = (
            elapsed_ms if cur is None
            else cur + self.EWMA_ALPHA * (elapsed_ms - cur))

    def class_cost_ms(self, qclass: str) -> float:
        return self._class_cost_ms.get(qclass, 0.0)

    def estimated_wait_ms(self) -> float:
        """Pre-parse wait estimate: recent batcher queue-wait EWMA scaled
        by current queue pressure, plus the worst per-class device-cost
        EWMA weighted by fan-out backlog. Idle node -> ~0 (admit all)."""
        base = self.wait_ewma_ms * (1.0 + self.queue_pressure)
        if self.queue_pressure > 1.0 and self._class_cost_ms:
            base += (self.queue_pressure - 1.0) * max(
                self._class_cost_ms.values())
        return base

    def _health_score(self, now: float) -> str:
        t, score = self._health
        if now - t > self.HEALTH_TTL_S and self.health_fn is not None:
            try:
                score = self.health_fn()["score"]
            except Exception:  # noqa: BLE001 — a health-input failure
                score = "green"  # must not start shedding traffic
            self._health = (now, score)
        return score

    # -- bookkeeping --------------------------------------------------------

    def _pp(self, principal: str) -> dict:
        e = self._per_principal.get(principal)
        if e is None:
            # bound includes the spill bucket: the table never exceeds
            # max_principals entries total (the ledger's discipline)
            if len(self._per_principal) >= self.max_principals - 1 \
                    and "~other" != principal:
                principal = "~other"
                e = self._per_principal.get(principal)
            if e is None:
                e = self._per_principal[principal] = {
                    "admitted": 0, "shed": 0, "throttled": 0}
        return e

    def record_expired(self, remote: bool) -> None:
        """A query found its deadline already expired at the execution
        boundary (before any device dispatch). Remote entries count
        separately — they prove the envelope's shrinking-deadline
        inheritance is doing its job."""
        with self._lock:
            self.shed["deadlineRemote" if remote else "deadline"] += 1

    def record_cost_shed(self) -> None:
        with self._lock:
            self.shed["estimatedCost"] += 1

    def record_drain_shed(self) -> None:
        """A new external query arrived on a draining node and was shed
        with `503 + X-Pilosa-Shed-Reason: draining` (server.drain). NOT
        gated on [qos] mode — drain shedding is a lifecycle decision, not
        an overload policy; this just rides the same counter families."""
        now = time.monotonic()
        with self._lock:
            self.shed["draining"] += 1
            storm_started = self._note_rejection(now, "draining")
        self._storm_debt_events(storm_started, False, "", "draining", 0.0)

    def _journal_emit(self, etype: str, **fields) -> None:
        if self.journal is not None:
            try:
                self.journal.emit(etype, **fields)
            except Exception:  # noqa: BLE001 — recording must never
                pass  # break the admission hot path it observes

    def _note_rejection(self, now: float, reason: str) -> bool:
        """Track one rejection toward storm onset (call under _lock);
        True when THIS rejection crossed the storm threshold."""
        dq = self._storm_times
        dq.append(now)
        while dq and now - dq[0] > self.STORM_WINDOW_S:
            dq.popleft()
        if self.storm_active:
            self._storm_total += 1
            return False
        if len(dq) >= self.STORM_N:
            self.storm_active = True
            self.storms += 1
            self._storm_started = now
            self._storm_total = len(dq)
            return True
        return False

    def _note_calm(self, now: float) -> Optional[dict]:
        """Storm-end check (call under _lock) — a full window without a
        rejection ends the storm; returns the end-event fields once."""
        if self.storm_active and (
                not self._storm_times
                or now - self._storm_times[-1] > self.STORM_WINDOW_S):
            self.storm_active = False
            return {"rejections": self._storm_total,
                    "durationSeconds": round(
                        now - self._storm_started, 3)}
        return None

    def _reject(self, principal: str, priority: str, status: int,
                retry_after: float, reason: str,
                message: str) -> Optional[Rejection]:
        """Count (and in observe mode, swallow) one rejection verdict."""
        kind = "throttled" if status == 429 else "shed"
        now = time.monotonic()
        storm_started = False
        debt = False
        observed = False
        with self._lock:
            # storm tracking counts observe-mode would-rejections too: a
            # dry-run storm is exactly what observe mode exists to show
            storm_started = self._note_rejection(now, reason)
            if status == 429 and retry_after >= self.QUOTA_DEBT_S:
                last = self._debt_last_emit.get(principal, 0.0)
                if now - last >= self.DEBT_EMIT_INTERVAL_S:
                    self._debt_last_emit[principal] = now
                    debt = True
            if self.mode == "observe":
                (self.would_throttled if status == 429
                 else self.would_shed)[reason] += 1
                observed = True
            else:
                (self.throttled if status == 429
                 else self.shed)[reason] += 1
                self._pp(principal)[kind] += 1
        # journal/log emission OUTSIDE the plane lock (the spool write
        # and log line must never serialize the admission hot path)
        self._storm_debt_events(storm_started, debt, principal, reason,
                                retry_after)
        if observed:
            if self.logger is not None:
                self.logger.printf(
                    "qos: observe: would %s %s (priority=%s): %s",
                    "throttle" if status == 429 else "shed",
                    principal, priority, message)
            return None
        return Rejection(status, retry_after, reason, message)

    def _storm_debt_events(self, storm_started: bool, debt: bool,
                           principal: str, reason: str,
                           retry_after: float) -> None:
        if storm_started:
            self._journal_emit("qos.shed_storm.start", reason=reason,
                             mode=self.mode,
                             windowSeconds=self.STORM_WINDOW_S,
                             threshold=self.STORM_N)
        if debt:
            self._journal_emit("qos.quota_debt", principal=principal,
                             reason=reason,
                             retryAfterSeconds=round(retry_after, 3))

    # -- the admission check (HTTP dispatch hot path) -----------------------

    def admit(self, principal: str, priority: str,
              remaining: Optional[float]) -> Optional[Rejection]:
        """One query's admission verdict: None = admitted, else a
        Rejection the HTTP layer turns into 429/503 + Retry-After.
        Called BEFORE parse; `remaining` is the deadline budget in
        seconds (None = no deadline -> no wait-based shedding)."""
        if self.mode == "off":
            return None
        now = time.monotonic()

        # 1. health: a red node rejects early instead of timing out late
        if self._health_score(now) == "red":
            rej = self._reject(
                principal, priority, 503, self.HEALTH_TTL_S, "healthRed",
                "node health is red; shedding load")
            if rej is not None:
                return rej

        # 2. deadline-aware shedding
        if remaining is not None:
            if remaining <= 0:
                rej = self._reject(principal, priority, 503, 0.0,
                                   "deadline", "deadline already expired")
                if rej is not None:
                    return rej
            else:
                self._refresh_signals(now)
                est = self.estimated_wait_ms()
                if est > remaining * 1e3:
                    rej = self._reject(
                        principal, priority, 503, est / 1e3,
                        "estimatedWait",
                        f"estimated queue wait {est:.0f} ms exceeds "
                        f"remaining deadline {remaining * 1e3:.0f} ms")
                    if rej is not None:
                        return rej

        # 3. per-principal quotas (token buckets; device/bytes refilled
        # against the ledger's measured spend)
        limits = self._limits_for(principal)
        if any(limits[k] > 0 for k in _LIMIT_KEYS):
            with self._lock:
                st = self._state_locked(principal)
                if self.ledger is not None and (st.device is not None
                                                or st.bytes is not None):
                    cur = self.ledger.peek(principal)
                    if cur is not None:
                        dms = cur["deviceMs"]
                        dby = cur["rpcBytes"] + cur["hbmBytes"]
                        if st.device is not None:
                            st.device.take(
                                max(0.0, dms - st.prev_device_ms), now)
                        if st.bytes is not None:
                            st.bytes.take(
                                max(0.0, dby - st.prev_bytes), now)
                        st.prev_device_ms = dms
                        st.prev_bytes = dby
                verdict = None
                for bucket, need, reason, what in (
                        (st.qps, 1.0, "queriesPerS", "query rate"),
                        (st.device, 0.0, "deviceMsPerS", "device-ms"),
                        (st.bytes, 0.0, "bytesPerS", "byte")):
                    if bucket is None:
                        continue
                    wait = bucket.wait_for(need, now)
                    if wait > 0:
                        verdict = (reason, wait, what)
                        break
                if verdict is None and st.qps is not None:
                    st.qps.take(1.0, now)
            if verdict is not None:
                reason, wait, what = verdict
                rej = self._reject(
                    principal, priority, 429, wait, reason,
                    f"{what} quota exhausted for {principal}")
                if rej is not None:
                    return rej

        with self._lock:
            self.admitted[priority] = self.admitted.get(priority, 0) + 1
            self._pp(principal)["admitted"] += 1
            calm = self._note_calm(now)
        if calm is not None:
            self._journal_emit("qos.shed_storm.end", **calm)
        return None

    # -- surfaces -----------------------------------------------------------

    def snapshot(self) -> dict:
        """The /debug/vars `qos` block."""
        with self._lock:
            return {
                "mode": self.mode,
                "admitted": dict(self.admitted),
                "shed": dict(self.shed),
                "throttled": dict(self.throttled),
                "wouldShed": dict(self.would_shed),
                "wouldThrottled": dict(self.would_throttled),
                "perPrincipal": {k: dict(v) for k, v in
                                 sorted(self._per_principal.items(),
                                        key=lambda kv:
                                        -sum(kv[1].values()))[:20]},
                "estimatedWaitMs": round(self.estimated_wait_ms(), 3),
                "queuePressure": round(self.queue_pressure, 3),
                "trackedPrincipals": len(self._principals),
                "defaultPriority": self.default_priority,
                "defaultDeadline": self.default_deadline,
                "shedStormActive": self.storm_active,
                "shedStorms": self.storms,
            }

    def totals(self) -> dict:
        """Flat totals for telemetry rate derivation."""
        with self._lock:
            return {
                "admitted": sum(self.admitted.values()),
                "shed": sum(self.shed.values()),
                "throttled": sum(self.throttled.values()),
                "wouldShed": (sum(self.would_shed.values())
                              + sum(self.would_throttled.values())),
            }

    def metrics_series(self) -> tuple[dict, dict]:
        """(counts, gauges) merged into /metrics — the full priority /
        reason key space emitted unconditionally (zeros included) so
        scrapes never see a missing series."""
        with self._lock:
            counts = {}
            for p in PRIORITIES:
                counts[f"qos/admitted,priority:{p}"] = self.admitted.get(
                    p, 0)
            for r in SHED_REASONS:
                counts[f"qos/shed,reason:{r}"] = self.shed[r]
                counts[f"qos/wouldShed,reason:{r}"] = self.would_shed[r]
            for r in THROTTLE_REASONS:
                counts[f"qos/throttled,reason:{r}"] = self.throttled[r]
                counts[f"qos/wouldThrottled,reason:{r}"] = \
                    self.would_throttled[r]
            for i, (p, e) in enumerate(
                    sorted(self._per_principal.items(),
                           key=lambda kv: -sum(kv[1].values()))):
                if i >= 20:
                    break
                for k, v in e.items():
                    counts[f"qosPrincipal/{k},principal:{p}"] = v
            gauges = {
                "qos/estimatedWaitMs": round(self.estimated_wait_ms(), 3),
                "qos/queuePressure": round(self.queue_pressure, 3),
                "qos/mode": float(MODES.index(self.mode)),
            }
        return counts, gauges


def retry_after_header(seconds: float) -> str:
    """Retry-After value: integer seconds, >= 1 (RFC 7231 delta-seconds;
    sub-second backpressure still tells the client to back off)."""
    return str(max(1, int(math.ceil(seconds))))
