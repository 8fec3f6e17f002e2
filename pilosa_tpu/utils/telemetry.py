"""Fleet telemetry: time-series rings, device/XLA counters, health scores.

The reference ships statsd/expvar plumbing (stats/stats.go) because a
distributed bitmap index lives or dies on aggregate cluster behavior; the
TPU re-host adds device-side failure modes with no reference analog —
silent XLA recompiles and HBM eviction churn. Three pieces live here:

* `Ring` + `TelemetrySampler`: a background sampler that snapshots key
  gauges (HBM residency, batcher queues, fan-out pool, WAL, RSS) into a
  bounded in-memory ring, served incrementally at `GET /debug/timeseries`
  with a `since` cursor. `PILOSA_TPU_TELEMETRY=0` is the kill switch.
* `XLACounters` + `counted_jit`: compiles vs cached dispatches per kernel
  family, tracked host-side by dispatch signature (shape/dtype/static-arg
  key — the same key jax.jit caches on), with a recompile-storm warning.
* `health_score`: ONE green/yellow/red definition shared by `GET /status`
  and the `/cluster/stats` federation, so load balancers and the fleet
  view can never disagree about what "unhealthy" means.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Callable, Optional

from pilosa_tpu.analysis import lockwitness
from pilosa_tpu.utils import threads, tracing


def enabled() -> bool:
    """PILOSA_TPU_TELEMETRY=0 kills sampling AND dispatch counting (read
    per call: tests and operators flip it at runtime)."""
    return os.environ.get("PILOSA_TPU_TELEMETRY", "1") != "0"


def kernel_stats_enabled() -> bool:
    """PILOSA_TPU_KERNEL_STATS=0 kills per-dispatch latency attribution
    while leaving compile/cached counting on (read per call: the bench
    device_obs stage A/Bs the timing overhead at runtime). Implied off
    when the master telemetry switch is off."""
    return (enabled()
            and os.environ.get("PILOSA_TPU_KERNEL_STATS", "1") != "0")


# ---------------------------------------------------------------------------
# Time-series ring
# ---------------------------------------------------------------------------


class Ring:
    """Bounded in-memory time series: (seq, ts, {gauge: value}) samples.

    seq ascends forever; the deque bounds memory. `since(cursor)` returns
    only samples newer than the cursor, so pollers (the dashboard, the
    federation) transfer each sample once regardless of poll rate."""

    def __init__(self, size: int = 720):
        self.size = max(1, int(size))
        self._buf: collections.deque = collections.deque(maxlen=self.size)
        self._seq = 0
        self._lock = threading.Lock()

    def append(self, gauges: dict, ts: Optional[float] = None) -> int:
        if ts is None:
            ts = time.time()  # wall-clock: sample ts on /debug/timeseries
        with self._lock:
            self._seq += 1
            self._buf.append((self._seq, ts, dict(gauges)))
            return self._seq

    def since(self, cursor: int = 0, limit: int = 0) -> dict:
        """Samples with seq > cursor (oldest first), newest `limit` when
        set. The returned `seq` is the next poll's cursor even when no
        samples qualified."""
        with self._lock:
            out = [s for s in self._buf if s[0] > cursor]
            seq = self._seq
        if limit > 0:
            out = out[-limit:]
        return {"seq": seq, "samples": [
            {"seq": s, "ts": round(ts, 3), "gauges": g}
            for s, ts, g in out]}

    def latest(self) -> dict:
        """The newest sample's gauges ({} when never sampled)."""
        with self._lock:
            return dict(self._buf[-1][2]) if self._buf else {}

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


class TelemetrySampler:
    """Background gauge sampler -> Ring (the node's local TSDB-of-last-
    resort). `source()` returns one flat {gauge: float} dict per tick;
    rate/ratio derivation from cumulative counters is the source's job
    (it owns the previous-tick state). Interval <= 0 or the env kill
    switch disables the thread; sample_once() still works for tests."""

    def __init__(self, interval: float = 5.0, ring_size: int = 720,
                 source: Optional[Callable[[], dict]] = None,
                 logger=None):
        self.interval = interval
        self.ring = Ring(ring_size)
        self.source = source
        self.logger = logger
        self._timer: Optional[threading.Timer] = None
        self._lock = threading.Lock()
        # generation token: stop()/start() bump it, and a timer chain
        # only survives while its generation is current — otherwise a
        # stop()+start() racing an in-flight tick would leave the old
        # tick's finally-reschedule running as a SECOND chain forever
        # (sampling at 2x and burning ring history)
        self._gen = 0
        self.closed = False
        self.running = False
        self.sample_errors = 0

    def sample_once(self) -> Optional[int]:
        if self.source is None:
            return None
        try:
            gauges = self.source()
        except Exception as e:  # noqa: BLE001 — a failing gauge must
            # never kill the sampler loop (it outlives schema churn,
            # closing executors, chaos tests)
            self.sample_errors += 1
            if self.logger is not None:
                self.logger.printf("telemetry: sample failed: %s", e)
            return None
        return self.ring.append(gauges)

    def start(self) -> None:
        if self.interval <= 0 or not enabled() or self.source is None:
            return
        with self._lock:
            if self.running or self.closed:
                return
            self.running = True
            self._gen += 1
            gen = self._gen
        self._schedule(gen)

    def stop(self) -> None:
        """Pause sampling (restartable — the bench A/B toggles this)."""
        with self._lock:
            self.running = False
            self._gen += 1  # orphan any tick already in flight
            if self._timer is not None:
                self._timer.cancel()
                self._timer = None

    def close(self) -> None:
        self.closed = True
        self.stop()

    def _schedule(self, gen: int) -> None:
        with self._lock:
            if not self.running or self.closed or gen != self._gen:
                return
            self._timer = threads.ctx_timer(self.interval, self._tick,
                                            args=(gen,))
            self._timer.start()

    def _tick(self, gen: int) -> None:
        with self._lock:
            if not self.running or self.closed or gen != self._gen:
                return  # stale chain: die without sampling or rescheduling
        try:
            self.sample_once()
        finally:
            self._schedule(gen)


# ---------------------------------------------------------------------------
# Device / XLA dispatch counters
# ---------------------------------------------------------------------------

# a "storm" = this many NEW compilations of one kernel family inside the
# window — the signature of a shape-churning workload silently recompiling
# per query instead of hitting the jit cache (the roaring cost model only
# holds when dispatches hit compiled kernels)
STORM_N = int(os.environ.get("PILOSA_TPU_RECOMPILE_STORM_N", "8"))
STORM_WINDOW_S = float(os.environ.get(
    "PILOSA_TPU_RECOMPILE_STORM_WINDOW_S", "60"))


def _fmt_sig(sig) -> str:
    """Human form of one _sig_of leaf signature: arrays render as
    "int32[8,4096]"; static args by repr (bounded)."""
    if isinstance(sig, tuple) and len(sig) == 3 and sig[0] == "arr":
        return f"{sig[2]}[{','.join(str(d) for d in sig[1])}]"
    r = repr(sig)
    return r if len(r) <= 48 else r[:45] + "..."


_SIG_DIFF_CAP = 8  # changed leaves reported per diff (bounded payloads)


def _sig_diff(old_key, new_key) -> Optional[dict]:
    """Leafwise shape/dtype diff between two dispatch keys — the
    actionable half of a recompile-storm warning: WHICH operand's shape
    churned, old vs new. None when there is no prior key or the keys
    differ only in treedef (arity changes show as missing leaves)."""
    if old_key is None:
        return None
    old_sigs = old_key[1] if isinstance(old_key, tuple) \
        and len(old_key) == 2 else ()
    new_sigs = new_key[1] if isinstance(new_key, tuple) \
        and len(new_key) == 2 else ()
    changed: list[dict] = []
    n = max(len(old_sigs), len(new_sigs))
    for i in range(n):
        o = _fmt_sig(old_sigs[i]) if i < len(old_sigs) else "(absent)"
        w = _fmt_sig(new_sigs[i]) if i < len(new_sigs) else "(absent)"
        if o != w:
            changed.append({"leaf": i, "old": o, "new": w})
            if len(changed) >= _SIG_DIFF_CAP:
                break
    if not changed:
        return None
    return {"changed": changed, "oldLeaves": len(old_sigs),
            "newLeaves": len(new_sigs),
            "truncated": len(changed) >= _SIG_DIFF_CAP}


def _diff_brief(diff: Optional[dict]) -> str:
    """One-line diff summary for the storm warning text."""
    if not diff or not diff.get("changed"):
        return ""
    c = diff["changed"][0]
    more = len(diff["changed"]) - 1
    tail = f" (+{more} more leaf{'s' if more > 1 else ''})" if more else ""
    return (f"; last signature change: leaf {c['leaf']} "
            f"{c['old']} -> {c['new']}{tail}")


class XLACounters:
    """Compiles vs cached dispatches per kernel family.

    A dispatch whose (treedef, shapes/dtypes, static args) signature was
    never seen is a compile — the same key jax.jit caches on, tracked
    host-side so it works on every backend and costs no device round
    trip. Storm detection warns when one family compiles STORM_N new
    signatures inside STORM_WINDOW_S, naming the leaf whose shape/dtype
    churned (the old-vs-new signature diff rides the warning, the
    `xla.recompile_storm` event payload and /debug/vars)."""

    def __init__(self, storm_n: int = STORM_N,
                 storm_window_s: float = STORM_WINDOW_S):
        self.storm_n = storm_n
        self.storm_window_s = storm_window_s
        self.log_fn = None  # printf-style sink; warnings.warn fallback
        # flight-recorder hook (utils/events.py; set by Server):
        # event_fn(family, new_shapes_in_window, signature_diff) on each
        # storm trip — the diff names the leaf whose shape churned
        self.event_fn = None
        self._lock = tracing.TimedLock("telemetry.xla", threading.Lock())
        self._families: dict[str, dict] = {}
        self.storms = 0

    def _family(self, family: str) -> dict:
        f = self._families.get(family)
        if f is None:
            f = self._families[family] = {
                "compiles": 0, "cached": 0, "storms": 0,
                "keys": set(), "recent": collections.deque(),
                "last_storm": 0.0, "last_key": None, "last_diff": None}
        return f

    def record(self, family: str, key) -> bool:
        """Count one dispatch; returns True when it was a (re)compile."""
        now = time.monotonic()
        storm_msg = None
        storm_shapes = 0
        storm_diff = None
        with self._lock:
            f = self._family(family)
            if key in f["keys"]:
                f["cached"] += 1
                return False
            f["keys"].add(key)
            f["compiles"] += 1
            # the old-vs-new signature diff against the PREVIOUS compile:
            # under shape churn consecutive new keys differ in exactly the
            # operand whose shape is flapping, which is what an operator
            # needs to see to fix the storm (bounded: _SIG_DIFF_CAP leaves)
            f["last_diff"] = _sig_diff(f["last_key"], key)
            f["last_key"] = key
            rec = f["recent"]
            rec.append(now)
            while rec and now - rec[0] > self.storm_window_s:
                rec.popleft()
            if (len(rec) >= self.storm_n
                    and now - f["last_storm"] > self.storm_window_s):
                f["last_storm"] = now
                f["storms"] += 1
                self.storms += 1
                storm_shapes = len(rec)
                storm_diff = f["last_diff"]
                storm_msg = (
                    f"telemetry: XLA recompile storm: kernel family "
                    f"{family!r} compiled {len(rec)} new program shapes in "
                    f"{self.storm_window_s:.0f}s ({f['compiles']} total) — "
                    f"shape churn is defeating the jit cache; expect "
                    f"latency cliffs until shapes stabilize"
                    f"{_diff_brief(storm_diff)}")
        if storm_msg is not None:
            self._warn(storm_msg)
            if self.event_fn is not None:
                try:
                    self.event_fn(family, storm_shapes, storm_diff)
                except Exception:  # noqa: BLE001 — recording must never
                    pass  # break the dispatch path
        return True

    def _warn(self, msg: str) -> None:
        if self.log_fn is not None:
            try:
                self.log_fn("%s", msg)
                return
            except Exception:  # noqa: BLE001 — fall through to warnings
                pass
        import warnings

        warnings.warn(msg, RuntimeWarning, stacklevel=3)

    def storm_active(self, now: Optional[float] = None) -> bool:
        """True when any family stormed within the current window (a
        health-score input: the node is up but recompiling itself sick)."""
        if now is None:
            now = time.monotonic()
        with self._lock:
            return any(f["storms"] and now - f["last_storm"]
                       <= self.storm_window_s
                       for f in self._families.values())

    def snapshot(self) -> dict:
        with self._lock:
            fams = {name: {"compiles": f["compiles"], "cached": f["cached"],
                           "storms": f["storms"],
                           "lastSignatureDiff": f["last_diff"]}
                    for name, f in sorted(self._families.items())}
        return {
            "families": fams,
            "compiles": sum(f["compiles"] for f in fams.values()),
            "cachedDispatches": sum(f["cached"] for f in fams.values()),
            "storms": self.storms,
        }

    def reset(self) -> None:
        with self._lock:
            self._families.clear()
            self.storms = 0


# process-global: kernel modules register their dispatch sites against this
xla = XLACounters()


# ---------------------------------------------------------------------------
# Kernel latency / byte attribution (the device observability plane)
# ---------------------------------------------------------------------------


def kernel_rep(family: str) -> str:
    """Device representation a kernel family operates on ("dense",
    "sparse" or "run") — from the KERNEL_FAMILY_REPS inventory
    (pilosa_tpu/constants.py), "dense" for unregistered families."""
    from pilosa_tpu.constants import KERNEL_FAMILY_REPS
    return KERNEL_FAMILY_REPS.get(family, "dense")


class KernelStats:
    """Per-(family, rep, arity) dispatch latency histograms plus
    per-family queue-wait and h2d/d2h byte attribution.

    Latency is host-side dispatch wall (enqueue + any compile; JAX
    dispatch is asynchronous, so a first-call sample is dominated by
    compilation — read it next to XLACounters.compiles). Queue wait is
    the batcher's submit->delivery time attributed to the family that
    served the batch (parallel/batcher.py). h2d bytes are host-array
    argument bytes at dispatch plus residency upload bytes per
    representation; d2h bytes are recorded where results are actually
    fetched to host. Buckets are the same log2 scheme as StatsClient
    timings, so /metrics renders them as proper cumulative histograms.

    Disabled cost (PILOSA_TPU_KERNEL_STATS=0): one env read per
    dispatch."""

    def __init__(self):
        self._lock = tracing.TimedLock("telemetry.kernels",
                                       threading.Lock())
        # (family, rep, arity) -> {n, ms, min, max, buckets}
        self._calls: dict[tuple, dict] = {}
        self._wait: dict[str, dict] = {}   # family -> {ms, n}
        self._bytes: dict[str, dict] = {}  # family -> {h2d, d2h}
        self.dispatches = 0
        self.dispatch_ms_total = 0.0

    def record_call(self, family: str, rep: str, arity: int,
                    ms: Optional[float] = None,
                    h2d_bytes: int = 0) -> None:
        """One dispatch under (family, rep, arity). `ms=None` counts the
        dispatch without a latency sample (the mesh record_dispatch hook
        has no wall clock around the jitted call)."""
        from pilosa_tpu.utils.stats import _pow2_bucket
        key = (family, rep, int(arity))
        with self._lock:
            c = self._calls.get(key)
            if c is None:
                c = self._calls[key] = {
                    "dispatches": 0, "timed": 0, "ms": 0.0,
                    "min": None, "max": None, "buckets": {}}
            c["dispatches"] += 1
            self.dispatches += 1
            if ms is not None:
                c["timed"] += 1
                c["ms"] += ms
                c["min"] = ms if c["min"] is None else min(c["min"], ms)
                c["max"] = ms if c["max"] is None else max(c["max"], ms)
                b = _pow2_bucket(ms)
                c["buckets"][b] = c["buckets"].get(b, 0) + 1
                self.dispatch_ms_total += ms
            if h2d_bytes:
                by = self._bytes.setdefault(family, {"h2d": 0, "d2h": 0})
                by["h2d"] += int(h2d_bytes)

    def record_wait(self, family: str, ms: float, n: int = 1) -> None:
        """Queue wait (submit -> result delivery) of `n` requests served
        under `family` — the batcher-side half of the dispatch-vs-wait
        split."""
        with self._lock:
            w = self._wait.setdefault(family, {"ms": 0.0, "n": 0})
            w["ms"] += float(ms)
            w["n"] += int(n)

    def record_bytes(self, family: str, h2d: int = 0, d2h: int = 0) -> None:
        with self._lock:
            by = self._bytes.setdefault(family, {"h2d": 0, "d2h": 0})
            by["h2d"] += int(h2d)
            by["d2h"] += int(d2h)

    def totals(self) -> dict:
        """Flat cumulative totals for the telemetry sampler's rate
        derivation (server.sample_gauges owns the previous-tick state)."""
        with self._lock:
            return {
                "dispatches": self.dispatches,
                "dispatch_ms_total": self.dispatch_ms_total,
                "wait_ms_total": sum(w["ms"] for w in self._wait.values()),
                "waited": sum(w["n"] for w in self._wait.values()),
                "h2d_bytes": sum(b["h2d"] for b in self._bytes.values()),
                "d2h_bytes": sum(b["d2h"] for b in self._bytes.values()),
            }

    def snapshot(self) -> dict:
        """The /debug/vars `kernels` block."""
        with self._lock:
            calls = [
                {"family": fam, "rep": rep, "arity": ar,
                 "dispatches": c["dispatches"], "timed": c["timed"],
                 "msTotal": round(c["ms"], 3),
                 "avgMs": round(c["ms"] / c["timed"], 4) if c["timed"]
                 else 0.0,
                 "minMs": c["min"], "maxMs": c["max"],
                 "buckets": dict(c["buckets"])}
                for (fam, rep, ar), c in sorted(self._calls.items())]
            wait = {fam: {"msTotal": round(w["ms"], 3), "waited": w["n"],
                          "avgMs": round(w["ms"] / w["n"], 3) if w["n"]
                          else 0.0}
                    for fam, w in sorted(self._wait.items())}
            byts = {fam: dict(b) for fam, b in sorted(self._bytes.items())}
            return {"enabled": kernel_stats_enabled(),
                    "dispatches": self.dispatches,
                    "dispatchMsTotal": round(self.dispatch_ms_total, 3),
                    "calls": calls, "wait": wait, "bytes": byts}

    def metrics_view(self) -> tuple[dict, dict]:
        """(counts, timings) fragments in StatsClient key syntax for the
        /metrics merge: counts feed pilosa_kernels*_total counters and
        timings feed the pilosa_kernelDispatchMs histogram family. Only
        live series — net/http_server.py zero-fills the full family ×
        rep keyspace so alerts never race first events."""
        counts: dict = {}
        timings: dict = {}
        with self._lock:
            for (fam, rep, ar), c in self._calls.items():
                k = f"kernelsDispatches/{fam},rep:{rep}"
                counts[k] = counts.get(k, 0) + c["dispatches"]
                if c["timed"]:
                    tk = f"kernelDispatchMs/{fam},rep:{rep}"
                    t = timings.setdefault(tk, {
                        "count": 0, "sum": 0.0, "min": None, "max": None,
                        "buckets": {}})
                    t["count"] += c["timed"]
                    t["sum"] += c["ms"]
                    t["min"] = c["min"] if t["min"] is None \
                        else min(t["min"], c["min"])
                    t["max"] = c["max"] if t["max"] is None \
                        else max(t["max"], c["max"])
                    for b, n in c["buckets"].items():
                        t["buckets"][b] = t["buckets"].get(b, 0) + n
            for fam, w in self._wait.items():
                counts[f"kernelsWaitMs/{fam},rep:{kernel_rep(fam)}"] = \
                    w["ms"]
                counts[f"kernelsWaited/{fam},rep:{kernel_rep(fam)}"] = \
                    w["n"]
            for fam, b in self._bytes.items():
                rep = kernel_rep(fam)
                counts[f"kernelsH2dBytes/{fam},rep:{rep}"] = b["h2d"]
                counts[f"kernelsD2hBytes/{fam},rep:{rep}"] = b["d2h"]
        return counts, timings

    def reset(self) -> None:
        with self._lock:
            self._calls.clear()
            self._wait.clear()
            self._bytes.clear()
            self.dispatches = 0
            self.dispatch_ms_total = 0.0


# process-global, like `xla`: counted_jit sites and the batchers record
# against this; /debug/vars, /metrics and the sampler read it
kernels = KernelStats()


def _sig_of(leaf):
    """Hashable signature of one pytree leaf: arrays by (shape, dtype) —
    the part of the jit cache key that changes under shape churn — other
    leaves by value when hashable (static args), else by type."""
    shape = getattr(leaf, "shape", None)
    if shape is not None:
        return ("arr", tuple(shape), str(getattr(leaf, "dtype", "?")))
    try:
        hash(leaf)
    except TypeError:
        return ("type", type(leaf).__name__)
    return leaf


def dispatch_key(args: tuple, kwargs: Optional[dict] = None):
    """(treedef, per-leaf signatures) for a call — tracks jax.jit's own
    cache key closely enough that a new key here is a new compilation."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs or {}))
    return treedef, tuple(_sig_of(l) for l in leaves)


def record_dispatch(family: str, *args, devices: int = 1,
                    collective: bool = False) -> None:
    """Manual counting hook for dispatch sites that build their jitted
    callables dynamically (the mesh shard_map paths). No wall clock wraps
    the jitted call here, so the kernel-stats entry counts the dispatch
    without a latency sample. `devices` is how many the launch goes to
    and `collective` whether its program holds a psum (the /debug/vars
    `mesh` block counts launches to several devices by it)."""
    lockwitness.note_blocking("dispatch", family)
    tracing.note_launch(devices, collective)
    if not enabled():
        return
    try:
        key = dispatch_key(args)
        xla.record(family, key)
        if kernel_stats_enabled():
            arity = sum(1 for s in key[1]
                        if isinstance(s, tuple) and s and s[0] == "arr")
            kernels.record_call(family, kernel_rep(family), arity)
    except Exception:  # noqa: BLE001 — counting must never break dispatch
        pass


def _launch_devices(args, kwargs) -> tuple[int, str]:
    """(the most devices an operand of a launch is laid over, their
    platform)."""
    import jax

    n, platform = 1, ""
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        if isinstance(leaf, jax.Array) and not isinstance(
                leaf, jax.core.Tracer):
            devs = leaf.sharding.device_set
            if len(devs) > n:
                n, platform = len(devs), next(iter(devs)).platform
    return n, platform


# On the CPU backend the parts of a program laid over several devices do
# not run independently of each other as they do on the chip: two threads
# that launch such programs at once, each reaching the devices in its own
# order, can leave the all-reduce of a collective program waiting on one
# device for a part that is queued on another behind the other thread's
# program, for ever (a served four-device mesh under eight client threads
# on a loaded host hung so once a kernel took milliseconds there). So on
# the CPU every launch to several devices is made under this one lock,
# and the programs reach every device in one order. The chip's runtime
# needs no such order.
_cpu_launch_order = lockwitness.native_rlock()


def _launch(jitted, order, args, kwargs):
    """jitted(*args, **kwargs), under `order` where it is a lock."""
    if order is None:
        return jitted(*args, **kwargs)
    with order:
        return jitted(*args, **kwargs)


def _order_for(devices: int, platform: str):
    return _cpu_launch_order if devices > 1 and platform == "cpu" else None


def launch_in_order(mesh, fn, *args):
    """fn(*args), a launch of a program laid over `mesh` that is not a
    counted_jit kernel (the mesh's shard_map programs), in the one launch
    order the CPU backend needs."""
    return _launch(fn, _order_for(mesh.size, mesh.devices.flat[0].platform),
                   args, {})


def counted_jit(family: str, cross_shard: bool = False, **jit_kwargs):
    """jax.jit + per-call compile/cached accounting under `family`, plus
    per-(family, rep, arity) dispatch latency and h2d byte attribution
    (KernelStats) when PILOSA_TPU_KERNEL_STATS is on.

    `cross_shard` declares that the function reduces over the shard axis
    of its operands: laid over a mesh, GSPMD puts an all-reduce into the
    program, so such a launch is counted as one that holds a collective
    (tracing.mesh_launches) and has to be made through
    parallel/mesh.py on_collective_thread.

    Drop-in at the decorator site: the wrapper forwards to the jitted
    callable and skips accounting AND timing inside a trace (a wrapped
    kernel called from another jitted function inlines; counting or
    timing tracer calls would double-book one outer compile/dispatch as
    N inner ones) and when the telemetry kill switch is off. The latency
    sample is host-side dispatch wall: JAX dispatch is asynchronous, so
    steady-state samples measure enqueue cost and first-call samples are
    dominated by compilation."""
    import functools

    import jax
    import numpy as np

    rep = kernel_rep(family)

    def wrap(fn):
        jitted = jax.jit(fn, **jit_kwargs)

        @functools.wraps(fn)
        def call(*args, **kwargs):
            # lock-order witness choke point: a device dispatch while
            # holding a witnessed lock stalls every sibling of that lock
            # behind the accelerator (no-op unless PILOSA_TPU_LOCKCHECK=1)
            lockwitness.note_blocking("dispatch", family)
            order = None
            if tracing.mesh_launches.watching:
                devices, platform = _launch_devices(args, kwargs)
                tracing.note_launch(devices, cross_shard and devices > 1)
                order = _order_for(devices, platform)
            else:
                tracing.note_launch()
            arity = -1
            h2d = 0
            if enabled():
                try:
                    leaves, treedef = jax.tree_util.tree_flatten(
                        (args, kwargs))
                    if not any(isinstance(l, jax.core.Tracer)
                               for l in leaves):
                        xla.record(family, (treedef,
                                            tuple(_sig_of(l)
                                                  for l in leaves)))
                        if kernel_stats_enabled():
                            arity = 0
                            for l in leaves:
                                if hasattr(l, "shape"):
                                    arity += 1
                                    # host arrays cross the h2d link at
                                    # dispatch; device arrays are free
                                    if isinstance(l, np.ndarray):
                                        h2d += l.nbytes
                except Exception:  # noqa: BLE001 — never break dispatch
                    pass
            if arity < 0:  # stats off, tracer context, or flatten failed
                return _launch(jitted, order, args, kwargs)
            t0 = time.perf_counter()
            try:
                return _launch(jitted, order, args, kwargs)
            finally:
                try:
                    kernels.record_call(
                        family, rep, arity,
                        ms=(time.perf_counter() - t0) * 1e3,
                        h2d_bytes=h2d)
                except Exception:  # noqa: BLE001 — never break dispatch
                    pass

        # AOT surface passthrough (callers may .lower()/.clear_cache())
        call._jitted = jitted
        for attr in ("lower", "clear_cache", "trace", "eval_shape"):
            if hasattr(jitted, attr):
                setattr(call, attr, getattr(jitted, attr))
        return call

    return wrap


def device_memory_stats() -> list[dict]:
    """Per-device memory_stats() where the backend provides it (TPU HBM
    live bytes etc.); memoryStats is a graceful null on CPU backends."""
    import jax

    out: list[dict] = []
    try:
        devices = jax.devices()
    except Exception:  # noqa: BLE001 — no backend at all
        return out
    for d in devices:
        stats = None
        try:
            fn = getattr(d, "memory_stats", None)
            stats = fn() if callable(fn) else None
        except Exception:  # noqa: BLE001 — backend without the API
            stats = None
        out.append({"device": str(d),
                    "platform": getattr(d, "platform", "?"),
                    "device_kind": getattr(d, "device_kind", "?"),
                    "memoryStats": stats})
    return out


# ---------------------------------------------------------------------------
# On-demand device profile capture
# ---------------------------------------------------------------------------


def device_profile_enabled() -> bool:
    """PILOSA_TPU_DEVICE_PROFILE=0 kills on-demand XLA profile capture
    (read per call: the emergency toggle needs no restart)."""
    return os.environ.get("PILOSA_TPU_DEVICE_PROFILE", "1") != "0"


# spool cap: captures beyond this total size evict oldest-first, so a
# crontabbed capture loop can never fill a disk
PROFILE_SPOOL_CAP_BYTES = 256 << 20
MAX_PROFILE_SECONDS = 60.0


def _dir_bytes(path: str) -> int:
    total = 0
    for dirpath, _, names in os.walk(path):
        for n in names:
            try:
                total += os.path.getsize(os.path.join(dirpath, n))
            except OSError:
                pass
    return total


class DeviceProfiler:
    """POST /debug/device-profile backing: a `jax.profiler` trace around
    a sleep of the requested duration, host tracer on and Python tracer
    off, spooling the trace into a byte-capped directory. The program's
    spans are written into it as `pilosa.<name>` host events for its
    duration. Exactly one capture runs at a time (a second
    request reports "busy" instead of queueing); serving is never
    blocked — the trace rides the requesting HTTP worker thread while
    query traffic proceeds, which is the point: the capture sees the
    live workload's device activity."""

    def __init__(self, spool_dir: Optional[str] = None,
                 cap_bytes: int = PROFILE_SPOOL_CAP_BYTES):
        import tempfile
        self.spool_dir = spool_dir or os.path.join(
            tempfile.gettempdir(), "pilosa-tpu-device-profiles")
        self.cap_bytes = int(cap_bytes)
        self._busy = threading.Lock()
        self.captures = 0
        self.errors = 0
        self.last: Optional[dict] = None

    def capture(self, seconds: float) -> dict:
        if not device_profile_enabled():
            return {"status": "disabled",
                    "error": "device profile capture disabled "
                             "(PILOSA_TPU_DEVICE_PROFILE=0)"}
        try:
            seconds = max(0.05, min(float(seconds), MAX_PROFILE_SECONDS))
        except (TypeError, ValueError):
            return {"status": "error", "error": "invalid seconds"}
        if not self._busy.acquire(blocking=False):
            return {"status": "busy",
                    "error": "a device profile capture is already running"}
        try:
            stamp = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
            out_dir = os.path.join(self.spool_dir,
                                   f"capture-{stamp}-{self.captures}")
            os.makedirs(out_dir, exist_ok=True)
            import jax
            t0 = time.perf_counter()
            # the program's own spans stand in for the Python tracer
            # (python_tracer_level 1, the default, records every call of
            # every request thread, slows them and takes seconds to
            # serialize at stop): while the flag is up each span is a
            # TraceAnnotation on the capture's clock (utils/tracing.py)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(out_dir, profiler_options=options)
            tracing.set_capturing(True)
            # the interpreter lock's hand-off, for the capture alone
            tracing.handoff_probe.start()
            try:
                time.sleep(seconds)
            finally:
                tracing.handoff_probe.stop()
                tracing.set_capturing(False)
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
            t_end = time.perf_counter()
            self.captures += 1
            doc = {"status": "ok", "dir": out_dir,
                   "spoolDir": self.spool_dir,
                   "seconds": round(t_end - t0, 3),
                   # what stop_trace took to serialize the capture
                   "stopSeconds": round(t_end - t_stop, 3),
                   "bytes": _dir_bytes(out_dir),
                   "captures": self.captures}
            self._enforce_cap()
        except Exception as e:  # noqa: BLE001 — capture is best-effort
            self.errors += 1
            doc = {"status": "error", "error": str(e)}
        finally:
            self._busy.release()
        self.last = doc
        return doc

    def _enforce_cap(self) -> None:
        """Evict oldest capture dirs until the spool fits the byte cap
        (the newest capture always survives, even oversized)."""
        import shutil
        try:
            subdirs = [os.path.join(self.spool_dir, n)
                       for n in os.listdir(self.spool_dir)
                       if n.startswith("capture-")]
        except OSError:
            return
        subdirs = [d for d in subdirs if os.path.isdir(d)]
        subdirs.sort(key=lambda d: os.path.getmtime(d))
        sizes = {d: _dir_bytes(d) for d in subdirs}
        total = sum(sizes.values())
        while total > self.cap_bytes and len(subdirs) > 1:
            victim = subdirs.pop(0)
            total -= sizes[victim]
            shutil.rmtree(victim, ignore_errors=True)

    def snapshot(self) -> dict:
        return {"enabled": device_profile_enabled(),
                "spoolDir": self.spool_dir,
                "capBytes": self.cap_bytes,
                "spoolBytes": _dir_bytes(self.spool_dir)
                if os.path.isdir(self.spool_dir) else 0,
                "captures": self.captures, "errors": self.errors,
                "busy": self._busy.locked(), "last": self.last}


# process-global, like `xla`/`kernels`: the HTTP handler and CLI hit this
device_profiler = DeviceProfiler()


# ---------------------------------------------------------------------------
# Node health score
# ---------------------------------------------------------------------------

# error-rate thresholds (5xx responses/second over the sampler window)
ERROR_RATE_YELLOW = 0.1
ERROR_RATE_RED = 2.0
# outbound fan-out work queued beyond the pool, as a multiple of pool size
QUEUE_SATURATION_YELLOW = 2.0

_SEVERITY = {"green": 0, "yellow": 1, "red": 2}


def health_score(inputs: dict) -> dict:
    """{"score": green|yellow|red, "reasons": [...]} from a node's health
    inputs. The ONE shared definition: `GET /status` reports it for load
    balancers and the `/cluster/stats` federation reuses it per node, so
    the two surfaces can never disagree. Inputs (all optional, absent =
    healthy): walPoisoned, needsRebuild, damagedFragments, errorRate
    (5xx/s), queueSaturation (queued / pool size), recompileStormActive,
    draining (graceful restart in progress — yellow, never red),
    fencedShards (rejoin read fence awaiting parity verification),
    sloStatus/sloReason (the worst [slo] objective's multi-window
    burn-rate verdict, utils/accounting.py SLOTracker.worst()).
    Liveness is the federation layer's job (a down node never answers)."""
    score = "green"
    reasons: list[str] = []

    def worsen(level: str, why: str) -> None:
        nonlocal score
        if _SEVERITY[level] > _SEVERITY[score]:
            score = level
        reasons.append(why)

    if inputs.get("walPoisoned"):
        worsen("red", "WAL poisoned: writes refused until snapshot")
    n = int(inputs.get("needsRebuild") or 0)
    if n:
        worsen("yellow", f"{n} quarantined fragment(s) awaiting replica "
                         "rebuild")
    d = int(inputs.get("damagedFragments") or 0)
    if d and not n:
        worsen("yellow", f"{d} fragment(s) recovered from damage "
                         "(quarantine/torn WAL)")
    err = float(inputs.get("errorRate") or 0.0)
    if err >= ERROR_RATE_RED:
        worsen("red", f"HTTP 5xx rate {err:.2f}/s")
    elif err >= ERROR_RATE_YELLOW:
        worsen("yellow", f"HTTP 5xx rate {err:.2f}/s")
    sat = float(inputs.get("queueSaturation") or 0.0)
    if sat >= QUEUE_SATURATION_YELLOW:
        worsen("yellow", f"fan-out queue saturated ({sat:.1f}x pool size)")
    if inputs.get("recompileStormActive"):
        worsen("yellow", "XLA recompile storm in progress")
    if inputs.get("draining"):
        # deliberate lifecycle state: yellow, never red — a rolling
        # restart in progress must not page anyone or trip QoS healthRed
        worsen("yellow", "node draining (graceful restart in progress)")
    fenced = int(inputs.get("fencedShards") or 0)
    if fenced:
        worsen("yellow", f"{fenced} shard(s) read-fenced pending rejoin "
                         "parity verification")
    slo_status = inputs.get("sloStatus")
    if slo_status in ("yellow", "red"):
        worsen(slo_status,
               inputs.get("sloReason") or "SLO burn-rate alert")
    return {"score": score, "reasons": reasons}
