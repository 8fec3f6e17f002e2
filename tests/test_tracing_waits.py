"""Where threads wait, counted where they wait (utils/tracing.py): a
`TimedLock` books a contended wait to its name and to the innermost open
span (the span table's `waitMs`), the interpreter's collections are timed
by one process-wide hook, and the interpreter lock's hand-off is probed
only while a capture runs. The recording tracer's ring takes no lock.

Waits are forced here by holding a lock, never by timing a race, so
nothing depends on how fast the machine is.
"""

import gc
import glob
import inspect
import json
import threading
import time
import urllib.request
import uuid

import pytest

from pilosa_tpu.analysis import lockwitness
from pilosa_tpu.server import Server
from pilosa_tpu.utils import telemetry, tracing
from pilosa_tpu.utils.heat import HeatTracker

PROBE = "pilosa-interp-probe"

# every lock a served Count, TopN (also under tanimotoThreshold) or
# GroupBy takes, by the witness (docs/operations.md "Request spans")
SERVED = ("accounting.slo", "accounting.usage", "events.hlc", "heat",
          "http.active", "planner", "plancache", "residency.lru",
          "stats", "telemetry.kernels", "telemetry.xla", "topn.memo",
          "tracing.spans")
NAMED = SERVED + ("batcher", "mesh.ici", "mesh.launches",
                  "residency.build", "residency.rowstats")


def unique(prefix: str) -> str:
    return f"{prefix}.{uuid.uuid4().hex[:8]}"


def span_entry(name: str) -> dict:
    return tracing.spans.snapshot()["byName"].get(name, {})


def hold_while(lock, waiters: list, settle_s: float = 0.15) -> None:
    """Hold `lock`, start the waiter threads, let them block on it for
    `settle_s`, release, and join them."""
    with lock:
        for t in waiters:
            t.start()
        time.sleep(settle_s)
    for t in waiters:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in waiters)


def probe_threads() -> list:
    return [t for t in threading.enumerate() if t.name == PROBE]


# ------------------------------------------------------------- TimedLock


def test_a_contended_lock_books_its_wait_to_the_lock_and_the_open_span():
    name = unique("test.contended")
    lk = tracing.TimedLock(name, threading.Lock())
    span_name = unique("t.waiter")

    def waiter():
        with tracing.span(span_name):
            with lk:
                pass

    hold_while(lk, [threading.Thread(target=waiter)])
    got = tracing.locks_snapshot()[name]
    assert got["waited"] == 1
    assert got["waitMs"] >= 100.0
    assert got["maxWaitMs"] == pytest.approx(got["waitMs"])
    e = span_entry(span_name)
    assert e["n"] == 1
    assert e["waitMs"] == pytest.approx(got["waitMs"])
    assert e["waitMs"] <= e["selfMs"]


def test_an_uncontended_lock_books_nothing():
    name = unique("test.free")
    lk = tracing.TimedLock(name, threading.Lock())
    span_name = unique("t.free")
    with tracing.span(span_name):
        for _ in range(1000):
            with lk:
                assert lk._lock.locked()
    assert not lk._lock.locked()
    assert tracing.locks_snapshot()[name] == {
        "waited": 0, "waitMs": 0.0, "maxWaitMs": 0.0}
    e = span_entry(span_name)
    assert e["n"] == 1 and e["waitMs"] == 0.0


def test_the_witness_still_reports_the_callers_construction_site(
        monkeypatch):
    monkeypatch.setenv(lockwitness.ENV_GATE, "1")
    was_active = lockwitness.ACTIVE
    lockwitness.maybe_install()
    try:
        line = inspect.currentframe().f_lineno + 1
        lk = tracing.TimedLock(unique("test.site"), threading.Lock())
        assert isinstance(lk._lock, lockwitness.WitnessLock)
        assert lk._lock.site == f"tests/test_tracing_waits.py:{line}"
        # a lock of the program: the site is the line that builds it
        tracker = HeatTracker()
        with open(inspect.getsourcefile(HeatTracker)) as fh:
            src = fh.read().splitlines()
        want = next(i + 1 for i, text in enumerate(src)
                    if 'TimedLock("heat", threading.Lock())' in text)
        assert tracker._lock._lock.site == f"pilosa_tpu/utils/heat.py:{want}"
        with tracker._lock:  # witnessed: held, then released
            assert tracker._lock._lock.locked()
        assert not tracker._lock._lock.locked()
    finally:
        if not was_active:
            lockwitness.uninstall()


def test_eight_threads_queued_on_the_heat_lock_book_the_same_wait_twice():
    """The heat map's queue at toy size: threads that queue on its
    lock show the queue in `locks.heat` and, by the same sum, in the
    `waitMs` of the spans they were in, not as work."""
    tracker = HeatTracker()
    keys = [("i", "f", "standard", s) for s in range(64)]
    span_name = unique("t.heat")
    before = tracing.locks_snapshot().get("heat", {}).get("waitMs", 0.0)

    def touch():
        with tracing.span(span_name):
            tracker.touch_many(keys, reads=1)

    hold_while(tracker._lock,
               [threading.Thread(target=touch) for _ in range(8)])
    own = tracker._lock.waits
    assert own.waited == 8 and own.wait_ms > 0
    after = tracing.locks_snapshot()["heat"]["waitMs"]
    assert after - before >= own.wait_ms - 1e-6
    e = span_entry(span_name)
    assert e["n"] == 8
    assert e["waitMs"] == pytest.approx(own.wait_ms)
    assert e["waitMs"] <= e["selfMs"]
    assert tracker.totals()["reads"] == 8 * len(keys)


def test_a_wait_is_booked_to_the_innermost_span_only():
    name = unique("test.inner")
    lk = tracing.TimedLock(name, threading.Lock())
    outer, inner = unique("t.outer"), unique("t.inner")

    def waiter():
        with tracing.span(outer):
            with tracing.span(inner):
                with lk:
                    pass

    hold_while(lk, [threading.Thread(target=waiter)], 0.05)
    assert span_entry(inner)["waitMs"] == pytest.approx(
        tracing.locks_snapshot()[name]["waitMs"])
    assert span_entry(outer)["waitMs"] == 0.0


def test_a_single_flight_wait_is_counted_as_residency_build():
    from pilosa_tpu.parallel.residency import DeviceResidency

    class Runner:
        def put_leaf(self, host):
            return host

    class Leaf:
        nbytes = 8

    res = DeviceResidency(Runner())
    release = threading.Event()

    def make():
        release.wait(timeout=30)
        return Leaf()

    span_name = unique("t.build")

    def leaf():
        with tracing.span(span_name):
            res.leaf(("k",), make)

    builder = threading.Thread(target=leaf)
    builder.start()
    deadline = time.time() + 10
    while not res._building and time.time() < deadline:
        time.sleep(0.005)
    waiter = threading.Thread(target=leaf)
    waiter.start()
    time.sleep(0.1)
    release.set()
    builder.join(timeout=30)
    waiter.join(timeout=30)
    assert res._build_waits.waited == 1
    assert res._build_waits.wait_ms >= 50
    assert "residency.build" in tracing.locks_snapshot()
    # the waiter may also have queued for the LRU lock the builder set
    # the event under: that wait is the lock's, and the span's too
    assert span_entry(span_name)["waitMs"] == pytest.approx(
        res._build_waits.wait_ms + res._lock.waits.wait_ms)


# ----------------------------------------------------- the served program


def post(uri, path, raw=b""):
    req = urllib.request.Request(uri + path, data=raw, method="POST")
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def get_vars(uri) -> dict:
    with urllib.request.urlopen(uri + "/debug/vars", timeout=60) as r:
        return json.loads(r.read())


def test_debug_vars_shows_every_named_lock_the_waits_and_the_runtime(
        tmp_path):
    s = Server(str(tmp_path / "n"), port=0).open()
    try:
        post(s.uri, "/index/i", b"{}")
        post(s.uri, "/index/i/field/f", b"{}")
        post(s.uri, "/index/i/query",
             b" ".join(f"Set({7 * c}, f={r})".encode()
                       for r in range(3) for c in range(30)))
        handoff = tracing.handoff_probe.snapshot()["n"]
        assert post(s.uri, "/index/i/query",
                    b"Count(Intersect(Row(f=0), Row(f=1)))")
        v = get_vars(s.uri)
    finally:
        s.close()
    assert set(NAMED) <= set(v["locks"]), sorted(v["locks"])
    for e in v["locks"].values():
        assert set(e) == {"waited", "waitMs", "maxWaitMs"}
    by_name = v["spans"]["byName"]
    assert "http.request" in by_name
    for e in by_name.values():
        assert 0.0 <= e["waitMs"] <= e["selfMs"] + 1e-9
    assert set(v["runtime"]["gc"]) == {"0", "1", "2"}
    for e in v["runtime"]["gc"].values():
        assert set(e) == {"n", "pauseMs", "maxPauseMs"}
    hand = v["runtime"]["interpHandoff"]
    assert set(hand) == {"n", "sumMs", "maxMs", "buckets"}
    # no capture ran: the probe neither exists nor counted
    assert hand["n"] == handoff
    assert not probe_threads()


# ------------------------------------------------- the interpreter's waits


def test_the_collection_hook_is_installed_once_across_two_servers(tmp_path):
    servers = [Server(str(tmp_path / n), port=0).open() for n in "ab"]
    try:
        hooks = [cb for cb in gc.callbacks
                 if getattr(cb, "__self__", None) is tracing.gc_pauses]
        assert len(hooks) == 1
        before = tracing.gc_pauses.snapshot()["2"]
        gc.collect(2)
        after = tracing.gc_pauses.snapshot()["2"]
        assert after["n"] == before["n"] + 1
        assert after["pauseMs"] > before["pauseMs"]
        assert after["maxPauseMs"] >= after["pauseMs"] - before["pauseMs"]
        # the runtime monitor's gauge reads the same counter
        servers[0].runtime_monitor.sample()
        gauges = servers[0].stats.snapshot()["gauges"]
        assert gauges["garbage/collections"] >= after["n"]
        assert "garbage/gen0" not in gauges
    finally:
        for s in servers:
            s.close()


def test_the_probe_runs_only_during_a_capture_and_collections_are_on_it(
        tmp_path):
    from jax.profiler import ProfileData

    assert not probe_threads()
    before = tracing.handoff_probe.snapshot()["n"]
    prof = telemetry.DeviceProfiler(spool_dir=str(tmp_path / "spool"))
    out: dict = {}
    t = threading.Thread(target=lambda: out.update(doc=prof.capture(0.5)))
    t.start()
    deadline = time.time() + 30
    while not tracing.capturing() and time.time() < deadline:
        time.sleep(0.005)
    assert tracing.capturing()
    deadline = time.time() + 10
    while not probe_threads() and time.time() < deadline:
        time.sleep(0.005)
    assert len(probe_threads()) == 1
    gc.collect(1)  # on this thread, inside the capture
    t.join(timeout=120)
    assert out["doc"]["status"] == "ok", out
    assert not probe_threads()
    hand = tracing.handoff_probe.snapshot()
    assert hand["n"] > before
    assert sum(hand["buckets"].values()) == hand["n"]
    assert hand["maxMs"] >= 0.0 and hand["sumMs"] >= 0.0

    path = glob.glob(out["doc"]["dir"] + "/**/*.xplane.pb",
                     recursive=True)[0]
    space = ProfileData.from_file(path)
    host = next(p for p in space.planes if p.name == "/host:CPU")
    gens = [dict(ev.stats).get("generation") for line in host.lines
            for ev in line.events if ev.name == "pilosa.gc"]
    assert gens and all(g >= 1 for g in gens), gens


def test_a_collective_launch_is_a_dispatch_event_on_its_own_thread(
        tmp_path):
    """The collective thread's launch is counted by the caller's span and,
    in a capture, written on the thread that made it, so the trace finds
    a span on the thread it finds the program's launch on."""
    from jax.profiler import ProfileData

    from pilosa_tpu.parallel import mesh as pmesh

    prof = telemetry.DeviceProfiler(spool_dir=str(tmp_path / "spool"))
    out: dict = {}
    t = threading.Thread(target=lambda: out.update(doc=prof.capture(0.3)))
    t.start()
    deadline = time.time() + 30
    while not tracing.capturing() and time.time() < deadline:
        time.sleep(0.005)
    span_name = unique("t.caller")
    with tracing.span(span_name):
        pmesh.on_collective_thread(lambda: None)
    t.join(timeout=120)
    assert out["doc"]["status"] == "ok", out
    path = glob.glob(out["doc"]["dir"] + "/**/*.xplane.pb",
                     recursive=True)[0]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    lines_of: dict = {}
    for i, line in enumerate(host.lines):
        for ev in line.events:
            lines_of.setdefault(ev.name, set()).add(i)
    caller = lines_of["pilosa." + span_name]
    assert lines_of.get("pilosa.dispatch", set()) - caller


# ------------------------------------------------- the lock-free span ring


def test_the_ring_keeps_limit_spans_under_eight_writers():
    tr = tracing.Tracer(limit=100)
    stop = threading.Event()
    reads = []

    def write(k):
        for i in range(500):
            tr.start_span(f"w{k}.{i}").finish()

    def read():
        while not stop.is_set():
            reads.append(len(tr.finished()))

    reader = threading.Thread(target=read)
    reader.start()
    writers = [threading.Thread(target=write, args=(k,)) for k in range(8)]
    for w in writers:
        w.start()
    for w in writers:
        w.join(timeout=60)
    stop.set()
    reader.join(timeout=30)
    assert len(tr.finished()) == 100
    assert len({s.span_id for s in tr.finished()}) == 100
    assert reads and max(reads) <= 100
    assert len(tr.finished("w0.499")) <= 1
