"""The span primitive (utils/tracing.py) and its three sinks: the
aggregate table (/debug/vars `spans`, /metrics `pilosa_spanMs`), the device
trace while a capture runs, the request's profile and the node's ring.

One in-process server on the CPU backend serves every HTTP case; nothing
here depends on how fast the machine is: self times are checked as a
partition of their root's wall, never against a threshold.
"""

import contextvars
import glob
import json
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import pytest

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.server import Server
from pilosa_tpu.utils import telemetry, tracing


def post(uri, path, raw=b"", headers=None):
    req = urllib.request.Request(uri + path, data=raw, method="POST",
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def get(uri, path):
    with urllib.request.urlopen(uri + path, timeout=60) as r:
        return r.read()


def load(server):
    """Rows 0-9 sparse (a few bits), rows 100-101 dense (over the
    4,096-bits-a-shard threshold), over two shards; each row's columns
    hold the row before it."""
    post(server.uri, "/index/i", b"{}")
    post(server.uri, "/index/i/field/f", b"{}")
    sets = [f"Set({s * SHARD_WIDTH + 7 * c}, f={r})"
            for r in range(10) for c in range(20 + r) for s in range(2)]
    post(server.uri, "/index/i/query", " ".join(sets).encode())
    for r in (100, 101):
        cols = [s * SHARD_WIDTH + 3 * c for s in range(2)
                for c in range(5000 + r)]
        post(server.uri, "/index/i/field/f/import", json.dumps(
            {"rowIDs": [r] * len(cols), "columnIDs": cols}).encode(),
            headers={"Content-Type": "application/json"})


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    s = Server(str(tmp_path_factory.mktemp("spans") / "n"), port=0).open()
    try:
        load(s)
        yield s
    finally:
        s.close()


def tree_of(server, trace_id):
    """The finished spans of one trace, once the root has landed (it
    finishes after the response's last byte, so a reader can be ahead)."""
    deadline = time.time() + 10
    while time.time() < deadline:
        got = [sp for sp in server.tracer.finished()
               if sp.trace_id == trace_id]
        if any(sp.parent is None for sp in got):
            return got
        time.sleep(0.01)
    raise AssertionError(f"no root span for trace {trace_id}")


# ------------------------------------------------------------ the primitive


def _sequential():
    with tracing.span("parent") as parent:
        time.sleep(0.01)
        with tracing.span("a") as a:
            time.sleep(0.02)
        with tracing.span("b") as b:
            time.sleep(0.01)
    return parent, [a, b], a.ms + b.ms


def _pool_thread():
    def work():
        with tracing.span("child") as c:
            time.sleep(0.03)
        return c

    with tracing.span("parent") as parent:
        time.sleep(0.01)
        with ThreadPoolExecutor(1) as pool:
            # a fan-out submit: the pool thread runs in a copy of the
            # submitter's context (executor._execute_cross_slice)
            child = pool.submit(contextvars.copy_context().run,
                                work).result(timeout=10)
    return parent, [child], child.ms


def _overlapping_pool_threads():
    gate = threading.Barrier(2, timeout=10)

    def work():
        with tracing.span("child") as c:
            gate.wait()
            time.sleep(0.03)
        return c

    with tracing.span("parent") as parent:
        with ThreadPoolExecutor(2) as pool:
            futs = [pool.submit(contextvars.copy_context().run, work)
                    for _ in range(2)]
            kids = [f.result(timeout=10) for f in futs]
    # two children side by side cover their union, not their sum
    covered = (max(k.end for k in kids) - min(k.start for k in kids)) * 1e3
    return parent, kids, covered


@pytest.mark.parametrize("shape", [_sequential, _pool_thread,
                                   _overlapping_pool_threads])
def test_parent_links_and_self_time(shape):
    parent, kids, covered_ms = shape()
    assert parent.parent is None
    for k in kids:
        assert k.parent is parent
        assert k.trace_id == parent.trace_id
        assert parent.start <= k.start and k.end <= parent.end
    assert abs(parent.self_ms - (parent.ms - covered_ms)) < 1.0
    assert parent.self_ms >= 0
    # outside any span again: the contextvar was restored
    assert tracing.current_span.get() is None


def test_span_outside_a_request_reports_to_no_ring(server):
    before = len(server.tracer.finished())
    with tracing.span("plan") as sp:
        pass
    assert sp.tracer is None
    assert len(server.tracer.finished()) == before


# ----------------------------------------------- one served Count, its tree

HTTP_SPANS = {"http.request", "http.read", "http.admit", "pql.parse",
              "http.encode", "http.write", "executor.Count", "plan",
              "leaves", "reduce"}

COUNTS = [
    # sparse operands: the eager hybrid path, launched and fetched by the
    # request's own thread
    ("hybrid", "Count(Intersect(Row(f={a}), Row(f={b})))", (0, 1),
     HTTP_SPANS | {"dispatch", "device.wait"}),
    # a first touch: the leaf is built and uploaded under `leaves`
    ("first-touch", "Count(Union(Row(f={a}), Row(f={b})))", (8, 9),
     HTTP_SPANS | {"dispatch", "device.wait", "leaf.build", "leaf.upload"}),
    # two dense planes: the continuous batcher; this request leads its
    # batch, so the launch and the fetch nest under its wait
    ("batched", "Count(Intersect(Row(f={a}), Row(f={b})))", (100, 101),
     HTTP_SPANS | {"batcher.wait", "dispatch", "device.wait"}),
]


@pytest.mark.parametrize("case,pql,rows,want", COUNTS,
                         ids=[c[0] for c in COUNTS])
def test_served_count_yields_one_tree(server, case, pql, rows, want):
    trace_id = f"spans-{case}"
    out = post(server.uri, "/index/i/query",
               pql.format(a=rows[0], b=rows[1]).encode(),
               headers={tracing.TRACE_HEADER: trace_id})
    assert out["results"][0] > 0
    spans = tree_of(server, trace_id)
    names = {sp.name for sp in spans}
    assert want <= names, want - names
    roots = [sp for sp in spans if sp.parent is None]
    assert [r.name for r in roots] == ["http.request"]
    root = roots[0]
    by_id = {id(sp) for sp in spans}
    for sp in spans:
        assert sp.trace_id == trace_id
        assert sp.parent is None or id(sp.parent) in by_id
        assert sp.end is not None and sp.self_ms >= -1e-6
    # self times partition the request: no stage is counted twice, and
    # what no stage span owns is the self time of the spans above it
    assert abs(sum(sp.self_ms for sp in spans) - root.ms) < 1.0
    # the stages under the call are the call's children
    call = next(sp for sp in spans if sp.name == "executor.Count")
    assert call.parent is root
    for name in ("plan", "leaves", "reduce"):
        assert any(sp.parent is call for sp in spans if sp.name == name)
    launch = next(sp for sp in spans if sp.name == "dispatch")
    assert launch.tags["dispatches"] >= 1
    wait = next(sp for sp in spans if sp.name == "device.wait")
    assert wait.parent is launch.parent and wait.start >= launch.end
    if case == "batched":
        assert launch.parent.name == "batcher.wait"
    if case == "first-touch":
        up = next(sp for sp in spans if sp.name == "leaf.upload")
        assert up.parent.name == "leaves" and up.tags["bytes"] > 0
        assert up.tags["rep"] == "sparse"


# stage spans under the calls that are not Count, with Count's names
OTHER_CALLS = [
    # the filter's plan / leaves / dispatch, the rank caches' merge, then
    # the recount: the dense rows 100-101 as stacked planes, rows 0-9 in
    # one launch from the field's pairs entry (built on this first touch)
    ("TopN", "TopN(f, Row(f=100), n=20)",
     {"plan", "leaves", "dispatch", "device.wait", "reduce",
      "topn.candidates", "topn.recount", "leaf.build", "pairs.build"},
     "topn.recount"),
    ("GroupBy", "GroupBy(Rows(field=f), filter=Row(f=101))",
     {"plan", "leaves", "dispatch", "device.wait", "reduce"}, None),
]


@pytest.mark.parametrize("call,pql,want,recount", OTHER_CALLS,
                         ids=[c[0] for c in OTHER_CALLS])
def test_served_topn_and_groupby_have_stage_spans(server, call, pql, want,
                                                  recount):
    trace_id = f"spans-{call}"
    out = post(server.uri, "/index/i/query", pql.encode(),
               headers={tracing.TRACE_HEADER: trace_id})
    assert out["results"][0]
    spans = tree_of(server, trace_id)
    names = {sp.name for sp in spans}
    assert want <= names, want - names
    root = next(sp for sp in spans if sp.parent is None)
    assert abs(sum(sp.self_ms for sp in spans) - root.ms) < 1.0
    top = next(sp for sp in spans if sp.name == f"executor.{call}")
    for sp in spans:
        if sp.name in ("dispatch", "device.wait"):
            # under the call itself or under its recount, never loose
            assert sp.parent is top or sp.parent.name == recount
    if recount:
        walk = next(sp for sp in spans if sp.name == recount)
        assert walk.parent is top and walk.tags["field"] == "f"
        build = next(sp for sp in spans if sp.name == "pairs.build")
        assert build.parent.name == "leaf.build"
        assert sum(sp.launches for sp in spans
                   if sp.name == "dispatch" and sp.parent is walk) >= 2


def test_non_work_routes_are_not_http_request(server):
    before = tracing.spans.snapshot()["byName"]
    get(server.uri, "/status")
    deadline = time.time() + 10
    while time.time() < deadline:
        after = tracing.spans.snapshot()["byName"]
        if after.get("http.other", {}).get("n", 0) \
                > before.get("http.other", {}).get("n", 0):
            break
        time.sleep(0.01)
    assert after["http.other"]["n"] > before.get("http.other",
                                                 {}).get("n", 0)
    assert after["http.request"]["n"] == before["http.request"]["n"]


def test_profile_carries_the_stage_tree(server):
    out = post(server.uri, "/index/i/query?profile=true",
               b"Count(Difference(Row(f=2), Row(f=3)))")
    stages = out["profile"]["stages"]
    by_id = {st["id"]: st for st in stages}
    call = next(st for st in stages if st["name"] == "executor.Count")
    kids = [st for st in stages if st["parent"] == call["id"]]
    assert {"plan", "leaves", "dispatch", "device.wait", "reduce"} \
        <= {st["name"] for st in kids}
    for st in stages:
        assert st["startMs"] >= 0 and st["ms"] >= st["selfMs"] - 1e-3
        assert st["parent"] in by_id or st is call
    # the exporter's view of the same profile: real start times and the
    # stage tree's own parent links
    recs = tracing.profile_to_spans(out["profile"])
    root = next(r for r in recs if r["operationName"] == "pilosa.query")
    rec_call = next(r for r in recs
                    if r["operationName"] == "executor.Count")
    assert rec_call["parentSpanID"] == root["spanID"]
    wait = next(r for r in recs if r["operationName"] == "device.wait")
    assert wait["parentSpanID"] == rec_call["spanID"]
    assert wait["startTimeMicros"] > root["startTimeMicros"]
    assert not any(r["operationName"].startswith("call.") for r in recs)


# ------------------------------------------------- sink a: the span table


@pytest.mark.parametrize("name", sorted(
    HTTP_SPANS | {"dispatch", "device.wait", "leaf.build", "leaf.upload"}))
def test_debug_vars_and_metrics_carry_the_span(server, name):
    post(server.uri, "/index/i/query",
         b"Count(Intersect(Row(f=4), Row(f=5)))")
    block = json.loads(get(server.uri, "/debug/vars"))["spans"]
    assert block["enabled"] is True and block["nowMs"] > 0
    e = block["byName"][name]
    assert e["n"] >= 1 and e["wallMs"] >= e["selfMs"] - 1e-6
    assert e["cpuMs"] >= 0 and sum(e["buckets"].values()) == e["n"]
    text = get(server.uri, "/metrics").decode()
    assert "# TYPE pilosa_spanMs histogram" in text
    count = next(ln for ln in text.splitlines()
                 if ln.startswith(f'pilosa_spanMs_count{{span="{name}"}}'))
    assert int(count.split()[-1]) >= e["n"]


def test_telemetry_switch_leaves_the_table_empty(server, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_TELEMETRY", "0")
    saved = tracing.spans
    monkeypatch.setattr(tracing, "spans", tracing.SpanStats())
    try:
        out = post(server.uri, "/index/i/query",
                   b"Count(Intersect(Row(f=6), Row(f=7)))",
                   headers={tracing.TRACE_HEADER: "spans-switched-off"})
        assert out["results"][0] > 0
        # the ring still has the tree; the table has nothing
        assert tree_of(server, "spans-switched-off")
        snap = tracing.spans.snapshot()
        assert snap["byName"] == {} and snap["enabled"] is False
    finally:
        assert tracing.spans is not saved


# --------------------------------------------------- sink c: one ring a node


def test_two_servers_keep_separate_rings(server, tmp_path):
    other = Server(str(tmp_path / "other"), port=0).open()
    try:
        load(other)
        post(server.uri, "/index/i/query", b"Count(Row(f=1))",
             headers={tracing.TRACE_HEADER: "ring-first"})
        post(other.uri, "/index/i/query", b"Count(Row(f=1))",
             headers={tracing.TRACE_HEADER: "ring-second"})
        assert tree_of(server, "ring-first")
        assert tree_of(other, "ring-second")
        assert not [sp for sp in server.tracer.finished()
                    if sp.trace_id == "ring-second"]
        assert not [sp for sp in other.tracer.finished()
                    if sp.trace_id == "ring-first"]
    finally:
        other.close()


def test_ring_is_bounded():
    t = tracing.Tracer(limit=3)
    for i in range(5):
        with t.start_span(f"s{i}"):
            pass
    assert [sp.name for sp in t.finished()] == ["s2", "s3", "s4"]


# --------------------------------------- sink b: the device trace's clock


def _drive(server, stop, rows):
    a, b = rows
    while not stop.is_set():
        post(server.uri, "/index/i/query",
             f"Count(Intersect(Row(f={a}), Row(f={b})))".encode())


def test_capture_holds_the_spans_on_its_clock(server, tmp_path, monkeypatch):
    import jax
    from jax.profiler import ProfileData

    made = []
    real = jax.profiler.TraceAnnotation

    def counting(*a, **kw):
        made.append(a[0])
        return real(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    # every request launches: a result-cache hit has no dispatch to show
    monkeypatch.setattr(server.executor.plan_cache, "enabled", False)
    post(server.uri, "/index/i/query",
         b"Count(Intersect(Row(f=0), Row(f=1)))")
    assert made == [] and not tracing.capturing()

    stop = threading.Event()
    clients = [threading.Thread(target=_drive, args=(server, stop, rows))
               for rows in ((0, 1), (2, 3))]
    for c in clients:
        c.start()
    try:
        prof = telemetry.DeviceProfiler(spool_dir=str(tmp_path / "spool"))
        doc = prof.capture(0.3)
    finally:
        stop.set()
        for c in clients:
            c.join(timeout=30)
    assert not any(c.is_alive() for c in clients)
    assert doc["status"] == "ok", doc
    assert not tracing.capturing()
    n_made = len(made)
    assert n_made > 0
    post(server.uri, "/index/i/query",
         b"Count(Intersect(Row(f=0), Row(f=1)))")
    assert len(made) == n_made  # none constructed outside a capture

    path = glob.glob(doc["dir"] + "/**/*.xplane.pb", recursive=True)[0]
    space = ProfileData.from_file(path)
    env = next(dict(p.stats) for p in space.planes
               if p.name == "Task Environment")
    window_ns = env["profile_stop_time"] - env["profile_start_time"]
    assert window_ns > 0
    host = next(p for p in space.planes if p.name == "/host:CPU")
    found: dict = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith("pilosa."):
                found.setdefault(ev.name, []).append(
                    (line.name, ev.start_ns, ev.duration_ns,
                     dict(ev.stats)))
    for name in ("pilosa.dispatch", "pilosa.device.wait",
                 "pilosa.http.request", "pilosa.leaves"):
        assert found.get(name), sorted(found)
        for _, start_ns, dur_ns, stats in found[name]:
            assert stats.get("trace_id")
            # on the capture's own clock: inside its window (event times
            # count from profile_start_time)
            if start_ns >= env["profile_start_time"]:
                start_ns -= env["profile_start_time"]
            assert 0 <= start_ns <= window_ns
            assert dur_ns >= 0
    # one trace id joins a request's dispatch to its wait
    ids = {st["trace_id"] for *_, st in found["pilosa.dispatch"]}
    assert ids & {st["trace_id"] for *_, st in found["pilosa.device.wait"]}


@pytest.mark.parametrize("broken", ["start_trace", "stop_trace"])
def test_flag_is_clear_after_a_capture_that_raised(tmp_path, monkeypatch,
                                                   broken):
    import jax

    def boom(*a, **kw):
        raise RuntimeError("profiler refused")

    if broken == "stop_trace":
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda *a, **kw: None)
    monkeypatch.setattr(jax.profiler, broken, boom)
    prof = telemetry.DeviceProfiler(spool_dir=str(tmp_path / "spool"))
    doc = prof.capture(0.05)
    assert doc["status"] == "error" and "refused" in doc["error"]
    assert not tracing.capturing()
    assert prof.snapshot()["busy"] is False
