"""Distributed query profiler (utils/profile.py): the ?profile=true tree,
cross-node fragment assembly over QueryResponse.Profile, per-entry trace
propagation through coalesced envelopes, the structured slow-query history,
and the profile_mode / kill-switch gates.

Unit tests drive QueryProfile and the coalescer entry encoding directly;
the integration tests run a REAL 3-node cluster (pinned node ids, the
test_coalesce fixture recipe) and assert the acceptance shape: per-node
RPC timings for every remote shard group, a device-dispatch record with
batch_size >= 1, residency hit/miss counts, and remote fragments — plus
mixed-version degradation to a coordinator-only tree."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.utils import profile as qprofile

SW = SHARD_WIDTH


# ------------------------------------------------------------------- unit


def test_query_profile_records_and_serializes():
    p = qprofile.QueryProfile(trace_id="t1", node_id="a", index="i",
                              pql="Count(Row(f=1))")
    p.record_call("Count", 12.5)
    p.record_fanout("b", 3, 9.5, "coalesced")
    p.record_hedge("b", "c", won=True)
    p.record_retry("d", 2, "ConnectionError: boom")
    p.record_dispatch("CountBatcher", 7, 4, 8.0)
    p.record_residency(hit=True)
    p.record_residency(hit=False, nbytes=1024)
    p.add_remote_fragment("http://b:1", {"node": "b", "calls": []})
    p.finish()
    d = p.to_dict()
    assert d["traceId"] == "t1" and d["node"] == "a"
    assert d["calls"] == [{"call": "Count", "ms": 12.5}]
    assert d["fanout"][0]["transport"] == "coalesced"
    assert d["fanout"][1] == {"node": "b", "hedgeNode": "c",
                              "kind": "hedge", "hedgeWon": True}
    assert d["fanout"][2]["kind"] == "failover"
    disp = d["dispatches"][0]
    assert disp["batchSize"] == 4 and disp["shareMs"] == 2.0
    assert d["residency"] == {"hits": 1, "misses": 1,
                              "hostToDeviceBytes": 1024}
    assert d["remoteProfiles"][0]["node"] == "http://b:1"
    assert d["elapsedMs"] >= 0
    json.dumps(d)  # the tree must be JSON-clean as-is


def test_truncate_pql_and_history_ring():
    assert qprofile.truncate_pql("short") == "short"
    long = "Set(" + "1" * 500 + ")"
    out = qprofile.truncate_pql(long, limit=64)
    assert len(out) == 64 and out.endswith("...")
    h = qprofile.QueryHistory(size=3)
    for i in range(5):
        h.append({"i": i})
    snap = h.snapshot()
    assert [e["i"] for e in snap] == [4, 3, 2]  # newest first, bounded


def test_invalid_profile_mode_fails_boot(tmp_path):
    from pilosa_tpu.server import Server
    with pytest.raises(ValueError, match="profile mode"):
        Server(str(tmp_path / "bad"), port=0, profile_mode="On")


def test_nop_fast_path_default():
    # with no profile installed, every instrumentation site reads None
    assert qprofile.current_profile.get() is None
    assert qprofile.current() is None


def test_finish_seals_against_late_records():
    """A discarded hedge loser's RPC can land AFTER the response was
    serialized; finish() seals the profile so every surface (response,
    history, wire fragment) sees one deterministic tree."""
    p = qprofile.QueryProfile(trace_id="t", node_id="a")
    p.record_fanout("b", 2, 5.0, "coalesced")
    p.finish()
    d1 = p.to_dict()
    p.record_fanout("c", 1, 99.0, "proto")  # late loser: dropped
    p.record_call("Count", 1.0)
    p.record_dispatch("CountBatcher", 1, 1, 1.0)
    p.record_residency(hit=True)
    p.add_remote_fragment("http://c:1", {})
    d2 = p.to_dict()
    assert d1 is d2  # sealed tree memoizes: one serialization, identical
    assert len(d2["fanout"]) == 1 and d2["fanout"][0]["node"] == "b"
    assert d2["calls"] == [] and d2["dispatches"] == []
    assert d2["remoteProfiles"] == []


def test_coalescer_entries_carry_trace_and_profile_flags():
    """Per-entry trace id mirrors the per-entry deadline: the envelope
    must carry each caller's OWN trace id and profile request, and
    deduped followers must not erase the first caller's trace."""
    from tests.test_coalesce import FakeClient
    from pilosa_tpu.net.coalesce import NodeCoalescer

    fc = FakeClient()
    co = NodeCoalescer(fc, window_s=0.0)
    co._compute(("http://n1:1",), [
        ("idx", "q1", None, None, "trace-A", True, "key:a", "batch"),
        ("idx", "q2", None, 1.5, None, False, None, None),
        # dedup of q1: later caller must not erase the first trace, and
        # its more urgent class upgrades the shared execution
        ("idx", "q1", None, None, "trace-B", False, "key:b",
         "interactive"),
    ])
    entries = fc.batch_calls[0]
    assert len(entries) == 2  # q1 deduped
    e1 = next(e for e in entries if e["query"] == "q1")
    e2 = next(e for e in entries if e["query"] == "q2")
    assert e1["traceId"] == "trace-A"  # first caller's trace wins
    assert e1["profile"] is True  # any profiled dup profiles the execution
    assert e1["priority"] == "interactive"  # most urgent dup wins
    assert "traceId" not in e2 and "profile" not in e2
    assert "priority" not in e2
    assert e2["timeout"] == 1.5


def test_query_batch_installs_per_entry_trace(tmp_path):
    """The remote side of satellite 1: api.query_batch installs each
    entry's traceId via tracing.current_trace_id before executing, so
    remote spans join the coordinator's trace instead of minting one."""
    from pilosa_tpu.server import Server

    s = Server(str(tmp_path / "n"), port=0).open()
    try:
        jpost(s.uri, "/index/i", {})
        jpost(s.uri, "/index/i/field/f", {})
        jpost(s.uri, "/index/i/query", raw=b"Set(5, f=1)")
        # the API is entered below the HTTP layer, which is what installs
        # the node's tracer for a request
        from pilosa_tpu.utils import tracing
        tok = tracing.current_tracer.set(s.tracer)
        try:
            out = s.api.query_batch([
                {"index": "i", "query": "Count(Row(f=1))", "remote": True,
                 "traceId": "envelope-trace-1"},
                {"index": "i", "query": "Count(Row(f=1))", "remote": True,
                 "traceId": "envelope-trace-2"},
            ])
        finally:
            tracing.current_tracer.reset(tok)
        assert [r for r, *_ in out] == [[1], [1]]
        got = {sp.trace_id for sp in s.tracer.finished("executor.Count")}
        # BOTH entries' spans carry their own caller's trace id — the
        # pre-fix behavior gave every entry the envelope leader's trace
        assert {"envelope-trace-1", "envelope-trace-2"} <= got, got
    finally:
        s.close()


# ------------------------------------------------------------ integration


def jpost(uri, path, payload=None, raw=None):
    body = raw if raw is not None else json.dumps(payload or {}).encode()
    req = urllib.request.Request(uri + path, data=body, method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def jget(uri, path):
    with urllib.request.urlopen(uri + path, timeout=30) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """3-node cluster with PINNED node ids (the test_coalesce recipe): the
    jump-hash placement is deterministic, so fan-out from node 0 reaches
    both remote nodes on every run."""
    from pilosa_tpu.server import Server

    tmp = tmp_path_factory.mktemp("profcluster")
    servers = [Server(str(tmp / f"n{i}"), port=0,
                      node_id=chr(ord("a") + i)).open()
               for i in range(3)]
    uris = [s.uri for s in servers]
    for s in servers:
        s.cluster_hosts = uris
        s.refresh_membership()
    u = uris[0]
    jpost(u, "/index/i", {})
    jpost(u, "/index/i/field/f", {})
    rng = np.random.default_rng(61)
    cols = np.unique(rng.choice(6 * SW, 3000))
    half = cols.size // 2
    jpost(u, "/index/i/field/f/import",
          {"rowIDs": [0] * half + [1] * (cols.size - half),
           "columnIDs": cols.tolist()})
    # wait for cross-node shard visibility (async create-shard announce)
    q = b"Count(Union(Row(f=0), Row(f=1)))"
    deadline = time.monotonic() + 30
    for uri in uris:
        while jpost(uri, "/index/i/query", raw=q)["results"][0] != cols.size:
            assert time.monotonic() < deadline
            time.sleep(0.2)
    yield servers, uris
    for s in servers:
        s.close()


def test_distributed_profile_tree_acceptance_shape(cluster):
    """The acceptance query: ?profile=true on a 3-node cluster returns a
    tree with per-node RPC timings for every remote shard group, a device
    dispatch record with batch_size >= 1 + residency counts, and remote
    fragments assembled from the QueryResponse.Profile protobuf field."""
    servers, uris = cluster
    # plan cache off for this test: a warm repeat would be served from the
    # cached Count scalar with (correctly) zero dispatches and zero
    # residency lookups — this test asserts the attribution plumbing
    # underneath the cache
    for s in servers:
        s.executor.plan_cache.enabled = False
    try:
        # run twice: the second profile sees warm residency (hits) while
        # the assertions stay valid for both
        jpost(uris[0], "/index/i/query?profile=true", raw=b"Count(Row(f=0))")
        out = jpost(uris[0], "/index/i/query?profile=true",
                    raw=b"Count(Row(f=0))")
    finally:
        for s in servers:
            s.executor.plan_cache.enabled = True
    prof = out["profile"]
    assert prof["traceId"] and prof["node"] == "a"
    assert prof["calls"] and prof["calls"][0]["call"] == "Count"

    # per-node RPC timings for every remote shard group the planner built
    groups = servers[0].cluster.shards_by_node(
        "i", servers[0].executor._query_shards(
            servers[0].holder.index("i"), None))
    remote_ids = {nid for nid in groups if nid != "a"}
    assert remote_ids  # pinned ids split ownership — fan-out must exist
    timed = {f["node"] for f in prof["fanout"]
             if "ms" in f and f.get("transport") != "local"}
    assert remote_ids <= timed, (remote_ids, prof["fanout"])
    for f in prof["fanout"]:
        if "ms" in f:
            assert f["ms"] >= 0 and f["shards"] >= 1

    # device dispatch attribution with the batch size this query shared
    assert any(d["batchSize"] >= 1 and d["wallMs"] >= 0
               for d in prof["dispatches"]), prof["dispatches"]
    # residency hit/miss counts (warm run: the leaf is HBM-resident)
    res = prof["residency"]
    assert res["hits"] + res["misses"] >= 1

    # remote fragments: one per remote node, carried in the protobuf
    # field (through the coalesced envelope's per-entry slots here)
    frag_nodes = {r["profile"]["node"] for r in prof["remoteProfiles"]}
    assert remote_ids <= frag_nodes, (remote_ids, frag_nodes)
    # remote spans of this query joined the coordinator's trace
    for r in prof["remoteProfiles"]:
        assert r["profile"]["traceId"] == prof["traceId"]
        assert r["profile"]["calls"]
        # batch entries profile the RAW PQL, not a parsed Query repr
        assert r["profile"]["pql"] == "Count(Row(f=0))", r["profile"]["pql"]


def test_remote_spans_join_coordinator_trace_through_envelope(cluster):
    """Satellite 1 end-to-end: remote executor spans of a coalesced
    distributed query carry the coordinator's trace id."""
    servers, uris = cluster
    req = urllib.request.Request(
        uris[0] + "/index/i/query", data=b"Count(Row(f=1))", method="POST",
        headers={"X-Pilosa-Trace-Id": "prof-trace-join"})
    with urllib.request.urlopen(req, timeout=30) as r:
        json.loads(r.read())
    remote_hits = [
        s.node_id for s in servers[1:]
        if any(sp.trace_id == "prof-trace-join"
               for sp in s.tracer.finished("executor.Count"))]
    assert remote_hits, "no remote span joined the coordinator's trace"


def test_mixed_version_legacy_peer_degrades_to_coordinator_only(cluster):
    """A peer that sends no Profile fragment (legacy binary / profiling
    off) must degrade the tree, not the query: results stay correct, the
    coordinator's own attribution is intact, and only that node's child
    is missing."""
    servers, uris = cluster
    old_mode = servers[1].api.profile_mode
    servers[1].api.profile_mode = "off"  # behaves like a legacy peer:
    # QueryRequest.Profile is ignored, QueryResponse.Profile stays absent
    try:
        out = jpost(uris[0], "/index/i/query?profile=true",
                    raw=b"Count(Row(f=0))")
        prof = out["profile"]
        assert out["results"][0] > 0
        frag_nodes = {r["profile"]["node"] for r in prof["remoteProfiles"]}
        assert "b" not in frag_nodes  # the legacy peer contributed nothing
        # the coordinator still timed node b's RPC (attribution survives)
        assert any(f.get("node") == "b" and "ms" in f
                   for f in prof["fanout"]), prof["fanout"]
    finally:
        servers[1].api.profile_mode = old_mode


def test_profile_mode_off_and_kill_switch(cluster):
    servers, uris = cluster
    api = servers[0].api
    old = api.profile_mode
    try:
        api.profile_mode = "off"
        out = jpost(uris[0], "/index/i/query?profile=true",
                    raw=b"Count(Row(f=0))")
        assert "profile" not in out
        api.profile_mode = "auto"
        api._profile_killed = True  # PILOSA_TPU_PROFILE=0 at boot
        out = jpost(uris[0], "/index/i/query?profile=true",
                    raw=b"Count(Row(f=0))")
        assert "profile" not in out
    finally:
        api.profile_mode = old
        api._profile_killed = False
    # and without the flag, no profile rides the response
    out = jpost(uris[0], "/index/i/query", raw=b"Count(Row(f=0))")
    assert "profile" not in out


def test_proto_query_path_carries_profile(cluster):
    """The protobuf codec path: QueryRequest.Profile in,
    QueryResponse.Profile out (what remote nodes speak)."""
    from pilosa_tpu.encoding.protobuf import CONTENT_TYPE, Serializer
    servers, uris = cluster
    s = Serializer()
    body = s.encode_query_request("Count(Row(f=0))", profile=True)
    req = urllib.request.Request(
        uris[0] + "/index/i/query", data=body, method="POST",
        headers={"Content-Type": CONTENT_TYPE, "Accept": CONTENT_TYPE})
    with urllib.request.urlopen(req, timeout=30) as r:
        resp = s.decode_query_response(r.read())
    assert resp["err"] == ""
    assert resp["profile"] is not None
    assert resp["profile"]["calls"][0]["call"] == "Count"


def test_slow_query_history_and_truncated_log_line(cluster):
    """Satellite 2 + the history surface: queries over long-query-time
    land in /debug/query-history with trace id, truncated PQL, elapsed
    and profile; the log line truncates the PQL and appends trace=<id>."""
    import io
    from pilosa_tpu.utils.logger import Logger

    servers, uris = cluster
    api = servers[0].api
    buf = io.StringIO()
    old_logger, old_lqt = api.logger, api.long_query_time
    api.logger = Logger(out=buf)
    api.long_query_time = 1e-9  # everything is slow
    try:
        # a PQL long enough to need truncation (batched Sets pad it)
        pql = "Count(Union(" + ", ".join(
            f"Row(f={i})" for i in range(60)) + "))"
        assert len(pql) > 256
        jpost(uris[0], "/index/i/query", raw=pql.encode())
        hist = jget(uris[0], "/debug/query-history")["queries"]
        assert hist, "slow query never reached the history ring"
        e = hist[0]
        assert e["pql"].endswith("...") and len(e["pql"]) <= 256
        assert e["traceId"] and e["traceId"] != "-"
        assert e["elapsed"] > 0
        # auto mode + long-query-time set => the entry carries a profile
        assert e["profile"] is not None
        assert e["profile"]["traceId"] == e["traceId"]
        line = buf.getvalue()
        assert "SLOW QUERY" in line
        assert f"trace={e['traceId']}" in line
        assert pql not in line  # raw unbounded PQL never hits the log
    finally:
        api.logger, api.long_query_time = old_logger, old_lqt


def test_history_ring_is_bounded(cluster):
    servers, uris = cluster
    api = servers[0].api
    old_size, old_lqt = api.query_history.size, api.long_query_time
    api.query_history.size = 3
    api.long_query_time = 1e-9
    try:
        for i in range(6):
            jpost(uris[0], "/index/i/query", raw=b"Count(Row(f=0))")
        hist = jget(uris[0], "/debug/query-history")["queries"]
        assert len(hist) == 3
    finally:
        api.query_history.size = old_size
        api.long_query_time = old_lqt


def test_profiled_queries_answer_identically_under_concurrency(cluster):
    """Profiling must be an observer: concurrent profiled + unprofiled
    queries (coalescing + device batching active) return identical
    results, and each profiled response carries its own tree."""
    servers, uris = cluster
    expect = jpost(uris[0], "/index/i/query",
                   raw=b"Count(Row(f=0))")["results"][0]
    errs = []

    def go(i):
        try:
            path = "/index/i/query" + ("?profile=true" if i % 2 else "")
            out = jpost(uris[0], path, raw=b"Count(Row(f=0))")
            assert out["results"][0] == expect
            if i % 2:
                assert out["profile"]["calls"][0]["call"] == "Count"
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=go, args=(i,)) for i in range(10)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
