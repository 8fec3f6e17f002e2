"""Multi-tenant QoS plane (pilosa_tpu/qos.py): quotas, priorities,
deadline-aware admission and load shedding.

Unit layers: token-bucket semantics, priority resolution, the priority
pool's ordering, QosPlane verdicts per mode (off/observe/enforce) and
the batcher's priority-ordered cut. Live layers: a single enforce-mode
server throttling one principal with `429 + Retry-After` while a
quota'd VIP sails through, observe-mode counting without rejecting, the
env kill switch, and a 3-node cluster proving the deadline budget
shrinks as it fans out — and that an entry arriving expired is shed
remotely before any device dispatch.
"""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu import qos
from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.qos import (
    PriorityPool,
    QosPlane,
    Rejection,
    TokenBucket,
)

SW = SHARD_WIDTH


# ------------------------------------------------------------------ buckets


def test_token_bucket_refill_and_debt():
    b = TokenBucket(rate=10.0, burst=20.0)
    t0 = time.monotonic()
    assert b.wait_for(1.0, now=t0) == 0.0
    b.take(20.0, now=t0)  # drain the whole burst
    assert b.wait_for(1.0, now=t0) == pytest.approx(0.1, abs=1e-6)
    # ledger feedback can push into debt; the wait scales with the debt
    b.take(30.0, now=t0)
    assert b.wait_for(0.0, now=t0) == pytest.approx(3.0, abs=1e-6)
    # refill is linear in elapsed time and capped at burst
    assert b.wait_for(0.0, now=t0 + 3.0) == 0.0
    b2 = TokenBucket(rate=10.0, burst=20.0)
    b2.take(1.0, now=t0)
    b2._refill(t0 + 100.0)
    assert b2.tokens == 20.0  # never exceeds burst


def test_zero_rate_bucket_reports_cap_wait():
    b = TokenBucket(rate=0.0, burst=0.0)
    b.take(1.0)
    assert b.wait_for(0.0) == qos.RETRY_AFTER_MAX_S


# ----------------------------------------------------------------- priority


def test_priority_levels_and_defaults():
    assert qos.priority_level("interactive") == 0
    assert qos.priority_level("batch") == 1
    assert qos.priority_level("internal") == 2
    # unknown / untagged sorts as internal: background work must never
    # queue ahead of tagged user traffic
    assert qos.priority_level(None) == 2
    assert qos.priority_level("garbage") == 2
    assert qos.current_level() == 2  # no contextvar installed


def test_priority_for_header_override_default():
    plane = QosPlane(mode="off", default_priority="interactive",
                     principals={"key:etl": {"priority": "batch"}})
    assert plane.priority_for("batch", "key:x") == "batch"
    assert plane.priority_for(" Interactive ", "key:etl") == "interactive"
    assert plane.priority_for(None, "key:etl") == "batch"  # override
    assert plane.priority_for("nonsense", "key:x") == "interactive"
    assert plane.priority_for(None, "key:x") == "interactive"


def test_plane_validates_config():
    with pytest.raises(ValueError):
        QosPlane(mode="enfroce")
    with pytest.raises(ValueError):
        QosPlane(default_priority="vip")
    with pytest.raises(ValueError):
        QosPlane(principals={"k": {"priority": "vip"}})
    with pytest.raises(ValueError):
        QosPlane(principals={"k": {"queries-per-sec": 1}})  # typo'd key
    # hyphenated TOML keys normalize
    p = QosPlane(principals={"k": {"queries-per-s": 5, "priority": "batch"}})
    assert p.overrides["k"] == {"queries_per_s": 5, "priority": "batch"}


def test_priority_pool_orders_by_class_under_saturation():
    import threading
    pool = PriorityPool(1, "t")
    release = threading.Event()
    order = []
    try:
        blocker = pool.submit(release.wait, 5.0)  # occupies the worker
        # queue three classes in reverse-priority submit order
        futs = []
        for name in ("internal", "batch", "interactive"):
            tok = qos.current_priority.set(name)
            try:
                futs.append(pool.submit(order.append, name))
            finally:
                qos.current_priority.reset(tok)
        release.set()
        for f in futs:
            f.result(timeout=5)
        assert blocker.result(timeout=5)
        assert order == ["interactive", "batch", "internal"]
    finally:
        pool.shutdown(wait=False, cancel_futures=True)


def test_priority_pool_delivers_exceptions_and_shutdown_cancels():
    pool = PriorityPool(2, "t")
    def boom():
        raise RuntimeError("boom")
    f = pool.submit(boom)
    with pytest.raises(RuntimeError):
        f.result(timeout=5)
    pool.shutdown(wait=True, cancel_futures=True)
    with pytest.raises(RuntimeError):
        pool.submit(lambda: 1)


def test_batcher_cut_is_priority_ordered():
    """When the pending queue overflows one batch, the cut takes the
    most urgent requests first (stable within a class)."""
    from pilosa_tpu.parallel.batcher import ContinuousBatcher, _Req

    seen = []

    class Rec(ContinuousBatcher):
        def _compute(self, key, payloads):
            seen.append(list(payloads))
            return payloads

    b = Rec(max_batch=2)
    b.admission_s = 0.0
    key = ("k",)
    reqs = []
    for payload, prio in (("bat1", 1), ("int1", 0), ("bat2", 1),
                          ("int2", 0)):
        r = _Req(payload)
        r.priority = prio
        reqs.append(r)
    b._pending[key] = list(reqs)
    b._serve_one_batch(key)
    assert seen[0] == ["int1", "int2"]  # interactive rode the first cut
    b._serve_one_batch(key)
    assert seen[1] == ["bat1", "bat2"]
    assert all(r.done for r in reqs)


# -------------------------------------------------------------- plane logic


class _FakeLedger:
    def __init__(self):
        self.entries = {}

    def peek(self, principal):
        return self.entries.get(principal)


def test_plane_mode_off_admits_everything():
    plane = QosPlane(mode="off", queries_per_s=0.001)
    for _ in range(50):
        assert plane.admit("p", "interactive", None) is None
    assert plane.totals()["admitted"] == 0  # off = not even counted


def test_plane_enforce_qps_quota_and_observe_mode():
    plane = QosPlane(mode="enforce", queries_per_s=2.0, burst_s=1.0)
    verdicts = [plane.admit("key:a", "interactive", None)
                for _ in range(5)]
    rejected = [v for v in verdicts if v is not None]
    assert len(rejected) == 3
    assert all(v.status == 429 and v.reason == "queriesPerS"
               and v.retry_after > 0 for v in rejected)
    assert plane.admitted["interactive"] == 2
    assert plane.throttled["queriesPerS"] == 3
    # a different principal has its own bucket
    assert plane.admit("key:b", "interactive", None) is None
    # observe mode: same decision, nothing rejected
    obs = QosPlane(mode="observe", queries_per_s=2.0, burst_s=1.0)
    assert all(obs.admit("key:a", "interactive", None) is None
               for _ in range(5))
    assert obs.would_throttled["queriesPerS"] == 3
    assert obs.throttled["queriesPerS"] == 0


def test_plane_ledger_feedback_throttles_device_spend():
    """Device-ms quota charges the ledger's MEASURED spend between
    requests — a principal that burned device time goes into debt and is
    throttled until the bucket refills."""
    ledger = _FakeLedger()
    plane = QosPlane(mode="enforce", device_ms_per_s=10.0, burst_s=1.0,
                     ledger=ledger)
    ledger.entries["key:a"] = {"deviceMs": 0.0, "rpcBytes": 0,
                               "hbmBytes": 0}
    assert plane.admit("key:a", "interactive", None) is None
    # the principal's queries burned 500 device-ms since admission
    ledger.entries["key:a"]["deviceMs"] = 500.0
    v = plane.admit("key:a", "interactive", None)
    assert v is not None and v.status == 429
    assert v.reason == "deviceMsPerS"
    # debt of ~490ms at 10ms/s -> long wait, capped at the ceiling
    assert v.retry_after == pytest.approx(qos.RETRY_AFTER_MAX_S)


def test_plane_health_red_sheds():
    plane = QosPlane(mode="enforce",
                     health_fn=lambda: {"score": "red", "reasons": []})
    v = plane.admit("p", "interactive", None)
    assert v is not None and v.status == 503 and v.reason == "healthRed"
    assert plane.shed["healthRed"] == 1


def test_plane_estimated_wait_sheds_against_deadline():
    plane = QosPlane(mode="enforce")
    plane.wait_ewma_ms = 500.0
    plane._sig_t = time.monotonic() + 3600  # pin the injected signal
    # 100 ms of budget against a 500 ms estimated wait: shed early
    v = plane.admit("p", "interactive", 0.1)
    assert v is not None and v.status == 503
    assert v.reason == "estimatedWait"
    assert 0 < v.retry_after <= qos.RETRY_AFTER_MAX_S
    # plenty of budget: admitted
    assert plane.admit("p", "interactive", 10.0) is None
    # already expired: shed, not executed
    v = plane.admit("p", "interactive", -0.1)
    assert v is not None and v.reason == "deadline"


def test_plane_bounded_principal_tables():
    plane = QosPlane(mode="enforce", queries_per_s=1000.0,
                     max_principals=4)
    for i in range(50):
        plane.admit(f"key:{i}", "interactive", None)
    assert len(plane._principals) <= 4
    assert len(plane._per_principal) <= 4
    snap = plane.snapshot()
    assert snap["mode"] == "enforce"
    assert sum(snap["admitted"].values()) == 50


def test_rejection_retry_after_is_capped():
    r = Rejection(429, 1e9, "queriesPerS", "m")
    assert r.retry_after == qos.RETRY_AFTER_MAX_S
    assert qos.retry_after_header(0.2) == "1"
    assert qos.retry_after_header(2.4) == "3"


# ------------------------------------------------------------ config plumb


def test_qos_config_toml_roundtrip(tmp_path):
    from pilosa_tpu.cli.config import Config, load_config
    toml = tmp_path / "c.toml"
    toml.write_text(
        '[qos]\nmode = "observe"\ndefault-priority = "batch"\n'
        'default-deadline = "500ms"\nqueries-per-s = 25.0\n'
        '[qos.principals."key:etl"]\npriority = "internal"\n'
        "queries-per-s = 5\n"
        '[gossip]\nsecret = "hush"\n')
    cfg = load_config(str(toml))
    assert cfg.qos.mode == "observe"
    assert cfg.qos.default_priority == "batch"
    assert cfg.qos.default_deadline == pytest.approx(0.5)
    assert cfg.qos.queries_per_s == 25.0
    assert cfg.qos.principals["key:etl"]["priority"] == "internal"
    assert cfg.gossip.secret == "hush"
    # generated TOML parses back to the same qos section
    rendered = Config()
    rendered.qos.mode = "enforce"
    rendered.qos.principals = {"key:x": {"queries-per-s": 9.0}}
    import tomllib

    back = tomllib.loads(rendered.to_toml())
    assert back["qos"]["mode"] == "enforce"
    assert back["qos"]["principals"]["key:x"]["queries-per-s"] == 9.0


def test_env_kill_switch_does_not_clobber_config_section():
    """PILOSA_TPU_QOS=0 is the runtime kill switch, NOT a config path:
    the env merge must leave the [qos] section object intact (and the
    dotted forms like PILOSA_TPU_QOS_MODE must still work)."""
    from pilosa_tpu.cli.config import QosConfig, load_config
    cfg = load_config(environ={"PILOSA_TPU_QOS": "0",
                               "PILOSA_TPU_QOS_MODE": "observe"})
    assert isinstance(cfg.qos, QosConfig)
    assert cfg.qos.mode == "observe"


def test_server_rejects_bad_qos_mode(tmp_path):
    from pilosa_tpu.server import Server
    with pytest.raises(ValueError):
        Server(str(tmp_path / "bad"), port=0, qos_mode="enfroce")


# ---------------------------------------------------------------- live HTTP


def _post(uri, path, body, key=None, hdrs=None):
    h = dict(hdrs or {})
    if key:
        h["X-API-Key"] = key
    req = urllib.request.Request(uri + path, data=body, method="POST",
                                 headers=h)
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


@pytest.fixture()
def enforce_server(tmp_path):
    from pilosa_tpu.server import Server
    srv = Server(str(tmp_path / "q"), port=0, qos_mode="enforce",
                 qos_queries_per_s=2.0, qos_burst=1.0,
                 qos_principals={
                     "key:vip": {"queries-per-s": 100000},
                     "key:etl": {"priority": "batch"}}).open()
    uri = srv.uri
    _post(uri, "/index/t", b"{}", key="vip")
    _post(uri, "/index/t/field/f", b"{}", key="vip")
    _post(uri, "/index/t/query", b"Set(1, f=1)", key="vip")
    yield srv, uri
    srv.close()


def test_http_quota_throttles_with_retry_after(enforce_server):
    srv, uri = enforce_server
    out = [_post(uri, "/index/t/query", b"Count(Row(f=1))", key="flood")
           for _ in range(6)]
    codes = [st for st, _, _ in out]
    assert codes.count(200) == 2  # rate 2/s, burst 1s -> 2 tokens
    rejected = [(st, h, b) for st, h, b in out if st == 429]
    assert len(rejected) == 4
    for st, h, body in rejected:
        assert int(h["Retry-After"]) >= 1
        assert h["X-Pilosa-Shed-Reason"] == "queriesPerS"
        assert json.loads(body)["code"] == "quota-exhausted"
    # the VIP principal's override keeps it unthrottled through the storm
    assert all(_post(uri, "/index/t/query", b"Count(Row(f=1))",
                     key="vip")[0] == 200 for _ in range(10))
    snap = srv.qos.snapshot()
    assert snap["throttled"]["queriesPerS"] == 4
    assert snap["perPrincipal"]["key:flood"]["throttled"] == 4
    # sheds are deliberate backpressure, not server errors: the health
    # score's 5xx input must not see them
    assert srv.handler.errors_5xx == 0


def test_http_doomed_query_shed_by_class_cost(enforce_server):
    """Enforce mode sheds a query whose class's observed device cost
    already exceeds its remaining deadline — 503 + code=shed, before any
    execution."""
    srv, uri = enforce_server
    srv.qos.observe_service("count", 10_000.0)  # counts "cost" 10s
    st, h, body = _post(uri, "/index/t/query?timeout=200ms",
                        b"Count(Row(f=1))", key="vip")
    assert st == 503
    assert json.loads(body)["code"] == "shed"
    assert srv.qos.shed["estimatedCost"] == 1
    srv.qos._class_cost_ms.clear()


def test_http_priority_rides_profile_and_vars(enforce_server):
    srv, uri = enforce_server
    st, _, body = _post(uri, "/index/t/query?profile=true",
                        b"Count(Row(f=1))", key="etl")
    assert st == 200
    prof = json.loads(body)["profile"]
    # the override (not the default) decided the class, and it shows in
    # the profile tree's qos node
    assert prof["qos"]["priority"] == "batch"
    v = json.loads(urllib.request.urlopen(uri + "/debug/vars",
                                          timeout=10).read())
    assert v["qos"]["mode"] == "enforce"
    assert v["qos"]["admitted"]["batch"] >= 1


def test_kill_switch_disables_enforcement(enforce_server, monkeypatch):
    srv, uri = enforce_server
    monkeypatch.setenv("PILOSA_TPU_QOS", "0")
    codes = [_post(uri, "/index/t/query", b"Count(Row(f=1))",
                   key="killswitch")[0] for _ in range(10)]
    assert codes == [200] * 10  # quota would have allowed only 2


def test_observe_mode_counts_without_rejecting(tmp_path):
    from pilosa_tpu.server import Server
    srv = Server(str(tmp_path / "obs"), port=0, qos_mode="observe",
                 qos_queries_per_s=1.0, qos_burst=1.0).open()
    try:
        uri = srv.uri
        _post(uri, "/index/o", b"{}")
        _post(uri, "/index/o/field/f", b"{}")
        _post(uri, "/index/o/query", b"Set(1, f=1)")
        codes = [_post(uri, "/index/o/query", b"Count(Row(f=1))",
                       key="noisy")[0] for _ in range(5)]
        assert codes == [200] * 5  # nothing rejected...
        snap = srv.qos.snapshot()
        assert snap["wouldThrottled"]["queriesPerS"] >= 1  # ...but seen
        assert snap["throttled"]["queriesPerS"] == 0
    finally:
        srv.close()


# ------------------------------------------------------------ 3-node plane


def _jpost(uri, path, payload=None, raw=None):
    body = raw if raw is not None else json.dumps(payload or {}).encode()
    req = urllib.request.Request(uri + path, data=body, method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


@pytest.fixture(scope="module")
def trio(tmp_path_factory):
    """3-node cluster with pinned ids (deterministic placement) and a
    6-shard index so node a's queries genuinely fan out."""
    from pilosa_tpu.server import Server
    tmp = tmp_path_factory.mktemp("qos3")
    servers = [Server(str(tmp / f"n{i}"), port=0, replica_n=1,
                      node_id=chr(ord("a") + i)).open() for i in range(3)]
    uris = [s.uri for s in servers]
    for s in servers:
        s.cluster_hosts = uris
        s.refresh_membership()
    rng = np.random.default_rng(7)
    u = uris[0]
    _jpost(u, "/index/i", {})
    _jpost(u, "/index/i/field/f", {})
    cols = np.unique(rng.choice(6 * SW, 4000))
    _jpost(u, "/index/i/field/f/import",
           {"rowIDs": [0] * cols.size, "columnIDs": cols.tolist()})
    expect = int(cols.size)
    deadline = time.monotonic() + 30
    while True:  # async create-shard announcements must settle
        out = _jpost(u, "/index/i/query", raw=b"Count(Row(f=0))")
        if out["results"][0] == expect:
            break
        assert time.monotonic() < deadline, out
        time.sleep(0.2)
    yield servers, uris, expect
    for s in servers:
        s.close()


def test_remote_deadline_is_coordinator_budget_minus_elapsed(trio):
    """The deadline budget SHRINKS as it crosses nodes: each remote sees
    the coordinator's budget minus wire/queue elapsed, never a fresh
    budget and never more than the coordinator had."""
    servers, uris, expect = trio
    budget = 5.0
    seen = {}  # node_id -> remaining at remote execution entry
    originals = {}
    from pilosa_tpu.utils import qctx

    def wrap(srv):
        orig = srv.api.query_results
        originals[srv.node_id] = orig

        def spy(*a, **k):
            if k.get("remote"):
                seen[srv.node_id] = qctx.remaining()
            return orig(*a, **k)
        srv.api.query_results = spy

    for s in servers[1:]:
        wrap(s)
    try:
        t0 = time.monotonic()
        out = _jpost(uris[0], f"/index/i/query?timeout={budget}s",
                     raw=b"Count(Row(f=0))")
        elapsed = time.monotonic() - t0
        assert out["results"][0] == expect
        assert seen, "query never fanned out to a remote"
        for node, rem in seen.items():
            assert rem is not None, f"{node} executed without a deadline"
            # strictly less than the full budget (time elapsed on the
            # coordinator + wire), strictly positive, and consistent
            # with the observed wall clock
            assert 0 < rem < budget, (node, rem)
            assert rem >= budget - elapsed - 0.5, (node, rem, elapsed)
    finally:
        for s in servers[1:]:
            s.api.query_results = originals[s.node_id]


def test_expired_entry_shed_remotely_without_device_dispatch(trio):
    """An envelope entry whose inherited deadline is already spent is
    rejected at the remote's execution boundary: the error comes back
    per-entry, the remote counts a deadlineRemote shed, and its count
    batcher never dispatched for it."""
    servers, uris, _ = trio
    remote = servers[1]
    before_shed = remote.qos.shed["deadlineRemote"]
    before_batches = remote.executor.batcher.batches
    out = remote.client.query_batch(uris[1], [
        {"index": "i", "query": "Count(Row(f=0))", "remote": True,
         "timeout": 0.0, "principal": "key:doomed"}])
    assert len(out) == 1
    assert "deadline" in out[0]["err"]
    assert remote.qos.shed["deadlineRemote"] == before_shed + 1
    assert remote.executor.batcher.batches == before_batches


def test_priority_header_propagates_to_remote_entries(trio):
    """X-Pilosa-Priority rides the fan-out (envelope field / header) so
    the remote's batchers order the work under the caller's class."""
    servers, uris, expect = trio
    seen = []
    orig = servers[1].api.query_batch
    orig2 = servers[2].api.query_batch

    def spy(entries, _orig=orig):
        seen.extend(e.get("priority") for e in entries)
        return _orig(entries)

    def spy2(entries, _orig=orig2):
        seen.extend(e.get("priority") for e in entries)
        return _orig(entries)

    servers[1].api.query_batch = spy
    servers[2].api.query_batch = spy2
    try:
        st, _, body = _post(uris[0], "/index/i/query",
                            b"Count(Row(f=0))",
                            hdrs={"X-Pilosa-Priority": "batch"})
        assert st == 200
        assert json.loads(body)["results"][0] == expect
        # whichever remotes were hit saw the batch class on every entry
        assert seen and all(p == "batch" for p in seen)
    finally:
        servers[1].api.query_batch = orig
        servers[2].api.query_batch = orig2
