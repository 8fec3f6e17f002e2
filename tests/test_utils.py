"""Aux subsystem tests: stats, tracing, logger, attr store, translate store."""

import io
import os
import time

import pytest

from pilosa_tpu.utils.attrstore import AttrStore, NopAttrStore
from pilosa_tpu.utils.logger import Logger, NopLogger
from pilosa_tpu.utils.stats import NopStatsClient, StatsClient, new_stats_client
from pilosa_tpu.utils.tracing import Tracer
from pilosa_tpu.utils.translate import TranslateStore


def test_stats_counts_gauges_timings():
    s = StatsClient()
    s.count("queries")
    s.count("queries", 2)
    s.gauge("goroutines", 5)
    s.timing("latency", 1.5)
    s.timing("latency", 0.5)
    s.set("indexes", "i")
    snap = s.snapshot()
    assert snap["counts"]["queries"] == 3
    assert snap["gauges"]["goroutines"] == 5
    assert snap["timings"]["latency"]["count"] == 2
    assert snap["timings"]["latency"]["min"] == 0.5
    assert snap["sets"]["indexes"] == ["i"]
    # tags namespace, shared store
    s.with_tags("index:i").count("queries")
    assert s.snapshot()["counts"]["queries,index:i"] == 1
    assert new_stats_client("nop").snapshot() == {}
    NopStatsClient().count("x")  # no-op


def test_tracer_spans_and_propagation():
    t = Tracer()
    with t.start_span("executor.Count") as span:
        span.set_tag("index", "i")
    spans = t.finished("executor.Count")
    assert len(spans) == 1
    assert spans[0].tags == {"index": "i"}
    assert spans[0].duration() >= 0
    headers = {}
    t.inject_headers(spans[0], headers)
    assert t.extract_trace_id(headers) == spans[0].trace_id
    # outside a request no tracer is installed: a span reports to no ring
    from pilosa_tpu.utils import tracing
    with tracing.span("executor.Count") as loose:
        pass
    assert loose.tracer is None and loose.end is not None
    assert len(t.finished()) == 1


def test_logger():
    buf = io.StringIO()
    log = Logger(verbose=False, out=buf)
    log.printf("hello %s", "world")
    log.debugf("hidden")
    out = buf.getvalue()
    assert "hello world" in out and "hidden" not in out
    Logger(verbose=True, out=buf).debugf("shown")
    assert "shown" in buf.getvalue()
    NopLogger().printf("x")


def test_attrstore(tmp_path):
    s = AttrStore(str(tmp_path / "a.db")).open()
    s.set_attrs(1, {"color": "red", "n": 5})
    s.set_attrs(1, {"n": None, "x": True})  # merge + delete
    assert s.attrs(1) == {"color": "red", "x": True}
    s.set_attrs(250, {"y": 1})
    assert s.ids() == [1, 250]
    blocks = dict(s.blocks())
    assert set(blocks) == {0, 2}
    assert s.block_data(2) == [(250, {"y": 1})]
    s.close()
    # persistence
    s2 = AttrStore(str(tmp_path / "a.db")).open()
    assert s2.attrs(1) == {"color": "red", "x": True}
    s2.close()
    assert NopAttrStore().open().attrs(1) == {}


def test_translate_store_persistence(tmp_path):
    path = str(tmp_path / "keys")
    t = TranslateStore(path).open()
    a = t.translate_column("i", "alpha")
    b = t.translate_column("i", "beta")
    assert (a, b) == (1, 2)
    assert t.translate_column("i", "alpha") == 1  # stable
    r = t.translate_row("i", "f", "row-key")
    assert r == 1  # row namespace separate from columns
    assert t.translate_column_to_string("i", 1) == "alpha"
    assert t.translate_row_to_string("i", "f", 1) == "row-key"
    t.close()
    t2 = TranslateStore(path).open()
    assert t2.translate_column("i", "alpha", create=False) == 1
    assert t2.translate_column("i", "gamma") == 3
    t2.close()


def test_translate_replication(tmp_path):
    primary = TranslateStore(str(tmp_path / "p")).open()
    primary.translate_column("i", "k1")
    primary.translate_column("i", "k2")
    replica = TranslateStore(str(tmp_path / "r")).open()
    replica.read_only = True
    replica.apply_log(primary.log_bytes(0))
    assert replica.translate_column("i", "k1", create=False) == 1
    with pytest.raises(ValueError):
        replica.translate_column("i", "new-key")
    # incremental tail
    off = primary.log_size()
    primary.translate_column("i", "k3")
    replica.apply_log(primary.log_bytes(off))
    assert replica.translate_column("i", "k3", create=False) == 3
    primary.close()
    replica.close()


# -- statsd client (statsd/statsd.go) ----------------------------------------

def test_statsd_client_datagrams():
    import socket
    from pilosa_tpu.utils.stats import StatsDClient, new_stats_client

    rx = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.settimeout(5)
    port = rx.getsockname()[1]
    c = StatsDClient("127.0.0.1", port, prefix="pilosa.")
    c.count("queries", 2)
    assert rx.recvfrom(1024)[0] == b"pilosa.queries:2|c"
    c.gauge("heap", 12.5)
    assert rx.recvfrom(1024)[0] == b"pilosa.heap:12.5|g"
    c.with_tags("index:i").timing("latency", 3)
    assert rx.recvfrom(1024)[0] == b"pilosa.latency:3|ms|#index:i"
    # factory selection
    s = new_stats_client("statsd", f"127.0.0.1:{port}")
    s.count("x")
    assert rx.recvfrom(1024)[0] == b"pilosa.x:1|c"
    rx.close()
    # unreachable agent must not raise
    dead = StatsDClient("127.0.0.1", 1)
    dead.count("x")


# -- system info / diagnostics / runtime monitor (diagnostics.go) ------------

def test_system_info_proc():
    from pilosa_tpu.utils.diagnostics import SystemInfo
    si = SystemInfo()
    assert si.uptime() > 0
    assert si.platform() == "Linux"
    assert si.mem_total() > si.mem_used() > 0
    assert si.cpu_count() >= 1


def test_diagnostics_collect_and_flush():
    import json
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer
    from pilosa_tpu.utils.diagnostics import DiagnosticsCollector

    received = []

    class H(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers["Content-Length"])
            received.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = HTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    url = f"http://127.0.0.1:{srv.server_address[1]}/diagnostics"
    d = DiagnosticsCollector("1.0.0", url=url)
    info = d.collect()
    assert info["Version"] == "1.0.0" and info["OS"] == "Linux"
    assert d.flush() is True
    assert received[0]["NumCPU"] >= 1
    srv.shutdown()
    # no URL -> disabled, flush is a no-op
    assert DiagnosticsCollector("1.0.0").flush() is False


def test_span_exporter_ships_batches():
    """Config-enabled span export to a collector (the reference's Jaeger
    wiring, tracing/opentracing/opentracing.go:21-39), through the one
    exporter as [tracing] agent-host-port builds it: spans buffer, flush
    in batches, and sampler type/param gate what ships."""
    import json
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer
    from pilosa_tpu.utils.tracing import TraceExporter

    received = []

    class H(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers["Content-Length"])
            received.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = HTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}/api/traces"

    exp = TraceExporter(mode="http", endpoint=url, fmt="jaeger",
                        batch_size=2, flush_interval=0)  # manual flush
    tr = Tracer(exporter=exp, sampler_type="const", sampler_param=1.0)
    with tr.start_span("executor.Count") as s:
        s.set_tag("index", "i")
    assert exp.exported == 0  # buffered below batch_size
    with tr.start_span("executor.TopN"):
        pass  # second span hits batch_size -> background flush
    deadline = time.time() + 5
    while exp.exported < 2 and time.time() < deadline:
        time.sleep(0.01)
    assert exp.exported == 2
    batch = received[0]
    assert batch["process"]["serviceName"] == "pilosa-tpu"
    ops = [s["operationName"] for s in batch["spans"]]
    assert ops == ["executor.Count", "executor.TopN"]
    assert batch["spans"][0]["tags"] == [
        {"key": "index", "type": "string", "value": "i"}]
    assert batch["spans"][0]["duration"] >= 0

    # sampler off -> recorded locally, never exported
    tr_off = Tracer(exporter=exp, sampler_type="off")
    with tr_off.start_span("x"):
        pass
    exp.flush()
    assert exp.exported == 2
    assert len(tr_off.finished("x")) == 1

    # probabilistic is deterministic per trace id
    tr_p = Tracer(exporter=exp, sampler_type="probabilistic",
                  sampler_param=0.5)
    v1 = tr_p._sampled(tr_p.start_span("y", trace_id="abc"))
    v2 = tr_p._sampled(tr_p.start_span("y", trace_id="abc"))
    assert v1 == v2

    # export failure (collector gone) drops the batch, never raises
    srv.shutdown()
    with tr.start_span("a"):
        pass
    with tr.start_span("b"):
        pass
    assert exp.exported == 2
    exp.close()


def test_runtime_monitor_gauges():
    from pilosa_tpu.utils.diagnostics import RuntimeMonitor
    from pilosa_tpu.utils.stats import StatsClient
    stats = StatsClient()
    RuntimeMonitor(stats).sample()
    snap = stats.snapshot()["gauges"]
    assert snap["memory/rss"] > 0
    assert snap["threads"] >= 1


def test_long_query_logging(tmp_path):
    import io
    from pilosa_tpu.server import Server
    from pilosa_tpu.utils.logger import Logger

    s = Server(str(tmp_path / "n"), port=0, long_query_time=0.0000001).open()
    try:
        buf = io.StringIO()
        s.api.logger = Logger(out=buf)
        s.api.create_index("i")
        from pilosa_tpu.models.field import FieldOptions
        s.api.create_field("i", "f", FieldOptions())
        s.api.query("i", "Count(Row(f=1))")
        assert "SLOW QUERY i Count(Row(f=1))" in buf.getvalue()
    finally:
        s.close()


def test_duration_strings():
    from pilosa_tpu.utils.duration import parse_duration
    assert parse_duration(5) == 5.0
    assert parse_duration("2.5") == 2.5
    assert parse_duration("250ms") == 0.25
    assert parse_duration("10s") == 10.0
    assert parse_duration("1h30m") == 5400.0
    assert parse_duration("") == 0.0
    with pytest.raises(ValueError):
        parse_duration("10 parsecs")
    with pytest.raises(ValueError):
        parse_duration("s10")


def test_uri_parse():
    from pilosa_tpu.net.uri import URI, URIError
    assert URI.parse("").normalize() == "http://localhost:10101"
    assert URI.parse("example.com").normalize() == "http://example.com:10101"
    assert URI.parse(":8080") == URI("http", "localhost", 8080)
    assert URI.parse("https://db1:444").normalize() == "https://db1:444"
    assert URI.parse("10.0.0.1:10101").host_port == "10.0.0.1:10101"
    with pytest.raises(URIError):
        URI.parse("ftp://x:1")
    with pytest.raises(URIError):
        URI.parse("http://host:99999")


def test_trace_id_propagation_context():
    """Incoming trace ids flow into spans opened while serving
    (extractTracing middleware + GlobalTracer), and onto outgoing internal
    requests (InjectHTTPHeaders)."""
    from pilosa_tpu.utils import tracing

    t = Tracer()
    token = tracing.current_trace_id.set("deadbeef")
    try:
        with t.start_span("executor.Execute") as span:
            assert span.trace_id == "deadbeef"
    finally:
        tracing.current_trace_id.reset(token)
    # outside the request context ids are fresh
    assert t.start_span("x").trace_id != "deadbeef"


def test_config_durations_and_tls(tmp_path):
    from pilosa_tpu.cli.config import load_config
    p = tmp_path / "c.toml"
    p.write_text(
        '[anti-entropy]\ninterval = "10m"\n'
        '[tls]\ncertificate = "crt.pem"\nkey = "key.pem"\nskip-verify = true\n')
    cfg = load_config(str(p))
    assert cfg.anti_entropy.interval == 600.0
    assert cfg.tls.enabled and cfg.tls.skip_verify
    cfg2 = load_config(None, environ={"PILOSA_TPU_ANTI_ENTROPY_INTERVAL": "90s",
                                      "PILOSA_TPU_TLS_CERTIFICATE": "x"})
    assert cfg2.anti_entropy.interval == 90.0
    assert cfg2.tls.certificate == "x" and not cfg2.tls.enabled
    assert "[tls]" in cfg2.to_toml()


def test_translate_sqlite_index_no_replay_on_reopen(tmp_path, monkeypatch):
    """The sqlite index absorbs the log incrementally: a clean reopen
    replays NOTHING (meta.log_pos == log size), so opening a 100M-key
    store is O(1), not O(keys) (the non-resident index of
    translate.go:359-433)."""
    import pilosa_tpu.utils.translate as tr

    path = str(tmp_path / "keys")
    t = TranslateStore(path, index_kind="sqlite").open()
    for i in range(500):
        t.translate_column("i", f"k{i}")
    t.close()

    def boom(self, data):
        raise AssertionError("clean reopen must not replay the log")

    monkeypatch.setattr(tr.TranslateStore, "_replay", boom)
    t2 = TranslateStore(path, index_kind="sqlite").open()
    assert t2.translate_column("i", "k250", create=False) == 251
    assert t2.translate_column_to_string("i", 251) == "k250"
    monkeypatch.undo()
    # minting continues from the persisted max id
    assert t2.translate_column("i", "fresh") == 501
    t2.close()


def test_translate_sqlite_index_heals_from_log_tail(tmp_path):
    """Crash between log append and index commit: the next open replays
    only the un-absorbed tail from meta.log_pos."""
    path = str(tmp_path / "keys")
    t = TranslateStore(path, index_kind="sqlite").open()
    t.translate_column("i", "a")
    t.close()
    # simulate a lost index commit: rewind log_pos to 0 (index empty-ish is
    # fine too — INSERT OR IGNORE dedups on replay)
    import sqlite3

    db = sqlite3.connect(path + ".idx")
    db.execute("UPDATE meta SET v=0 WHERE k='log_pos'")
    db.commit()
    db.close()
    t2 = TranslateStore(path, index_kind="sqlite").open()
    assert t2.translate_column("i", "a", create=False) == 1
    assert t2.translate_column("i", "b") == 2
    t2.close()


def test_translate_index_ahead_of_log_rebuilds(tmp_path):
    """Index ahead of the log (crash wrote the index before the log hit
    disk, or the log was replaced): the LOG is the source of truth — the
    index rebuilds from it instead of serving mappings the cluster never
    minted or refusing to open."""
    from pilosa_tpu.utils.translate import _record_end

    path = str(tmp_path / "keys")
    t = TranslateStore(path, index_kind="sqlite").open()
    for i in range(10):
        t.translate_column("i", f"k{i}")
    t.close()
    # truncate the log at a record boundary, behind the absorbed offset
    data = open(path, "rb").read()
    pos = 0
    for _ in range(4):
        pos = _record_end(data, pos)
    with open(path, "r+b") as f:
        f.truncate(pos)
    t2 = TranslateStore(path, index_kind="sqlite").open()
    assert t2.translate_column("i", "k3", create=False) == 4
    assert t2.translate_column("i", "k7", create=False) is None  # truncated away
    assert t2.translate_column("i", "fresh") == 5  # minting resumes from log truth
    t2.close()
    # log deleted entirely but index left behind: same rule
    os.remove(path)
    t3 = TranslateStore(path, index_kind="sqlite").open()
    assert t3.translate_column("i", "k3", create=False) is None
    assert t3.translate_column("i", "first") == 1
    t3.close()


def test_translate_sqlite_replication_parity(tmp_path):
    """Replica tailing works identically over the sqlite index."""
    primary = TranslateStore(str(tmp_path / "p"), index_kind="sqlite").open()
    for i in range(50):
        primary.translate_column("i", f"c{i}")
        primary.translate_row("i", "f", f"r{i}")
    replica = TranslateStore(str(tmp_path / "r"), index_kind="sqlite").open()
    replica.read_only = True
    replica.apply_log(primary.log_bytes(0))
    assert replica.translate_column("i", "c7", create=False) == 8
    assert replica.translate_row_to_string("i", "f", 8) == "r7"
    assert replica.log_size() == primary.log_size()
    # ensure_mapping installs without touching the log (byte-prefix rule)
    before = replica.log_size()
    replica.ensure_mapping(0, "i", "", "minted-elsewhere", 999)
    assert replica.log_size() == before
    assert replica.translate_column("i", "minted-elsewhere",
                                    create=False) == 999
    primary.close()
    replica.close()
