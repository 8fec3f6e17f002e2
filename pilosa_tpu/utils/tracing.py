"""Tracing: one span primitive, one tree per served request, three sinks.

Reference: tracing/tracing.go:9-59 (StartSpanFromContext, the per-node
tracer, InjectHTTPHeaders/extractTracing) + the opentracing/Jaeger adapter.

`span(name, **tags)` is the one primitive. A span knows its parent (the
span open in the same context: fan-out pool threads run in copied
contexts, so their spans nest under the submitter's), the request's trace
id, wall time (`time.perf_counter`) and the thread's CPU time
(`time.thread_time`) at both ends. On finish it reports to three sinks:

  a. aggregates, always on: the process-global `spans` table (per name
     n / wallMs / selfMs / cpuMs / log2 buckets), served by /debug/vars
     `spans` and /metrics `pilosa_spanMs{span=...}`;
     PILOSA_TPU_TELEMETRY=0 turns it off.
  b. the device trace, only while DeviceProfiler.capture runs: the span
     is also a `jax.profiler.TraceAnnotation("pilosa.<name>",
     trace_id=...)`, so a capture's .xplane.pb holds the program's
     stages on the capture's own clock, beside the device's operations.
  c. the request's QueryProfile (`stages`, ?profile=true and
     /debug/query-history) and the node's recording `Tracer` (a ring
     read by tests and the slow-query tooling, and the exporter behind
     [tracing] agent-host-port / [metric] trace-export).

The tracer a span reports to is found through `current_tracer`, which the
HTTP layer sets to its node's Tracer; outside a request there is none and
only sink a sees the span.
"""

from __future__ import annotations

import collections
import contextvars
import os
import random
import threading
import time
from typing import Optional

from pilosa_tpu.utils import profile as qprofile
from pilosa_tpu.utils import threads
from pilosa_tpu.utils.stats import _pow2_bucket

TRACE_HEADER = "X-Pilosa-Trace-Id"

# process-seeded PRNG for trace and span ids: uniqueness, not
# cryptographic strength (uuid4 costs an os.urandom syscall per call,
# visible in serving-path profiles)
_trace_rng = random.Random()

# trace id of the request being served, for cross-node propagation: the HTTP
# handler sets it from the incoming header (or mints one on a work route),
# the InternalClient injects it into outgoing internal requests
# (InjectHTTPHeaders / extractTracing, tracing/tracing.go:22-26,
# http/handler.go:226-234)
current_trace_id: contextvars.ContextVar[Optional[str]] = contextvars.ContextVar(
    "pilosa_trace_id", default=None)

# the node's Tracer for the request being served (several servers share a
# process in tests, each with a ring of its own); None outside a request
current_tracer: contextvars.ContextVar[Optional["Tracer"]] = \
    contextvars.ContextVar("pilosa_tracer", default=None)

# the span open in this context: the parent of the next one
current_span: contextvars.ContextVar[Optional["Span"]] = \
    contextvars.ContextVar("pilosa_span", default=None)

# sink b's switch: DeviceProfiler.capture holds it for a capture's
# duration. Off a capture a span pays one read of it.
_capturing = False


def set_capturing(on: bool) -> None:
    global _capturing
    _capturing = bool(on)


def capturing() -> bool:
    return _capturing


def new_trace_id() -> str:
    """Mint a fresh trace id, one for a whole request, so the slow-query
    log, /debug/query-history and exported spans all join on it."""
    return f"{_trace_rng.getrandbits(64):016x}"


def _covered(intervals: list, lo: float, hi: float) -> float:
    """Length of [lo, hi] the (start, end) intervals cover, overlaps
    counted once: children on pool threads run side by side."""
    total = 0.0
    edge = lo
    for s, e in sorted(intervals):
        s, e = max(s, edge), min(e, hi)
        if e > s:
            total += e - s
            edge = e
    return total


class Span:
    __slots__ = ("tracer", "name", "trace_id", "span_id", "parent", "start",
                 "end", "start_wall", "tags", "self_ms", "cpu_ms",
                 "launches", "devices", "_cpu0", "_kids", "_token",
                 "_annotation")

    def __init__(self, tracer, name: str, trace_id: Optional[str] = None,
                 tags: Optional[dict] = None):
        parent = current_span.get()
        self.tracer = tracer
        self.name = name
        self.parent = parent
        self.trace_id = (trace_id or current_trace_id.get()
                         or (parent.trace_id if parent is not None
                             else new_trace_id()))
        self.span_id = _trace_rng.getrandbits(64)
        self.tags: dict = tags if tags is not None else {}
        self.end: Optional[float] = None
        self.self_ms = 0.0
        self.cpu_ms = 0.0
        self.launches = 0  # device programs enqueued under this span
        self.devices = 1  # the most devices one of them went to
        self._kids: list = []  # (start, end) of finished child spans
        self._token = None
        self._annotation = None
        self.start_wall = time.time()  # wall clock for export timestamps
        self._cpu0 = time.thread_time()
        self.start = time.perf_counter()

    def set_tag(self, key: str, value) -> None:
        self.tags[key] = value

    def __enter__(self):
        self._token = current_span.set(self)
        if _capturing:
            import jax
            self._annotation = jax.profiler.TraceAnnotation(
                "pilosa." + self.name, trace_id=self.trace_id)
            self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self.finish()

    def finish(self) -> None:
        """Close the span (once: a second call is a no-op) on the thread
        that opened it, and report it to the sinks."""
        if self.end is not None:
            return
        end = self.end = time.perf_counter()
        self.cpu_ms = (time.thread_time() - self._cpu0) * 1e3
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._token is not None:
            current_span.reset(self._token)
            self._token = None
        wall_ms = (end - self.start) * 1e3
        kids = self._kids
        self.self_ms = wall_ms - (_covered(kids, self.start, end) * 1e3
                                  if kids else 0.0)
        if self.parent is not None:
            self.parent._kids.append((self.start, end))
        if self.launches:
            self.tags["dispatches"] = self.launches
            self.tags["devices"] = self.devices
        if _telemetry_on():
            spans.add(self.name, wall_ms, self.self_ms, self.cpu_ms)
        prof = qprofile.current_profile.get()
        if prof is not None:
            prof.record_stage(self)
        if self.tracer is not None:
            self.tracer._record(self)

    def duration(self) -> float:
        return (self.end if self.end is not None
                else time.perf_counter()) - self.start

    @property
    def ms(self) -> float:
        return self.duration() * 1e3


def span(name: str, trace_id: Optional[str] = None, **tags) -> Span:
    """A span under the one open in this context, reporting to this
    request's tracer: `with tracing.span("plan"): ...`."""
    return Span(current_tracer.get(), name, trace_id, tags)


def note_launch(devices: int = 1, collective: bool = False) -> None:
    """One device program enqueued (counted_jit / record_dispatch): the
    open span's `dispatches` tag and, of the devices a launch went to,
    the most (`devices`). A launch to several devices is also counted in
    `mesh_launches`, as one that holds a collective or as a local one."""
    sp = current_span.get()
    if sp is not None:
        sp.launches += 1
        if devices > sp.devices:
            sp.devices = devices
    if devices > 1:
        mesh_launches.add(collective)


class MeshLaunches:
    """Launches that went to more than one device, counted where they are
    made: `collective` where the program holds a psum or a GSPMD reduce
    across the mesh's shard axis, `local` where every device works its own
    block and nothing crosses the interconnect. `threads` are the threads
    that have launched a collective one: independent threads' collective
    programs can reach the devices in different orders and wait for each
    other for ever, so parallel/mesh.py launches every one of them on one
    thread, and this set is how to see that it does (the /debug/vars
    `mesh` block: `collectiveThreads` reads 0 or 1)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.watching = False  # a DeviceRunner with a mesh exists
        self._collective = 0
        self._local = 0
        self._threads: set = set()

    def add(self, collective: bool) -> None:
        with self._lock:
            if collective:
                self._collective += 1
                self._threads.add(threading.get_ident())
            else:
                self._local += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"collectiveLaunches": self._collective,
                    "localLaunches": self._local,
                    "collectiveThreads": len(self._threads)}

    def reset(self) -> None:
        with self._lock:
            self._collective = self._local = 0
            self._threads.clear()


# process-global, like `spans` below: one process, one set of devices
mesh_launches = MeshLaunches()


def _telemetry_on() -> bool:
    # telemetry.enabled()'s switch, read here because utils/telemetry.py
    # imports this module
    return os.environ.get("PILOSA_TPU_TELEMETRY", "1") != "0"


class SpanStats:
    """Sink a: per span name, how many finished and their summed wall,
    self and thread-CPU milliseconds, with log2 buckets of the wall time
    (the pattern of telemetry.kernels). A layer's metric is a delta of
    two snapshots: self time partitions a request's wall, so the names'
    selfMs sum to the roots' wallMs."""

    def __init__(self):
        self._lock = threading.Lock()
        self._by_name: dict[str, dict] = {}

    def add(self, name: str, wall_ms: float, self_ms: float,
            cpu_ms: float) -> None:
        b = _pow2_bucket(wall_ms)
        with self._lock:
            e = self._by_name.get(name)
            if e is None:
                e = self._by_name[name] = {
                    "n": 0, "wallMs": 0.0, "selfMs": 0.0, "cpuMs": 0.0,
                    "buckets": {}}
            e["n"] += 1
            e["wallMs"] += wall_ms
            e["selfMs"] += self_ms
            e["cpuMs"] += cpu_ms
            e["buckets"][b] = e["buckets"].get(b, 0) + 1

    def snapshot(self) -> dict:
        """The /debug/vars `spans` block. `nowMs` is this process's
        perf_counter, the clock a delta of two snapshots is taken over."""
        with self._lock:
            by_name = {name: {**e, "buckets": dict(e["buckets"])}
                       for name, e in sorted(self._by_name.items())}
        return {"enabled": _telemetry_on(),
                "nowMs": time.perf_counter() * 1e3, "byName": by_name}

    def metrics_view(self) -> dict:
        """Timings in StatsClient key syntax: the pilosa_spanMs{span=...}
        histogram family of /metrics."""
        with self._lock:
            return {f"spanMs,span:{name}": {
                        "count": e["n"], "sum": e["wallMs"],
                        "buckets": dict(e["buckets"])}
                    for name, e in self._by_name.items()}

    def reset(self) -> None:
        with self._lock:
            self._by_name.clear()


# process-global, like telemetry.kernels: every span adds to it, and
# /debug/vars, /metrics and the benchmark's readers read it
spans = SpanStats()


def trace_export_enabled() -> bool:
    """PILOSA_TPU_TRACE_EXPORT=0 kills all external trace export (read per
    batch: operators flip it at runtime when a collector misbehaves)."""
    import os
    return os.environ.get("PILOSA_TPU_TRACE_EXPORT", "1") != "0"


def _new_span_id() -> str:
    return f"{_trace_rng.getrandbits(64):016x}"


def profile_to_spans(profile: dict) -> list[dict]:
    """Flatten a cross-node QueryProfile tree (utils/profile.py to_dict)
    into exportable span records with parent/child links, all under the
    profile's ONE trace id — so a trace id found in the slow-query log can
    be followed outside the process, remote hops included.

    Record shape (the exporter's internal interchange, formatted to
    Jaeger-JSON or OTLP-JSON at flush): traceID, spanID, parentSpanID
    ("" = root), operationName, startTimeMicros, durationMicros, tags.

    Structure: one root `pilosa.query` span per profile node; child spans
    for the node's stage tree (or, from a peer without one, its executor
    calls), per-shard-group fan-out RPCs, and batched-dispatch
    shares; remote profile fragments recurse under the fan-out span of
    their node (falling back to the root when the RPC record is absent —
    e.g. a hedge winner whose primary record sealed late)."""
    spans: list[dict] = []

    def emit(trace_id: str, name: str, start_us: int, dur_us: int,
             parent: str, tags: dict, sid: str = "") -> str:
        sid = sid or _new_span_id()
        spans.append({
            "traceID": trace_id, "spanID": sid, "parentSpanID": parent,
            "operationName": name,
            "startTimeMicros": int(start_us),
            "durationMicros": max(0, int(dur_us)),
            "tags": {k: str(v) for k, v in tags.items() if v is not None},
        })
        return sid

    def walk(node: dict, parent: str, trace_id: str) -> None:
        trace_id = node.get("traceId") or trace_id
        start_us = int(float(node.get("startWall") or 0.0) * 1e6)
        root = emit(trace_id, "pilosa.query", start_us,
                    float(node.get("elapsedMs") or 0.0) * 1e3, parent,
                    {"node": node.get("node"), "index": node.get("index"),
                     "pql": node.get("pql")})
        stages = node.get("stages") or []
        stage_ids = {st.get("id"): _new_span_id() for st in stages}
        for st in stages:
            # the request's stage tree (Span -> QueryProfile.record_stage):
            # each at its own start, under the stage that opened it
            emit(trace_id, st.get("name", "?"),
                 start_us + float(st.get("startMs") or 0.0) * 1e3,
                 float(st.get("ms") or 0.0) * 1e3,
                 stage_ids.get(st.get("parent"), root),
                 {"selfMs": st.get("selfMs"), **(st.get("tags") or {})},
                 sid=stage_ids[st.get("id")])
        if not stages:
            # a peer that records no stages: its calls, stamped with the
            # root's start (their own is not known); with stages the
            # `executor.<Call>` stage is the call
            for c in node.get("calls", []):
                emit(trace_id, f"call.{c.get('call', '?')}", start_us,
                     float(c.get("ms") or 0.0) * 1e3, root, {})
        fanout_span_by_node: dict[str, str] = {}
        for fo in node.get("fanout", []):
            kind = fo.get("kind")
            if kind:  # hedge / failover bookkeeping records: tag-only spans
                emit(trace_id, f"fanout.{kind}", start_us, 0, root, fo)
                continue
            sid = emit(trace_id, f"fanout.{fo.get('node', '?')}", start_us,
                       float(fo.get("ms") or 0.0) * 1e3, root,
                       {"shards": fo.get("shards"),
                        "transport": fo.get("transport"),
                        "hedge": fo.get("hedge"),
                        "error": fo.get("error")})
            fanout_span_by_node.setdefault(str(fo.get("node")), sid)
        for d in node.get("dispatches", []):
            emit(trace_id, f"dispatch.{d.get('batcher', '?')}", start_us,
                 float(d.get("shareMs") or 0.0) * 1e3, root,
                 {"dispatch": d.get("dispatch"),
                  "batchSize": d.get("batchSize"),
                  "wallMs": d.get("wallMs")})
        for rem in node.get("remoteProfiles", []):
            frag = rem.get("profile")
            if not isinstance(frag, dict):
                continue
            # remote fragments are grafted under the peer's URI
            # (coalesce/query_proto), while fan-out records carry the
            # cluster node id — the fragment's OWN node id is the join
            # key; the graft label is the fallback
            anchor = (fanout_span_by_node.get(str(frag.get("node")))
                      or fanout_span_by_node.get(str(rem.get("node")))
                      or root)
            walk(frag, anchor, trace_id)

    walk(profile, "", profile.get("traceId") or _new_span_id())
    return spans


def spans_to_jaeger(records: list[dict],
                    service_name: str = "pilosa-tpu") -> dict:
    """Jaeger-JSON batch: the shape a Jaeger HTTP collector's JSON
    endpoint (and jaeger-ui's import) accepts — references carry the
    CHILD_OF links."""
    spans = []
    for r in records:
        refs = []
        if r.get("parentSpanID"):
            refs.append({"refType": "CHILD_OF", "traceID": r["traceID"],
                         "spanID": r["parentSpanID"]})
        spans.append({
            "traceID": r["traceID"], "spanID": r["spanID"],
            "operationName": r["operationName"],
            "references": refs,
            "startTime": r["startTimeMicros"],
            "duration": r["durationMicros"],
            "tags": [{"key": k, "type": "string", "value": v}
                     for k, v in sorted(r.get("tags", {}).items())],
        })
    return {"process": {"serviceName": service_name}, "spans": spans}


def spans_to_otlp(records: list[dict],
                  service_name: str = "pilosa-tpu") -> dict:
    """OTLP/JSON ExportTraceServiceRequest. OTLP trace ids are 128-bit:
    the native 64-bit ids are zero-padded left, which every OTLP consumer
    accepts and keeps the join with log lines trivially greppable."""
    spans = []
    for r in records:
        start_ns = r["startTimeMicros"] * 1000
        spans.append({
            "traceId": r["traceID"].rjust(32, "0"),
            "spanId": r["spanID"],
            "parentSpanId": r.get("parentSpanID", ""),
            "name": r["operationName"],
            "kind": 1,
            "startTimeUnixNano": str(start_ns),
            "endTimeUnixNano": str(start_ns + r["durationMicros"] * 1000),
            "attributes": [{"key": k, "value": {"stringValue": v}}
                           for k, v in sorted(r.get("tags", {}).items())],
        })
    return {"resourceSpans": [{
        "resource": {"attributes": [
            {"key": "service.name",
             "value": {"stringValue": service_name}}]},
        "scopeSpans": [{"scope": {"name": "pilosa-tpu"}, "spans": spans}],
    }]}


class TraceExporter:
    """External trace egress: Jaeger-JSON or OTLP-JSON batches to a spool
    file (one JSON batch per line — ship with any log forwarder) or an
    HTTP collector endpoint ([metric] trace-export = off|file|http).

    The one exporter: [tracing] agent-host-port builds it in http mode
    with the Jaeger format, [metric] trace-export in the mode it names.
    Feeds from two sources: the recording tracer's finished spans (wired
    as Tracer.exporter) and finished cross-node profile trees
    (export_profile), both parent/child-linked. Sampling is deterministic per trace id (crc32,
    the Tracer._sampled scheme) so every node of one trace agrees; the
    `PILOSA_TPU_TRACE_EXPORT=0` kill switch and any I/O failure drop
    batches — export must never block or break serving."""

    def __init__(self, mode: str = "file", path: str = "",
                 endpoint: str = "", fmt: str = "jaeger",
                 sample: float = 1.0, batch_size: int = 64,
                 flush_interval: float = 2.0,
                 service_name: str = "pilosa-tpu"):
        if mode not in ("file", "http"):
            raise ValueError(
                f"invalid trace-export mode {mode!r} (expected file | http)")
        if fmt not in ("jaeger", "otlp"):
            raise ValueError(
                f"invalid trace-export format {fmt!r} "
                "(expected jaeger | otlp)")
        if mode == "file" and not path:
            raise ValueError("trace-export = file requires a spool path")
        if mode == "http" and not endpoint:
            raise ValueError("trace-export = http requires an endpoint")
        self.mode = mode
        self.path = path
        self.endpoint = endpoint
        self.fmt = fmt
        self.sample = sample
        self.batch_size = batch_size
        self.flush_interval = flush_interval
        self.service_name = service_name
        self._buf: list[dict] = []
        self._lock = threading.Lock()
        self._timer: Optional[threading.Timer] = None
        self._flush_pending = False
        self._closed = False
        self.exported = 0  # span records successfully shipped
        self.dropped = 0   # span records lost to I/O failures
        self._schedule()

    # -- sampling -----------------------------------------------------------

    def sampled(self, trace_id: Optional[str]) -> bool:
        if self.sample >= 1.0:
            return True
        if self.sample <= 0.0:
            return False
        import zlib
        h = zlib.crc32((trace_id or "").encode())
        return (h % 10_000) < self.sample * 10_000

    # -- ingestion ----------------------------------------------------------

    def export(self, span: "Span") -> None:
        """Recording-tracer hook: one finished span, linked to its
        parent. Tracer._sampled already gated it."""
        if not trace_export_enabled():
            return
        parent = span.parent
        self._push([{
            "traceID": span.trace_id, "spanID": f"{span.span_id:016x}",
            "parentSpanID": (f"{parent.span_id:016x}"
                             if parent is not None else ""),
            "operationName": span.name,
            "startTimeMicros": int(span.start_wall * 1e6),
            "durationMicros": int(span.duration() * 1e6),
            "tags": {k: str(v) for k, v in span.tags.items()},
        }])

    def export_profile(self, profile: dict) -> None:
        """One finished cross-node profile tree -> linked spans."""
        if not trace_export_enabled():
            return
        if not self.sampled(profile.get("traceId")):
            return
        try:
            self._push(profile_to_spans(profile))
        except Exception:  # noqa: BLE001 — export must never break serving
            self.dropped += 1

    def _push(self, records: list[dict]) -> None:
        if not records:
            return
        with self._lock:
            if self._closed:
                return
            self._buf.extend(records)
            spawn = (len(self._buf) >= self.batch_size
                     and not self._flush_pending)
            if spawn:
                self._flush_pending = True
        if spawn:
            threads.spawn(self._bg_flush)

    # -- flushing -----------------------------------------------------------

    def _schedule(self) -> None:
        if self._closed or self.flush_interval <= 0:
            return
        self._timer = threads.ctx_timer(self.flush_interval, self._tick)
        self._timer.start()

    def _tick(self) -> None:
        try:
            self.flush()
        finally:
            self._schedule()

    def _bg_flush(self) -> None:
        try:
            self.flush()
        finally:
            with self._lock:
                self._flush_pending = False

    def flush(self) -> None:
        with self._lock:
            batch, self._buf = self._buf, []
        if not batch or not trace_export_enabled():
            self.dropped += len(batch)
            return
        import json
        body_obj = (spans_to_jaeger(batch, self.service_name)
                    if self.fmt == "jaeger"
                    else spans_to_otlp(batch, self.service_name))
        try:
            if self.mode == "file":
                # one JSON batch per line: append-only spool any log
                # shipper can tail; partial-line torn writes are bounded
                # to the final line and skipped by readers
                with open(self.path, "a") as f:
                    f.write(json.dumps(body_obj) + "\n")
            else:
                import urllib.request
                req = urllib.request.Request(
                    self.endpoint, data=json.dumps(body_obj).encode(),
                    method="POST",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=2.0):
                    pass
            self.exported += len(batch)
        except Exception:  # noqa: BLE001 — drop the batch: never let
            # trace egress break (or block) serving
            self.dropped += len(batch)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        if self._timer is not None:
            self._timer.cancel()
        self.flush()


class Tracer:
    """A node's recording tracer; keeps the last `limit` finished spans.

    `sampler_type`/`sampler_param` mirror the reference's Jaeger sampler
    config (server/config.go:96-104): "const" with param>=1 samples
    everything, "probabilistic" samples that fraction, "off"/param 0
    samples nothing (recording still happens for slow-query logging; the
    sampler only gates *export*)."""

    def __init__(self, limit: int = 1000,
                 exporter: Optional[TraceExporter] = None,
                 sampler_type: str = "const", sampler_param: float = 1.0):
        self._lock = threading.Lock()
        self.spans: "collections.deque[Span]" = collections.deque(
            maxlen=limit)
        self.exporter = exporter
        self.sampler_type = sampler_type
        self.sampler_param = sampler_param

    def start_span(self, name: str, trace_id: Optional[str] = None) -> Span:
        """A span that reports to THIS tracer, whatever the context's is."""
        return Span(self, name, trace_id)

    def _sampled(self, span: Span) -> bool:
        if self.exporter is None or self.sampler_type == "off":
            return False
        if self.sampler_type == "probabilistic":
            # deterministic per-trace: hash the trace id so every span of
            # one trace gets the same verdict on every node (ids from
            # X-Pilosa-Trace-Id are caller-supplied, not always hex)
            import zlib
            h = zlib.crc32(span.trace_id.encode()) if span.trace_id else 0
            return (h % 10_000) < self.sampler_param * 10_000
        return self.sampler_param >= 1  # const

    def _record(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)
        if self._sampled(span):
            self.exporter.export(span)

    def finished(self, name: Optional[str] = None) -> list[Span]:
        with self._lock:
            return [s for s in self.spans if name is None or s.name == name]

    # HTTP propagation (tracing/tracing.go:22-26)
    def inject_headers(self, span: Span, headers: dict) -> None:
        headers[TRACE_HEADER] = span.trace_id

    def extract_trace_id(self, headers) -> Optional[str]:
        return headers.get(TRACE_HEADER)
