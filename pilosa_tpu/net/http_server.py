"""REST handler: the reference's public + internal HTTP surface.

Route table mirrors http/handler.go:236-277. Built on stdlib
ThreadingHTTPServer: one regex route table, JSON bodies, text PQL queries.
"""

from __future__ import annotations

import base64
import json
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional
from urllib.parse import parse_qs, urlparse

from pilosa_tpu.utils import threads as _threads
from pilosa_tpu import qos
from pilosa_tpu.api import API, ApiError
from pilosa_tpu.encoding.protobuf import CONTENT_TYPE as PROTO_CONTENT_TYPE
from pilosa_tpu.encoding.protobuf import Serializer
from pilosa_tpu.models.field import FieldOptions
from pilosa_tpu.utils import accounting, qctx, tracing

# (method, regex) -> handler name; ordered
ROUTES: list[tuple[str, re.Pattern, str]] = [
    ("GET", re.compile(r"^/$"), "home"),
    ("POST", re.compile(r"^/cluster/drain$"), "post_cluster_drain"),
    ("POST", re.compile(r"^/cluster/resize/abort$"), "post_resize_abort"),
    ("POST", re.compile(r"^/cluster/resize/remove-node$"), "post_remove_node"),
    ("POST", re.compile(r"^/cluster/resize/set-coordinator$"), "post_set_coordinator"),
    ("GET", re.compile(r"^/export$"), "get_export"),
    ("GET", re.compile(r"^/index$"), "get_indexes"),
    ("GET", re.compile(r"^/index/(?P<index>[^/]+)$"), "get_index"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)$"), "post_index"),
    ("DELETE", re.compile(r"^/index/(?P<index>[^/]+)$"), "delete_index"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)$"), "post_field"),
    ("DELETE", re.compile(r"^/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)$"), "delete_field"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import$"), "post_import"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/import-roaring/(?P<shard>\d+)$"), "post_import_roaring"),
    ("POST", re.compile(r"^/index/(?P<index>[^/]+)/query$"), "post_query"),
    ("GET", re.compile(r"^/info$"), "get_info"),
    ("POST", re.compile(r"^/recalculate-caches$"), "post_recalculate_caches"),
    ("GET", re.compile(r"^/schema$"), "get_schema"),
    ("GET", re.compile(r"^/status$"), "get_status"),
    ("GET", re.compile(r"^/version$"), "get_version"),
    ("GET", re.compile(r"^/cluster/stats$"), "get_cluster_stats"),
    ("GET", re.compile(r"^/cluster/usage$"), "get_cluster_usage"),
    ("GET", re.compile(r"^/cluster/heat$"), "get_cluster_heat"),
    ("GET", re.compile(r"^/cluster/events$"), "get_cluster_events"),
    ("GET", re.compile(r"^/debug/events$"), "get_debug_events"),
    ("GET", re.compile(r"^/debug/vars$"), "get_debug_vars"),
    ("GET", re.compile(r"^/debug/usage$"), "get_debug_usage"),
    ("GET", re.compile(r"^/debug/heat$"), "get_debug_heat"),
    ("GET", re.compile(r"^/debug/hbm$"), "get_debug_hbm"),
    ("GET", re.compile(r"^/cluster/hbm$"), "get_cluster_hbm"),
    ("POST", re.compile(r"^/debug/device-profile$"), "post_device_profile"),
    ("GET", re.compile(r"^/debug/query-history$"), "get_query_history"),
    ("GET", re.compile(r"^/debug/timeseries$"), "get_debug_timeseries"),
    ("GET", re.compile(r"^/debug/dashboard$"), "get_debug_dashboard"),
    ("GET", re.compile(r"^/metrics$"), "get_metrics"),
    ("GET", re.compile(r"^/debug/pprof(?:/(?P<profile>[^/]*))?$"), "get_debug_pprof"),
    # internal
    ("POST", re.compile(r"^/internal/cluster/message$"), "post_cluster_message"),
    ("GET", re.compile(r"^/internal/fragment/block/data$"), "get_fragment_block_data"),
    ("GET", re.compile(r"^/internal/fragment/blocks$"), "get_fragment_blocks"),
    ("GET", re.compile(r"^/internal/fragment/data$"), "get_fragment_data"),
    ("GET", re.compile(r"^/internal/fragment/views$"), "get_fragment_views"),
    ("GET", re.compile(r"^/internal/fragment/nodes$"), "get_fragment_nodes"),
    ("POST", re.compile(r"^/internal/index/(?P<index>[^/]+)/attr/diff$"), "post_column_attr_diff"),
    ("POST", re.compile(r"^/internal/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/attr/diff$"), "post_row_attr_diff"),
    ("DELETE", re.compile(r"^/internal/index/(?P<index>[^/]+)/field/(?P<field>[^/]+)/remote-available-shards/(?P<shard>\d+)$"), "delete_remote_available_shard"),
    ("GET", re.compile(r"^/internal/nodes$"), "get_nodes"),
    ("GET", re.compile(r"^/internal/probe$"), "get_internal_probe"),
    ("GET", re.compile(r"^/internal/stats$"), "get_internal_stats"),
    ("POST", re.compile(r"^/internal/query-batch$"), "post_query_batch"),
    ("GET", re.compile(r"^/internal/shards/max$"), "get_shards_max"),
    ("GET", re.compile(r"^/internal/translate/data$"), "get_translate_data"),
    ("POST", re.compile(r"^/internal/translate/keys$"), "post_translate_keys"),
]

# Per-endpoint allowed URL query arguments (queryValidationSpec,
# http/handler.go:171-224): unknown arguments on a LISTED endpoint are a 400,
# catching typos like ?shard= on an endpoint that reads ?shards=. Endpoints
# not listed here are left open (matching the reference: validation only
# applies to routes in the spec).
ALLOWED_QUERY_ARGS: dict[str, frozenset] = {
    "post_query": frozenset({"shards", "remote", "columnAttrs",
                             "excludeRowAttrs", "excludeColumns", "timeout",
                             "profile", "explain"}),
    "get_export": frozenset({"index", "field", "shard"}),
    "get_fragment_blocks": frozenset({"index", "field", "view", "shard"}),
    "get_fragment_block_data": frozenset({"index", "field", "view", "shard",
                                          "block"}),
    "get_fragment_data": frozenset({"index", "field", "view", "shard"}),
    "get_fragment_views": frozenset({"index", "field", "shard"}),
    "get_fragment_nodes": frozenset({"index", "shard"}),
    "get_translate_data": frozenset({"offset"}),
    "get_debug_pprof": frozenset({"seconds"}),
    "get_debug_timeseries": frozenset({"since", "limit"}),
    "get_debug_usage": frozenset({"since", "limit", "top"}),
    "get_debug_heat": frozenset({"since", "limit", "top", "advice"}),
    "get_debug_hbm": frozenset({"top"}),
    "post_device_profile": frozenset({"seconds"}),
    "get_debug_events": frozenset({"since", "limit", "type", "severity"}),
    "get_cluster_events": frozenset({"since", "limit"}),
}


class Handler:
    """Route dispatch against an API instance."""

    def __init__(self, api: API,
                 cluster_message_fn: Optional[Callable[[dict], None]] = None,
                 stats=None, query_timeout: float = 0.0, telemetry=None,
                 qos_plane=None, events=None, tracer=None):
        self.api = api
        # the node's recording Tracer (utils/tracing.py): dispatch installs
        # it for the request, so every span opened while serving reports
        # to THIS node's ring and exporter. None = aggregates only.
        self.tracer = tracer
        self.cluster_message_fn = cluster_message_fn
        self.stats = stats
        self.query_timeout = query_timeout  # [cluster] query-timeout default
        self.telemetry = telemetry  # TelemetrySampler (GET /debug/timeseries)
        # flight-recorder journal (utils/events.py EventJournal, set by
        # Server): serves GET /debug/events, merges incoming X-Pilosa-HLC
        # stamps into the node's clock, and stamps every response
        self.events = events
        # multi-tenant QoS plane (pilosa_tpu/qos.py): admission control —
        # quotas, priority resolution, deadline-aware shedding — runs here
        # at dispatch, BEFORE parse. None = no admission (plumbing only).
        self.qos = qos_plane
        self.errors_5xx = 0  # cumulative 5xx responses (health-score input)
        # graceful-drain gate (server.drain flips it): new external
        # queries get 503 + X-Pilosa-Shed-Reason: draining; internal
        # fan-out entries and non-query routes keep working so peers can
        # finish in-flight work, replay hints and fetch fragments
        self.draining = False
        self.drain_sheds = 0
        # in-flight work-route requests (query/import/query-batch): the
        # drain sequence waits for this to hit zero before snapshotting
        self.active_queries = 0
        self._counter_lock = tracing.TimedLock("http.active",
                                               threading.Lock())
        self.serializer = Serializer()
        self._local = threading.local()

    # routes the drain sequence waits out (and counts as in-flight work)
    WORK_ROUTES = frozenset({"post_query", "post_query_batch",
                             "post_import", "post_import_roaring"})

    def _set_deadline(self, route: str, query: dict, headers) -> object:
        """Adopt the caller's remaining deadline (X-Pilosa-Deadline, set by
        InternalClient on every fan-out RPC), a ?timeout= duration on
        /query, or the server's [cluster] query-timeout default. Returns a
        contextvar token to reset, or None. The deadline is checked between
        shard batches (executor.go:2591-2608 validateQueryContext)."""
        import time

        # gather every applicable source and take the STRICTEST: a
        # malformed or forged fan-out header must not disable the local
        # sources (the operator's query-timeout cap in particular), and
        # ?timeout=0 means "no timeout from this source" per the
        # documented convention, not an already-expired deadline
        candidates = []
        incoming = (headers or {}).get(qctx.DEADLINE_HEADER)
        if incoming:
            try:
                candidates.append(float(incoming))
            except ValueError:
                pass  # malformed header: fall through to local sources
        if route == "post_query":
            arg = self._arg(query, "timeout")
            if arg:
                from pilosa_tpu.utils.duration import parse_duration
                try:
                    secs = parse_duration(arg)
                except ValueError:
                    raise ApiError(f"invalid timeout: {arg!r}")
                if secs > 0:
                    candidates.append(secs)
            if self.query_timeout > 0:
                candidates.append(self.query_timeout)
        if not candidates:
            return None
        return qctx.deadline.set(time.monotonic() + min(candidates))

    def dispatch(self, method: str, path: str, query: dict, body: bytes,
                 headers=None, client_addr=None):
        """-> (status, content_type, payload bytes)."""
        self._local.headers = headers
        tracer_token = tracing.current_tracer.set(self.tracer)
        # extractTracing middleware (http/handler.go:226-234): adopt the
        # caller's trace id for every span opened while serving this request
        incoming_trace = (headers or {}).get(tracing.TRACE_HEADER) if headers else None
        token = tracing.current_trace_id.set(incoming_trace) if incoming_trace else None
        # http.admit: the middleware below, route match, deadline and
        # admission, up to the handler call (closed there, or on the way
        # out where no handler ran)
        admit = tracing.span("http.admit").__enter__()
        is_work = False
        if self.events is not None and headers is not None \
                and hasattr(headers, "get"):
            # HLC piggyback (utils/events.py): merge the caller's stamp
            # so events recorded while serving this request sort causally
            # after the caller's events — cheap no-op when absent
            from pilosa_tpu.utils import events as _events
            stamp = _events.decode_hlc(headers.get(_events.HLC_HEADER))
            if stamp is not None:
                self.events.clock.update(stamp)
        # accounting middleware (utils/accounting.py): install the
        # caller's Account so every charge site in the stack attributes
        # this request's device-ms/HBM/RPC spend to its principal —
        # X-API-Key / Authorization (digested) / remote addr, or the
        # X-Pilosa-Principal header an internal fan-out RPC inherited
        # from its coordinator. One contextvar set; charge sites are nop
        # when accounting is off.
        acct_token = None
        principal = None
        ledger = getattr(self.api, "usage_ledger", None)
        if ledger is not None and ledger.enabled and accounting.enabled():
            principal = accounting.principal_from_headers(headers,
                                                          client_addr)
            acct_token = accounting.current_account.set(
                accounting.Account(ledger, principal))
        # QoS priority install (pilosa_tpu/qos.py): header value, or the
        # principal's [qos.principals] override, or the [qos] default
        # class — one contextvar set carried by every batcher cut, pool
        # submit and fan-out RPC this request makes. Plumbing works even
        # without a plane (header-only), and the kill switch drops it all.
        prio_token = None
        plane = self.qos
        hdr_priority = (headers or {}).get(qos.PRIORITY_HEADER) \
            if headers is not None and hasattr(headers, "get") else None
        if qos.enabled() and (plane is not None or hdr_priority):
            if plane is not None:
                if principal is None:
                    principal = accounting.principal_from_headers(
                        headers, client_addr)
                pname = plane.priority_for(hdr_priority, principal)
            else:
                pname = (hdr_priority or "").strip().lower()
                pname = pname if pname in qos.PRIORITIES else None
            if pname:
                prio_token = qos.current_priority.set(pname)
        try:
            for m, rx, name in ROUTES:
                if m != method:
                    continue
                match = rx.match(path)
                if match is None:
                    continue
                allowed = ALLOWED_QUERY_ARGS.get(name)
                if allowed is not None and (unknown := set(query) - allowed):
                    return self._error(
                        400, f"invalid query argument(s): {', '.join(sorted(unknown))}")
                handler = getattr(self, name)
                dl_token = None
                qos_dl_token = None
                qos_rejected = False
                is_work = name in self.WORK_ROUTES
                if is_work:
                    with self._counter_lock:
                        self.active_queries += 1
                    if token is None:
                        # one trace id for the whole request (the root
                        # span's, minted when it opened), so logs, the
                        # query history, exported spans and fan-out RPCs
                        # all join on it
                        token = tracing.current_trace_id.set(admit.trace_id)
                try:
                    # inside the try: an invalid ?timeout= must map to a
                    # clean 400 like any other ApiError, not escape dispatch
                    # (and an injected dispatch fault surfaces as a 500 the
                    # same way a real handler crash would)
                    from pilosa_tpu.utils import failpoints
                    failpoints.hit("http.server.dispatch")
                    dl_token = self._set_deadline(name, query, headers)
                    if (self.draining and name == "post_query"
                            and not self._qos_inherited(query, headers)):
                        # graceful drain: NEW external queries are shed
                        # (clients fail over to the next replica with no
                        # backoff — net/client.py honors the reason
                        # header); fan-out entries a coordinator already
                        # admitted finish normally. Excluded from the
                        # 5xx health input like QoS sheds — a drain must
                        # not page as an error spike.
                        qos_rejected = True
                        with self._counter_lock:
                            self.drain_sheds += 1
                        if self.qos is not None:
                            self.qos.record_drain_shed()
                        self._record_shed(match, body, principal,
                                          "draining", 503)
                        st, ct, payload = self._error(
                            503, "node is draining (graceful restart): "
                                 "retry against another replica",
                            code="shed")
                        return (st, ct, payload, {
                            "Retry-After": "1",
                            "X-Pilosa-Shed-Reason": "draining"})
                    rej = None
                    if (plane is not None and qos.enabled()
                            and name == "post_query"
                            and not self._qos_inherited(query, headers)):
                        # [qos] default-deadline: every query gets a
                        # budget even when the client sent none, so
                        # deadline-aware shedding has something to shed
                        # against. Never applied to inherited fan-out
                        # entries — their budget is the coordinator's.
                        if (plane.default_deadline > 0
                                and qctx.deadline.get() is None):
                            import time as _t
                            qos_dl_token = qctx.deadline.set(
                                _t.monotonic() + plane.default_deadline)
                        # admission: quotas + deadline/health shedding,
                        # BEFORE the body is even parsed
                        rej = plane.admit(
                            principal or "anonymous",
                            qos.current_priority.get()
                            or plane.default_priority,
                            qctx.remaining())
                    if rej is not None:
                        qos_rejected = True
                        self._record_shed(match, body, principal,
                                          rej.reason, rej.status)
                        st, ct, payload = self._error(
                            rej.status, rej.message,
                            code=("quota-exhausted" if rej.status == 429
                                  else "shed"))
                        resp = (st, ct, payload, {
                            "Retry-After":
                                qos.retry_after_header(rej.retry_after),
                            "X-Pilosa-Shed-Reason": rej.reason})
                    else:
                        admit.finish()
                        resp = handler(match.groupdict(), query, body)
                except qctx.QueryTimeoutError as e:
                    resp = self._error(504, str(e))
                except ApiError as e:
                    resp = self._error(e.status, str(e), code=e.code)
                except Exception as e:  # noqa: BLE001 — surface as 500
                    resp = self._error(500, str(e))
                finally:
                    if is_work:
                        with self._counter_lock:
                            self.active_queries -= 1
                    if qos_dl_token is not None:
                        qctx.deadline.reset(qos_dl_token)
                    if dl_token is not None:
                        qctx.deadline.reset(dl_token)
                if resp[0] >= 500 and not qos_rejected:
                    # server-error rate feeds the node health score (the
                    # telemetry sampler derives errors/s from this).
                    # Deliberate QoS sheds are EXCLUDED: counting them
                    # would raise the error rate, worsen health, and shed
                    # harder — a self-amplifying feedback loop.
                    self.errors_5xx += 1
                    if self.stats is not None:
                        self.stats.count("http/serverErrors")
                return resp
        finally:
            admit.finish()
            root = admit.parent
            if (not is_work and root is not None
                    and root.name == "http.request"):
                # the span table's `http.request` is a served work
                # request; status, debug and scrape routes are apart
                root.name = "http.other"
            tracing.current_tracer.reset(tracer_token)
            if token is not None:
                tracing.current_trace_id.reset(token)
            if acct_token is not None:
                accounting.current_account.reset(acct_token)
            if prio_token is not None:
                qos.current_priority.reset(prio_token)
        if any(rx.match(path) for _, rx, _ in ROUTES):
            return 405, "application/json", b'{"error": "method not allowed"}'
        return 404, "application/json", b'{"error": "not found"}'

    # -- helpers ------------------------------------------------------------

    @staticmethod
    def _qos_inherited(query: dict, headers) -> bool:
        """True when this query was fanned out BY a coordinator (the
        ?remote= flag or the inherited-principal header an internal RPC
        always carries): the coordinator already ran admission, and
        re-admitting at every remote would multiply one user query's
        quota charge by the fan-out width."""
        vals = query.get("remote")
        if vals and vals[0] in ("1", "true"):
            return True
        h = headers if headers is not None and hasattr(headers, "get") \
            else {}
        return bool(h.get(accounting.PRINCIPAL_HEADER))

    def _record_shed(self, match, body: bytes, principal, reason: str,
                     status: int) -> None:
        """Rejected queries (QoS quota/deadline/health sheds, drain
        sheds) used to VANISH: /debug/query-history recorded only
        executed queries, so an operator reconstructing an incident saw
        the latency tail but never WHAT was rejected. Shed requests land
        in the same ring, marked by a `shed` reason, carrying the
        principal and priority the admission decision was made against
        and the (truncated) PQL that never ran."""
        hist = getattr(self.api, "query_history", None)
        if hist is None:
            return
        from datetime import datetime, timezone
        from pilosa_tpu.utils import profile as qprofile
        hist.append({
            "time": datetime.now(timezone.utc).isoformat(),
            "index": (match.groupdict() or {}).get("index", ""),
            "pql": qprofile.truncate_pql(
                body.decode("utf-8", "replace") if body else ""),
            "shed": reason,
            "status": status,
            "principal": principal or "anonymous",
            "priority": qos.current_priority.get() if qos.enabled()
            else None,
            "traceId": tracing.current_trace_id.get() or "-",
        })

    def _error(self, status: int, msg: str, code: str = ""):
        """Protobuf clients get errors as QueryResponse{Err} so they can
        unmarshal them (proto.go encodes Err the same way); JSON otherwise.
        `code` is the machine-readable discriminator (ApiError.code)."""
        if self._wants_proto():
            return (status, PROTO_CONTENT_TYPE,
                    self.serializer.encode_query_response([], err=msg))
        body = {"error": msg}
        if code:
            body["code"] = code
        return status, "application/json", json.dumps(body).encode()

    @staticmethod
    def _json(payload, status: int = 200):
        with tracing.span("http.encode"):
            return status, "application/json", json.dumps(payload).encode()

    @staticmethod
    def _body_json(body: bytes) -> dict:
        if not body:
            return {}
        try:
            out = json.loads(body)
        except json.JSONDecodeError as e:
            raise ApiError(f"invalid JSON body: {e}")
        if not isinstance(out, dict):
            raise ApiError("JSON body must be an object")
        return out

    @staticmethod
    def _arg(query: dict, name: str, default=None):
        vals = query.get(name)
        return vals[0] if vals else default

    # content negotiation (http/handler.go:915-988): JSON is the default;
    # application/x-protobuf selects the wire codec per request.
    def _header(self, name: str, default: str = "") -> str:
        h = getattr(self._local, "headers", None)
        if h is None:
            return default
        return h.get(name, default) if hasattr(h, "get") else default

    def _wants_proto(self) -> bool:
        return PROTO_CONTENT_TYPE in self._header("Accept")

    def _sends_proto(self) -> bool:
        return PROTO_CONTENT_TYPE in self._header("Content-Type")

    # -- public handlers ----------------------------------------------------

    def home(self, params, query, body):
        return self._json({"name": "pilosa-tpu", "version": self.api.version()})

    def post_query(self, params, query, body):
        if self._sends_proto():
            req = self.serializer.decode_query_request(body)
            pql, shard_list, remote = req["query"], req["shards"], req["remote"]
            column_attrs = bool(req.get("columnAttrs"))
            ex_attrs = bool(req.get("excludeRowAttrs"))
            ex_cols = bool(req.get("excludeColumns"))
            want_profile = bool(req.get("profile"))
        else:
            shards = self._arg(query, "shards")
            shard_list = [int(s) for s in shards.split(",")] if shards else None
            remote = self._arg(query, "remote") in ("1", "true")
            column_attrs = self._arg(query, "columnAttrs") in ("1", "true")
            ex_attrs = self._arg(query, "excludeRowAttrs") in ("1", "true")
            ex_cols = self._arg(query, "excludeColumns") in ("1", "true")
            want_profile = self._arg(query, "profile") in ("1", "true")
            pql = body.decode()
            if self._arg(query, "explain") in ("1", "true"):
                # ?explain=true: return the planned tree instead of
                # executing — zero device dispatches (api.explain).
                # JSON-only: the protobuf QueryResponse has no explain
                # shape and legacy decoders would choke on one
                if self._wants_proto():
                    raise ApiError("explain=true requires a JSON response"
                                   " (drop the protobuf Accept header)")
                return self._json(self.api.explain(params["index"], pql,
                                                   shards=shard_list))
        if self._wants_proto():
            results = self.api.query_results(params["index"], pql,
                                             shards=shard_list, remote=remote,
                                             exclude_row_attrs=ex_attrs,
                                             exclude_columns=ex_cols,
                                             profile=want_profile)
            cas = (self.api.column_attr_sets(params["index"], results)
                   if column_attrs else None)
            prof = None
            if want_profile:
                # published by api.query_results in this same context; rides
                # QueryResponse.Profile (absent for legacy/off — decoders
                # degrade gracefully)
                from pilosa_tpu.utils import profile as qprofile
                got = qprofile.last_profile.get()
                prof = got.to_dict() if got is not None else None
            with tracing.span("http.encode"):
                payload = self.serializer.encode_query_response(
                    results, column_attr_sets=cas, profile=prof)
            return 200, PROTO_CONTENT_TYPE, payload
        return self._json(self.api.query(params["index"], pql,
                                         shards=shard_list, remote=remote,
                                         column_attrs=column_attrs,
                                         exclude_row_attrs=ex_attrs,
                                         exclude_columns=ex_cols,
                                         profile=want_profile))

    def get_indexes(self, params, query, body):
        return self._json(self.api.schema())

    def get_index(self, params, query, body):
        for idx in self.api.schema()["indexes"]:
            if idx["name"] == params["index"]:
                return self._json(idx)
        raise ApiError(f"index not found: {params['index']}", status=404)

    def post_index(self, params, query, body):
        opts = self._body_json(body).get("options", {})
        self.api.create_index(params["index"], keys=opts.get("keys", False),
                              track_existence=opts.get("trackExistence", True))
        return self._json({"success": True})

    def delete_index(self, params, query, body):
        self.api.delete_index(params["index"])
        return self._json({"success": True})

    def post_field(self, params, query, body):
        o = self._body_json(body).get("options", {})
        options = FieldOptions(
            type=o.get("type", "set"),
            cache_type=o.get("cacheType", "ranked"),
            cache_size=o.get("cacheSize", 50000),
            min=o.get("min", 0),
            max=o.get("max", 0),
            time_quantum=o.get("timeQuantum", ""),
            keys=o.get("keys", False),
        )
        self.api.create_field(params["index"], params["field"], options)
        return self._json({"success": True})

    def delete_field(self, params, query, body):
        self.api.delete_field(params["index"], params["field"])
        return self._json({"success": True})

    def post_import(self, params, query, body):
        if self._sends_proto():
            # the wire carries ImportRequest or ImportValueRequest on the same
            # endpoint; the field's type picks the message (handler.go:990)
            fld = self.api.holder.index(params["index"])
            fld = fld.field(params["field"]) if fld is not None else None
            if fld is not None and fld.options.type == "int":
                req = self.serializer.decode_import_value_request(body)
            else:
                req = self.serializer.decode_import_request(body)
        else:
            req = self._body_json(body)
        remote = bool(req.get("remote", False))
        if "values" in req:
            self.api.import_values(
                params["index"], params["field"],
                column_ids=req.get("columnIDs"), values=req.get("values"),
                column_keys=req.get("columnKeys"), remote=remote)
        else:
            # clear=true (query param or body) treats the import as
            # clear-bits (handler.go:184, :1002-1004)
            clear = (self._arg(query, "clear") == "true"
                     or bool(req.get("clear", False)))
            self.api.import_bits(
                params["index"], params["field"],
                row_ids=req.get("rowIDs"), column_ids=req.get("columnIDs"),
                row_keys=req.get("rowKeys"), column_keys=req.get("columnKeys"),
                timestamps=req.get("timestamps"), remote=remote, clear=clear)
        return self._json({})

    def post_import_roaring(self, params, query, body):
        if self._sends_proto():
            req = self.serializer.decode_import_roaring_request(body)
            views = req["views"]
        else:
            req = self._body_json(body)
            views = {name: base64.b64decode(data)
                     for name, data in req.get("views", {}).items()}
        # the reference carries these as URL params (PostImportRoaring
        # Optional("remote", "clear"), handler.go:185); accept either
        self.api.import_roaring(
            params["index"], params["field"], int(params["shard"]), views,
            clear=(self._arg(query, "clear") == "true"
                   or bool(req.get("clear", False))),
            remote=(self._arg(query, "remote") == "true"
                    or bool(req.get("remote", False))))
        return self._json({})

    def get_export(self, params, query, body):
        index = self._arg(query, "index")
        field = self._arg(query, "field")
        shard = self._arg(query, "shard")
        if index is None or field is None or shard is None:
            raise ApiError("index, field and shard are required")
        out = self.api.export_csv(index, field, int(shard))
        return 200, "text/csv", out.encode()

    def get_schema(self, params, query, body):
        return self._json(self.api.schema())

    def get_status(self, params, query, body):
        return self._json(self.api.status())

    def get_info(self, params, query, body):
        return self._json(self.api.info())

    def get_version(self, params, query, body):
        return self._json({"version": self.api.version()})

    def get_debug_vars(self, params, query, body):
        snap = self.stats.snapshot() if self.stats is not None else {}
        ex = getattr(self.api, "executor", None)
        if ex is not None:
            residency = getattr(ex, "residency", None)
            if residency is not None:
                snap["deviceResidency"] = residency.snapshot()
                row_stats = getattr(ex, "row_stats", None)
                if row_stats is not None:
                    # the row statistics memo beside the leaves' own
                    # hits and misses (docs/operations.md)
                    snap["deviceResidency"].update(row_stats.snapshot())
            snap["topnRecountRows"] = getattr(ex, "topn_recount_rows", 0)
            snap["groupByHostSyncs"] = getattr(ex, "groupby_host_syncs", 0)
            snap["topnPairsRecounts"] = getattr(ex, "topn_pairs_recounts", 0)
            snap["topnPairsRecountsByColumn"] = getattr(
                ex, "topn_pairs_recounts_by_column", 0)
            snap["topnPairsRecountsBySlab"] = getattr(
                ex, "topn_pairs_recounts_by_slab", 0)
            snap["topnPairsBytes"] = getattr(ex, "topn_pairs_bytes", 0)
            snap["pairsEntriesBuilt"] = getattr(ex, "pairs_entries_built", 0)
            snap["pairsEntryBytes"] = getattr(ex, "pairs_entry_bytes", 0)
            snap["topnBandIn"] = getattr(ex, "topn_band_in", 0)
            snap["topnBandKept"] = getattr(ex, "topn_band_kept", 0)
            batcher = getattr(ex, "batcher", None)
            if batcher is not None:
                snap["countBatcher"] = batcher.snapshot()
            sum_batcher = getattr(ex, "sum_batcher", None)
            if sum_batcher is not None:
                snap["planeSumBatcher"] = sum_batcher.snapshot()
            mm = getattr(ex, "minmax_batcher", None)
            if mm is not None:
                snap["minMaxBatcher"] = mm.snapshot()
            # network-layer fan-out coalescing + hedging (net/coalesce.py):
            # batch-size distribution, mean coalesce factor, 404-fallback
            # counters, and the hedged-read race outcomes
            coal = getattr(ex, "coalescer", None)
            if coal is not None:
                snap["netCoalesce"] = coal.snapshot()
            # cost-based planner + generation-keyed plan cache
            # (pilosa_tpu/planner.py, parallel/residency.py PlanCache):
            # reorder/pushdown/short-circuit decision counts and the
            # cross-query subexpression cache's occupancy/hit economics
            pl = getattr(ex, "planner", None)
            if pl is not None:
                snap["planner"] = pl.snapshot()
                # EXPLAIN est-vs-actual calibration ring (planner.py
                # CalibrationRing): recent estimate/result pairs and the
                # aggregate relative-error stats
                from pilosa_tpu import planner as _planner
                snap["planner"]["calibration"] = \
                    _planner.calibration.snapshot()
            pc = getattr(ex, "plan_cache", None)
            if pc is not None:
                snap["planCache"] = pc.snapshot()
            # HBM residency map (executor.hbm_snapshot): the compact
            # summary rides the expvar dump; GET /debug/hbm carries the
            # per-(index, field, rep) breakdown and the pin set
            if hasattr(ex, "hbm_snapshot"):
                try:
                    hbm = ex.hbm_snapshot(top=0)
                except Exception:  # noqa: BLE001 — never 500 the dump
                    hbm = None
                if hbm is not None:
                    snap["hbm"] = {k: hbm[k] for k in
                                   ("budgetBytes", "residentBytes",
                                    "headroomBytes", "accountedBytes",
                                    "planCacheBytes", "wasteByRep",
                                    "allocator", "hbmDriftBytes")}
            # hybrid sparse/dense containers (parallel/residency.py
            # HybridManager): uploads and promote/demote transitions by
            # representation, plus live sparse/dense leaf occupancy —
            # the operator's view of how much HBM the sparse rows return
            if hasattr(ex, "hybrid_snapshot"):
                snap["hybrid"] = ex.hybrid_snapshot()
            # coalesced streaming ingest (parallel/ingest.py +
            # executor._apply_ingest_*): batch/coalesce economics, WAL
            # group-commit ratio (mutations per fsync-able append), and
            # the in-place resident-leaf patch counters
            if hasattr(ex, "ingest_snapshot"):
                snap["ingest"] = ex.ingest_snapshot()
            # fragment heat map (utils/heat.py): top hot/cold fragments,
            # totals, skew — the expvar mirror of GET /debug/heat
            tracker = getattr(ex, "heat", None)
            if tracker is not None:
                snap["heat"] = tracker.snapshot(top=10)
            snap["hedges"] = {
                "hedgesFired": getattr(ex, "hedges_fired", 0),
                "hedgesWon": getattr(ex, "hedges_won", 0),
                "hedgesCancelled": getattr(ex, "hedges_cancelled", 0),
            }
            # ICI slice-local serving (executor._ici_route): route
            # decision counters + the shard_map serving-mode program
            # cache — the dashboard's slice-local-share sparkline source
            if hasattr(ex, "ici_snapshot"):
                snap["iciServing"] = ex.ici_snapshot()
            # one node, several chips: how many devices a leaf is laid
            # over, and the launches that went to more than one, by
            # whether their program holds a collective; collectiveThreads
            # is how many threads ever launched one of those (0 or 1:
            # parallel/mesh.py on_collective_thread)
            runner = getattr(ex, "runner", None)
            if runner is not None:
                snap["mesh"] = runner.mesh_snapshot()
            # durable hinted handoff (storage/hints.py): queued/replayed/
            # dropped totals + per-target pending bytes — the previously
            # silent skipped-replica writes, now an operator surface
            hints = getattr(ex, "hints", None)
            if hints is not None:
                snap["writeHandoffs"] = hints.snapshot()
            # rejoin read fence: shards still awaiting parity verification
            fence = ex.fence_snapshot()
            if any(fence.values()):
                snap["readFence"] = fence
        # graceful-drain lifecycle state (server.drain)
        if self.api.drain_status_fn is not None:
            snap["drain"] = self.api.drain_status_fn()
        # flight-recorder journal (utils/events.py): per-type emit
        # counts, lane occupancy/evictions, spool state
        if self.events is not None:
            snap["events"] = self.events.snapshot()
        holder = getattr(self.api, "holder", None)
        if holder is not None:
            # volatility surface (frozen bulk loads are NOT durable until
            # an explicit snapshot; mutations on them ride the same
            # contract): operators see which fragments would lose
            # acknowledged writes on restart, and how many such writes
            # have been taken
            vol = []
            for iname, fname, vname, shard, frag in holder.walk_fragments():
                if getattr(frag, "_volatile", False):
                    vol.append({
                        "index": iname, "field": fname,
                        "view": vname, "shard": shard,
                        "mutations": frag.volatile_mutations,
                    })
            if vol:
                snap["volatileFragments"] = vol
            # corruption-recovery surface: quarantined snapshots (pending /
            # completed replica rebuilds) and truncated torn WAL tails
            damaged = holder.damaged_fragments()
            if damaged:
                snap["damagedFragments"] = damaged
        # fault-injection counters (utils/failpoints.py): which points are
        # armed, per-point evaluation/fired counts, the chaos seed, and the
        # tail of the fired-action log — how a chaos run is audited live
        from pilosa_tpu.utils import failpoints
        fps = failpoints.snapshot()
        if fps["points"] or fps["armed"]:
            snap["failpoints"] = fps
        # per-principal usage ledger + SLO burn rates (the /debug/usage
        # document's totals/top rows, mirrored here so the expvar dump
        # stays the one-stop snapshot)
        ledger = getattr(self.api, "usage_ledger", None)
        if ledger is not None:
            snap["usage"] = ledger.snapshot(top=20)
        slo = getattr(self.api, "slo", None)
        if slo is not None:
            snap["slo"] = slo.evaluate()
        # multi-tenant QoS plane (pilosa_tpu/qos.py): admission verdicts
        # per priority/reason/principal, the live wait estimate, mode
        if self.qos is not None:
            snap["qos"] = self.qos.snapshot()
        # device kernel latency attribution (utils/telemetry.py
        # KernelStats): per-(family, rep, arity) dispatch counts, log2
        # latency histograms, batcher queue-wait split, h2d/d2h bytes
        from pilosa_tpu.utils import telemetry as _telemetry
        snap["kernels"] = _telemetry.kernels.snapshot()
        # the span table (utils/tracing.py SpanStats): per span name n /
        # wallMs / selfMs / cpuMs / log2 buckets since process start, and
        # the clock (`nowMs`) a delta of two dumps is taken over — the
        # one source per layer boundary
        snap["spans"] = tracing.spans.snapshot()
        # where threads wait (utils/tracing.py): contended waits at each
        # named lock, and the interpreter's own — its collections by
        # generation and, during a capture, its lock's hand-off
        snap["locks"] = tracing.locks_snapshot()
        snap["runtime"] = tracing.runtime_snapshot()
        # which devices this node serves from (platform, device_kind,
        # allocator stats): the first thing to read before trusting any
        # device number from this dump
        snap["deviceMemory"] = _telemetry.device_memory_stats()
        # on-demand XLA profile capture state (POST /debug/device-profile)
        snap["deviceProfiler"] = _telemetry.device_profiler.snapshot()
        return self._json(snap)

    def get_debug_hbm(self, params, query, body):
        """HBM residency map (executor.hbm_snapshot): what the residency
        accounting says lives in device memory — resident leaves by
        (index, field, representation) at real padded byte cost with
        per-rep padding waste, non-row kinds by kind, plan-cache bytes,
        budget headroom and the heat advisor's pin set — joined against
        the backend allocator's memory_stats() with the accounted-vs-
        allocator drift called out (`hbmDriftBytes`). `?top=` bounds the
        per-field list (default 64, 0 = all)."""
        ex = getattr(self.api, "executor", None)
        if ex is None or not hasattr(ex, "hbm_snapshot"):
            raise ApiError("hbm map not supported", status=501)
        try:
            top = int(self._arg(query, "top", "64"))
        except ValueError:
            raise ApiError("top must be an integer")
        return self._json(ex.hbm_snapshot(top=top))

    def get_cluster_hbm(self, params, query, body):
        """The fleet's HBM residency maps: every live peer's /debug/hbm
        document collected over the persistent fan-out pool
        (Server.cluster_hbm — legacy peers that 404 the route degrade to
        "legacy", never an error)."""
        if self.api.cluster_hbm_fn is None:
            raise ApiError("cluster hbm not supported", status=501)
        return self._json(self.api.cluster_hbm_fn())

    def post_device_profile(self, params, query, body):
        """On-demand XLA profile capture (utils/telemetry.py
        DeviceProfiler): wraps ?seconds= of live traffic in
        jax.profiler.trace into a byte-capped spool dir and returns the
        capture path. Never blocks serving — a concurrent capture
        answers "busy", the PILOSA_TPU_DEVICE_PROFILE=0 kill switch
        answers "disabled"; both are 409/403-free 200s so operator
        tooling can poll without special-casing."""
        from pilosa_tpu.utils import telemetry as _telemetry
        try:
            seconds = float(self._arg(query, "seconds", "2"))
        except ValueError:
            raise ApiError("seconds must be a number")
        return self._json(_telemetry.device_profiler.capture(seconds))

    def get_query_history(self, params, query, body):
        """Structured slow-query history (the SLOW QUERY printf grown into
        an operator surface): the last `query-history-size` queries over
        long-query-time, newest first — trace id, truncated PQL, elapsed
        seconds, and the full cross-node profile tree when profiling was
        on for that query (profile_mode auto profiles every query while
        long-query-time is set, so slow queries normally carry one)."""
        return self._json({"queries": self.api.query_history.snapshot()})

    def get_debug_timeseries(self, params, query, body):
        """Incremental time-series ring data (utils/telemetry.py sampler):
        `?since=<seq>` returns only samples newer than the cursor, so a
        poller transfers each sample once; the response's `seq` is the
        next cursor. Memory stays bounded by the ring regardless of how
        many pollers exist or how rarely they poll."""
        from pilosa_tpu.utils import telemetry as _telemetry
        try:
            since = int(self._arg(query, "since", "0"))
            limit = int(self._arg(query, "limit", "0"))
        except ValueError:
            raise ApiError("since and limit must be integers")
        if self.telemetry is None:
            return self._json({"seq": 0, "interval": 0.0, "ringSize": 0,
                               "enabled": False, "samples": []})
        out = self.telemetry.ring.since(since, limit)
        out["interval"] = self.telemetry.interval
        out["ringSize"] = self.telemetry.ring.size
        out["enabled"] = _telemetry.enabled() and self.telemetry.running
        return self._json(out)

    def get_debug_dashboard(self, params, query, body):
        """Self-contained live fleet dashboard (net/dashboard.py): one
        HTML file, inline CSS/JS/SVG, zero external assets — works
        air-gapped from any node's port."""
        from pilosa_tpu.net.dashboard import render_dashboard
        return 200, "text/html; charset=utf-8", render_dashboard().encode()

    def get_debug_usage(self, params, query, body):
        """Per-principal usage ledger (utils/accounting.py): aggregates
        sorted by device-ms (`?top=` bounds the list), exact totals, the
        since-cursor delta ring (`?since=` — the /debug/timeseries
        contract, each tick transfers once), and the current SLO
        burn-rate evaluation."""
        ledger = getattr(self.api, "usage_ledger", None)
        if ledger is None:
            raise ApiError("usage accounting not supported", status=501)
        try:
            since = int(self._arg(query, "since", "0"))
            limit = int(self._arg(query, "limit", "0"))
            top = int(self._arg(query, "top", "0"))
        except ValueError:
            raise ApiError("since, limit and top must be integers")
        out = ledger.snapshot(top=top)
        out.update(ledger.since(since, limit))
        out["enabled"] = ledger.enabled and accounting.enabled()
        slo = getattr(self.api, "slo", None)
        if slo is not None:
            out["slo"] = slo.evaluate()
        return self._json(out)

    def get_debug_heat(self, params, query, body):
        """Fragment heat map (utils/heat.py HeatTracker): top-K hot and
        cold fragment lists with scores and charge fields, exact totals,
        the score distribution and the skew gauge, plus the since-cursor
        summary ring (`?since=` — the /debug/timeseries contract).
        `?advice=true` appends the placement advisor's dry-run
        recommendations (analysis/advisor.py)."""
        from pilosa_tpu.utils import heat as _heat
        ex = getattr(self.api, "executor", None)
        tracker = getattr(ex, "heat", None) if ex is not None else None
        try:
            since = int(self._arg(query, "since", "0"))
            limit = int(self._arg(query, "limit", "0"))
            top = int(self._arg(query, "top", "20"))
        except ValueError:
            raise ApiError("since, limit and top must be integers")
        if tracker is None:
            # kill switch (PILOSA_TPU_HEAT=0) or a bare API: the surface
            # answers with an empty document, never a 404 — pollers and
            # the dashboard degrade instead of erroring
            return self._json({"enabled": False, "hot": [], "cold": [],
                               "totals": {}, "trackedFragments": 0,
                               "spilledFragments": 0, "hotFragments": 0,
                               "skew": 1.0, "seq": 0, "samples": []})
        out = tracker.snapshot(top=top)
        out.update(tracker.since(since, limit))
        out["enabled"] = tracker.enabled and _heat.enabled()
        if self._arg(query, "advice") in ("1", "true"):
            from pilosa_tpu.analysis.advisor import advise
            res = getattr(ex, "residency", None)
            out["advice"] = advise(
                tracker.snapshot(top=0),
                residency=res.snapshot() if res is not None else None,
                budget_bytes=res.budget if res is not None else 0)
        return self._json(out)

    def get_debug_events(self, params, query, body):
        """Flight-recorder event feed (utils/events.py EventJournal):
        `?since=<seq>` returns only events newer than the cursor (the
        /debug/timeseries discipline — each event crosses the wire once
        per poller); `?type=` / `?severity=lifecycle|log` filter. Every
        event carries the node's HLC stamp, so feeds from several nodes
        merge into one causal timeline (GET /cluster/events does exactly
        that)."""
        from pilosa_tpu.utils import events as _events
        try:
            since = int(self._arg(query, "since", "0"))
            limit = int(self._arg(query, "limit", "0"))
        except ValueError:
            raise ApiError("since and limit must be integers")
        etype = self._arg(query, "type")
        severity = self._arg(query, "severity")
        if severity and severity not in _events.LANES:
            raise ApiError(
                f"invalid severity {severity!r} (expected "
                f"{' | '.join(_events.LANES)})")
        if etype and etype not in _events.EVENT_TYPES:
            raise ApiError(f"unknown event type {etype!r}")
        if self.events is None:
            return self._json({"seq": 0, "enabled": False, "node": "",
                               "events": []})
        out = self.events.since(since, limit, etype=etype,
                                severity=severity)
        out["enabled"] = _events.enabled()
        out["node"] = self.events.node_id
        return self._json(out)

    def get_cluster_events(self, params, query, body):
        """The merged cluster timeline: every live peer's /debug/events
        feed collected concurrently and HLC-sorted into one causal event
        stream (Server.cluster_events — legacy peers that 404 the route
        degrade to "legacy", never an error)."""
        if self.api.cluster_events_fn is None:
            raise ApiError("cluster events not supported", status=501)
        try:
            limit = int(self._arg(query, "limit", "0"))
        except ValueError:
            raise ApiError("limit must be an integer")
        return self._json(self.api.cluster_events_fn(limit=limit))

    def get_cluster_heat(self, params, query, body):
        """The fleet's merged fragment heat map: every live peer's
        /debug/heat document collected over the persistent fan-out pool
        and merged per fragment (Server.cluster_heat — legacy peers that
        404 the route degrade, never an error)."""
        if self.api.cluster_heat_fn is None:
            raise ApiError("cluster heat not supported", status=501)
        return self._json(self.api.cluster_heat_fn())

    def get_cluster_usage(self, params, query, body):
        """The fleet's merged per-principal usage: every live peer's
        ledger collected and summed per principal (Server.cluster_usage —
        legacy peers that 404 the route degrade, never an error)."""
        if self.api.cluster_usage_fn is None:
            raise ApiError("cluster usage not supported", status=501)
        return self._json(self.api.cluster_usage_fn())

    def get_internal_stats(self, params, query, body):
        """This node's fleet-telemetry document (fanned over by a peer's
        /cluster/stats). Nodes that predate this route 404 it, and the
        federation marks them "legacy" — never an error."""
        if self.api.node_stats_fn is None:
            raise ApiError("node stats not supported", status=501)
        return self._json(self.api.node_stats_fn())

    def get_cluster_stats(self, params, query, body):
        """The merged fleet document: every live peer's stats snapshot
        collected over the persistent fan-out pool, with per-node health
        scores (legacy peers degrade to "legacy"; down peers are "red")."""
        if self.api.cluster_stats_fn is None:
            raise ApiError("cluster stats not supported", status=501)
        return self._json(self.api.cluster_stats_fn())

    def get_metrics(self, params, query, body):
        """Prometheus text exposition of the StatsClient snapshot
        (GET /metrics): counters, gauges, set cardinalities, and the log2
        timing buckets converted to cumulative `_bucket{le=...}` series
        with `_sum`/`_count` (utils/stats.py prometheus_exposition). The
        expvar JSON at /debug/vars stays; this is the scrape surface.
        Gauges that previously lived only in /debug/vars — HBM residency,
        damaged fragments, batcher queues, hedges, XLA compile counters —
        are merged in here so scrapers can alert on them."""
        from pilosa_tpu.utils import failpoints
        from pilosa_tpu.utils import telemetry as _telemetry
        from pilosa_tpu.utils.stats import prometheus_exposition
        snap = self.stats.snapshot() if self.stats is not None else {}
        counts = dict(snap.get("counts", {}))
        gauges = dict(snap.get("gauges", {}))
        counts.update({f"failpoints/{name}": c["fired"]
                       for name, c in failpoints.counters().items()
                       if c["fired"]})
        ex = getattr(self.api, "executor", None)
        res = getattr(ex, "residency", None) if ex is not None else None
        if res is not None:
            rs = res.snapshot()
            gauges["residency/bytes"] = rs["bytes"]
            gauges["residency/budget"] = float(res.budget)
            gauges["residency/entries"] = rs["entries"]
            # WINDOWED hit rate (the sampler's, when it runs): a lifetime
            # ratio stays >0.9 for hours after a warm node starts
            # thrashing, which would suppress the churn alert exactly
            # when it matters; lifetime ratio is the cold-start fallback
            latest = (self.telemetry.ring.latest()
                      if self.telemetry is not None else {})
            lookups = rs["hits"] + rs["misses"]
            gauges["residency/hitRate"] = latest.get(
                "residency.hit_rate",
                rs["hits"] / lookups if lookups else 1.0)
            counts["residency/hits"] = rs["hits"]
            counts["residency/misses"] = rs["misses"]
            counts["residency/evictions"] = rs["evictions"]
            counts["residency/heatEvictions"] = rs["heatEvictions"]
        if ex is not None:
            for attr, kind in (("batcher", "count"),
                               ("sum_batcher", "planeSum"),
                               ("minmax_batcher", "minMax")):
                b = getattr(ex, attr, None)
                if b is None:
                    continue
                bs = b.snapshot()
                counts[f"batcher/{kind}/batches"] = bs["batches"]
                counts[f"batcher/{kind}/queries"] = bs["batched_queries"]
                gauges[f"batcher/{kind}/queueDepth"] = bs["queue_depth"]
            counts["hedges/fired"] = getattr(ex, "hedges_fired", 0)
            counts["hedges/won"] = getattr(ex, "hedges_won", 0)
            counts["hedges/cancelled"] = getattr(ex, "hedges_cancelled", 0)
            # coalesced streaming ingest: the full keyspace emitted
            # unconditionally (zeros included) so an "ingest stalled" or
            # "fsync ratio collapsed" alert never races the first write
            # for the family to exist
            if hasattr(ex, "ingest_snapshot"):
                ing = ex.ingest_snapshot()
                counts["ingest,op:set"] = ing["setMutations"]
                counts["ingest,op:clear"] = ing["clearMutations"]
                counts["ingestBatches,kind:applied"] = ing["appliedBatches"]
                counts["ingestBatches,kind:remote"] = ing["remoteBatches"]
                counts["ingestWal/appends"] = ing["walAppends"]
                counts["ingestWal/ops"] = ing["walOps"]
                counts["ingestPatch,kind:dense"] = ing["patchedDense"]
                counts["ingestPatch,kind:sparse"] = ing["patchedSparse"]
                counts["ingestPatch,kind:dropped"] = ing["patchDropped"]
                counts["ingest/hinted"] = ing["hintedMutations"]
                counts["ingest/errors"] = ing["errors"]
                gauges["ingest/queueDepth"] = ing["queue_depth"]
                gauges["ingest/enabled"] = 1.0 if ing["enabled"] else 0.0
            # ICI slice-local routing: the full route keyspace emitted
            # unconditionally (zeros included) like the planner families,
            # so a "slice-local share collapsed" alert never races the
            # first routed query for the family to exist
            if hasattr(ex, "ici_snapshot"):
                isnap = ex.ici_snapshot()
                counts["iciServing,route:slice_local"] = isnap["sliceLocal"]
                counts["iciServing,route:cross_slice"] = isnap["crossSlice"]
                counts["iciServing,route:fallback"] = isnap["fallback"]
                ipc = isnap["programCache"]
                counts["iciProgramCache/hits"] = ipc["hits"]
                counts["iciProgramCache/misses"] = ipc["misses"]
                gauges["iciProgramCache/programs"] = ipc["programs"]
                gauges["iciServing/mode"] = {
                    "off": 0.0, "auto": 1.0, "on": 2.0}.get(
                        isnap["mode"], 1.0)
            # query planner + plan cache: emitted unconditionally (zeros
            # included) so scrapers can alert on "planner stopped
            # reordering" / "cache hit rate collapsed" without a
            # first-event race in the family's existence
            pl = getattr(ex, "planner", None)
            if pl is not None:
                ps = pl.snapshot()
                counts["planner/plans"] = ps["plans"]
                counts["planner/reorders"] = ps["reorders"]
                counts["planner/pushdowns"] = ps["pushdowns"]
                counts["planner/shortCircuits"] = ps["shortCircuits"]
            pc = getattr(ex, "plan_cache", None)
            if pc is not None:
                cs = pc.snapshot()
                counts["planCache/hits"] = cs["hits"]
                counts["planCache/misses"] = cs["misses"]
                counts["planCache/evictions"] = cs["evictions"]
                gauges["planCache/bytes"] = cs["bytes"]
                gauges["planCache/entries"] = cs["entries"]
            # hybrid sparse/dense containers: the full rep/transition
            # keyspace emitted unconditionally (zeros included) like the
            # planner families, so a "sparse share collapsed" alert never
            # races the first sparse upload for the family to exist
            if hasattr(ex, "hybrid_snapshot"):
                hy = ex.hybrid_snapshot()
                counts["hybrid,rep:sparse"] = hy["sparseUploads"]
                counts["hybrid,rep:run"] = hy["runUploads"]
                counts["hybrid,rep:dense"] = hy["denseUploads"]
                counts["hybrid,transition:promoted"] = hy["promoted"]
                counts["hybrid,transition:demoted"] = hy["demoted"]
                counts["hybrid,transition:materialized"] = \
                    hy["materialized"]
                counts["hybrid,transition:run"] = hy["runTransitions"]
                gauges["hybridLeaves,rep:sparse"] = \
                    hy["residentSparseLeaves"]
                gauges["hybridLeaves,rep:run"] = \
                    hy["residentRunLeaves"]
                gauges["hybridLeaves,rep:dense"] = \
                    hy["residentDenseRowLeaves"]
                gauges["hybridBytes,rep:sparse"] = \
                    hy["residentSparseBytes"]
                gauges["hybridBytes,rep:run"] = \
                    hy["residentRunBytes"]
                gauges["hybridBytes,rep:dense"] = \
                    hy["residentDenseRowBytes"]
                gauges["hybrid/threshold"] = float(hy["threshold"])
                gauges["hybrid/runThreshold"] = float(hy["runThreshold"])
                gauges["hybrid/enabled"] = 1.0 if hy["enabled"] else 0.0
            # hinted handoff + rejoin fence: emitted unconditionally
            # (zeros included) like the planner families — "hint log
            # growing" / "fence stuck" alerts must never race the first
            # skipped write for the family to exist
            hints = getattr(ex, "hints", None)
            if hints is not None:
                hsnap = hints.snapshot()
                counts["writeHandoffs/queued"] = hsnap["queued"]
                counts["writeHandoffs/replayed"] = hsnap["replayed"]
                counts["writeHandoffs/dropped"] = hsnap["dropped"]
                counts["writeHandoffs/replayFailures"] = \
                    hsnap["replayFailures"]
                gauges["writeHandoffs/pendingBytes"] = hsnap["pendingBytes"]
                gauges["writeHandoffs/pendingTargets"] = len(
                    hsnap["pendingTargets"])
            fence = ex.fence_snapshot()
            counts["readFence/rerouted"] = fence["rerouted"]
            counts["readFence/refusedRemote"] = fence["refusedRemote"]
            counts["readFence/servedStale"] = fence["servedStale"]
            gauges["readFence/fencedShards"] = fence["fencedShards"]
            # fragment heat families (utils/heat.py): aggregate-only —
            # per-fragment cardinality lives behind /debug/heat, the
            # scrape stays bounded regardless of fragment count. Emitted
            # unconditionally while a tracker exists (zeros included)
            # like every family above, so "fleet went cold" / "skew
            # spiked" alerts never race the first access. The score
            # distribution rides cumulative le labels (a histogram
            # SNAPSHOT: gauge semantics, since scores decay).
            tracker = getattr(ex, "heat", None)
            if tracker is not None:
                hsnap2 = tracker.snapshot(top=0)
                for f, v in hsnap2["totals"].items():
                    counts[f"heat/{f}"] = round(v, 3)
                gauges["heat/trackedFragments"] = \
                    hsnap2["trackedFragments"]
                gauges["heat/spilledFragments"] = \
                    hsnap2["spilledFragments"]
                gauges["heat/hotFragments"] = hsnap2["hotFragments"]
                gauges["heat/skew"] = hsnap2["skew"]
                for le, n in hsnap2["distribution"].items():
                    gauges[f"heatDistribution/score,le:{le}"] = float(n)
        holder = getattr(self.api, "holder", None)
        if holder is not None:
            damaged = holder.damaged_fragments()
            gauges["damagedFragments"] = len(damaged)
            gauges["damagedFragmentsNeedingRebuild"] = sum(
                1 for d in damaged if d["needsRebuild"])
            gauges["walPoisonedFragments"] = sum(
                1 for *_, frag in holder.walk_fragments()
                if getattr(getattr(frag, "storage", None),
                           "wal_poisoned", False))
        xs = _telemetry.xla.snapshot()
        for fam, f in xs["families"].items():
            counts[f"xlaCompiles/{fam}"] = f["compiles"]
            counts[f"xlaCachedDispatches/{fam}"] = f["cached"]
        counts["xlaRecompileStorms"] = xs["storms"]
        # device kernel attribution families: the FULL registered
        # (family, rep) keyspace from the import-free inventory
        # (constants.KERNEL_FAMILY_REPS) emitted unconditionally (zeros
        # included) like the planner families, so a "sparse kernels
        # stalled" alert never races the first dispatch; live series
        # (including the timing histograms) overlay the zero floor
        from pilosa_tpu.constants import KERNEL_FAMILY_REPS
        for fam, rep in sorted(KERNEL_FAMILY_REPS.items()):
            counts.setdefault(f"kernelsDispatches/{fam},rep:{rep}", 0)
            counts.setdefault(f"kernelsWaitMs/{fam},rep:{rep}", 0)
            counts.setdefault(f"kernelsWaited/{fam},rep:{rep}", 0)
            counts.setdefault(f"kernelsH2dBytes/{fam},rep:{rep}", 0)
            counts.setdefault(f"kernelsD2hBytes/{fam},rep:{rep}", 0)
        kcounts, ktimings = _telemetry.kernels.metrics_view()
        counts.update(kcounts)
        timings = dict(snap.get("timings", {}))
        timings.update(ktimings)
        # pilosa_spanMs{span=...}: wall time per span name
        timings.update(tracing.spans.metrics_view())
        # HBM residency families: accounted bytes per representation
        # (zeros, plan cache and drift included) — the full rep keyspace
        # emitted unconditionally so headroom/drift alerts need no
        # family bootstrap. rep labels follow the residency kind map.
        hbm_rep_of = {"row": "dense", "sparse": "sparse", "run": "run"}
        for rep in ("dense", "sparse", "run", "other"):
            gauges.setdefault(f"hbmResidentBytes,rep:{rep}", 0.0)
            gauges.setdefault(f"hbmResidentEntries,rep:{rep}", 0.0)
        if res is not None:
            rs2 = res.snapshot()
            for kind, e in rs2.get("by_kind", {}).items():
                rep = hbm_rep_of.get(kind, "other")
                gauges[f"hbmResidentBytes,rep:{rep}"] += float(e["bytes"])
                gauges[f"hbmResidentEntries,rep:{rep}"] += \
                    float(e["entries"])
            pc2 = getattr(ex, "plan_cache", None)
            pc_bytes = pc2.snapshot()["bytes"] if pc2 is not None else 0
            accounted = rs2["bytes"] + pc_bytes
            gauges["hbmPlanCacheBytes"] = float(pc_bytes)
            gauges["hbmBudgetBytes"] = float(res.budget)
            gauges["hbmHeadroomBytes"] = float(
                max(0, res.budget - rs2["bytes"]))
            drift = 0.0
            for dev in _telemetry.device_memory_stats():
                ms = dev["memoryStats"]
                if ms and "bytes_in_use" in ms:
                    drift = float(int(ms["bytes_in_use"]) - accounted)
                    break
            gauges["hbmDriftBytes"] = drift
        else:
            gauges.setdefault("hbmPlanCacheBytes", 0.0)
            gauges.setdefault("hbmBudgetBytes", 0.0)
            gauges.setdefault("hbmHeadroomBytes", 0.0)
            gauges.setdefault("hbmDriftBytes", 0.0)
        # per-principal usage + SLO burn-rate families: emitted
        # unconditionally (zeros included) like the planner families, so
        # scrapers can alert on "a principal's spend spiked" / "an SLO is
        # burning" without a first-event race in the family's existence
        ledger = getattr(self.api, "usage_ledger", None)
        if ledger is not None:
            us = ledger.snapshot()
            for f, v in us["totals"].items():
                counts[f"usage/{f}"] = round(v, 3)
            gauges["usage/trackedPrincipals"] = us["trackedPrincipals"]
            gauges["usage/spilledPrincipals"] = us["spilledPrincipals"]
            # per-principal series ride `principal` labels on the same
            # family; the scrape stays bounded by the ledger's own top-K
            # bound plus this explicit cap
            for i, (p, e) in enumerate(us["principals"].items()):
                if i >= 20:
                    break
                for f in ("deviceMs", "hbmBytes", "rpcBytes", "queueMs",
                          "queries", "errors"):
                    counts[f"usage/{f},principal:{p}"] = round(e[f], 3)
        slo = getattr(self.api, "slo", None)
        if slo is not None:
            worst = 0.0
            for name, ob in slo.evaluate().items():
                gauges[f"slo/burnShort,objective:{name}"] = ob["burnShort"]
                gauges[f"slo/burnLong,objective:{name}"] = ob["burnLong"]
                level = {"green": 0.0, "yellow": 1.0,
                         "red": 2.0}[ob["status"]]
                gauges[f"slo/status,objective:{name}"] = level
                worst = max(worst, level)
            gauges["slo/worst"] = worst
        # QoS admission families: the full priority/reason key space is
        # emitted unconditionally (zeros included) like the planner and
        # usage families, so "shed rate" alerts never race the first shed
        if self.qos is not None:
            qc, qg = self.qos.metrics_series()
            counts.update(qc)
            gauges.update(qg)
        # drain lifecycle: unconditional gauges + the shed counter so a
        # "rolling restart in progress" panel needs no family bootstrap
        if self.api.drain_status_fn is not None:
            ds = self.api.drain_status_fn()
            gauges["drain/draining"] = 1.0 if ds["draining"] else 0.0
            gauges["drain/activeQueries"] = ds["activeQueries"]
            counts["drain/shedQueries"] = ds["shedQueries"]
        # flight-recorder event families: the FULL registered type
        # keyspace emitted unconditionally (zeros included) like the qos
        # families, so an "event rate spiked" alert never races the
        # first emitted event for the family to exist
        if self.events is not None:
            from pilosa_tpu.utils import events as _events
            es = self.events.snapshot()
            for t in sorted(_events.EVENT_TYPES):
                counts[f"events,type:{t}"] = es["byType"].get(t, 0)
            for lane, n in es["evicted"].items():
                counts[f"events/evicted,lane:{lane}"] = n
            gauges["events/retained"] = float(
                sum(es["retained"].values()))
            gauges["events/spoolBytes"] = float(es["spoolBytes"])
        if self.api.health_fn is not None:
            try:
                score = self.api.health_fn()["score"]
                gauges["nodeHealth"] = {"green": 0.0, "yellow": 1.0,
                                        "red": 2.0}.get(score, 1.0)
            except Exception:  # noqa: BLE001
                pass  # scrape must never 500 on a health-input failure
        snap = dict(snap, counts=counts, gauges=gauges, timings=timings)
        body_out = prometheus_exposition(snap)
        return (200, "text/plain; version=0.0.4; charset=utf-8",
                body_out.encode())

    def get_debug_pprof(self, params, query, body):
        """Runtime profiling surface (/debug/pprof, http/handler.go:242).

        Go exposes pprof profiles; the analogs here: `goroutine` → live
        thread stacks (sys._current_frames), `profile` → cProfile stats
        sampled for ?seconds= (default 2), index → the profile list."""
        import sys
        import traceback
        profile = params.get("profile") or ""
        if profile in ("", "index"):
            return self._json({"profiles": ["goroutine", "profile"]})
        if profile == "goroutine":
            frames = sys._current_frames()
            stacks = {
                str(tid): traceback.format_stack(frame)
                for tid, frame in frames.items()
            }
            return self._json({"threads": len(stacks), "stacks": stacks})
        if profile == "profile":
            # sampling profiler: poll all threads' frames for ?seconds=,
            # report hottest (file:line function) sites by sample count
            import time as _time
            from collections import Counter
            seconds = min(float(self._arg(query, "seconds", 2)), 30.0)
            hits: Counter = Counter()
            me = __import__("threading").get_ident()
            deadline = _time.monotonic() + seconds
            samples = 0
            while _time.monotonic() < deadline:
                for tid, frame in sys._current_frames().items():
                    if tid == me:
                        continue
                    code = frame.f_code
                    hits[f"{code.co_filename}:{frame.f_lineno} {code.co_name}"] += 1
                samples += 1
                _time.sleep(0.005)
            top = [{"site": site, "samples": n}
                   for site, n in hits.most_common(50)]
            return self._json({"samples": samples, "top": top})
        return self._error(404, f"unknown profile: {profile}")

    def post_recalculate_caches(self, params, query, body):
        self.api.recalculate_caches()
        return self._json({})

    def post_cluster_drain(self, params, query, body):
        """Graceful drain (docs/operations.md "Rolling restarts and
        drains"): starts the drain in the background and returns the
        status document immediately; {"abort": true} cancels an
        in-progress drain and re-announces READY."""
        req = self._body_json(body)
        return self._json(self.api.drain(abort=bool(req.get("abort"))))

    def post_resize_abort(self, params, query, body):
        self.api.resize_abort()
        return self._json({})

    def post_remove_node(self, params, query, body):
        req = self._body_json(body)
        node_id = req.get("id")
        if not node_id:
            raise ApiError("id is required")
        self.api.remove_node(node_id)
        return self._json({})

    def post_set_coordinator(self, params, query, body):
        req = self._body_json(body)
        node_id = req.get("id")
        if not node_id:
            raise ApiError("id is required")
        self.api.set_coordinator(node_id)
        return self._json({})

    # -- internal handlers --------------------------------------------------

    def post_cluster_message(self, params, query, body):
        if self.cluster_message_fn is None:
            raise ApiError("cluster messages not supported", status=501)
        self.cluster_message_fn(self._body_json(body))
        return self._json({})

    def _frag_args(self, query):
        return (self._arg(query, "index"), self._arg(query, "field"),
                self._arg(query, "view"), int(self._arg(query, "shard", "0")))

    def get_fragment_blocks(self, params, query, body):
        i, f, v, s = self._frag_args(query)
        return self._json({"blocks": self.api.fragment_blocks(i, f, v, s)})

    def get_fragment_block_data(self, params, query, body):
        i, f, v, s = self._frag_args(query)
        block = int(self._arg(query, "block", "0"))
        return self._json(self.api.fragment_block_data(i, f, v, s, block))

    def get_fragment_data(self, params, query, body):
        i, f, v, s = self._frag_args(query)
        return 200, "application/octet-stream", self.api.fragment_data(i, f, v, s)

    def get_fragment_views(self, params, query, body):
        index = self._arg(query, "index")
        field = self._arg(query, "field")
        shard = int(self._arg(query, "shard", "0"))
        return self._json({"views": self.api.fragment_views(index, field, shard)})

    def get_fragment_nodes(self, params, query, body):
        index = self._arg(query, "index")
        shard = int(self._arg(query, "shard", "0"))
        return self._json(self.api.shard_nodes(index, shard))

    def post_column_attr_diff(self, params, query, body):
        req = self._body_json(body)
        attrs = self.api.column_attr_diff(params["index"],
                                          req.get("blocks", []),
                                          req.get("blockRange"))
        return self._json({"attrs": {str(k): v for k, v in attrs.items()}})

    def post_row_attr_diff(self, params, query, body):
        req = self._body_json(body)
        attrs = self.api.row_attr_diff(params["index"], params["field"],
                                       req.get("blocks", []),
                                       req.get("blockRange"))
        return self._json({"attrs": {str(k): v for k, v in attrs.items()}})

    def delete_remote_available_shard(self, params, query, body):
        self.api.delete_remote_available_shard(
            params["index"], params["field"], int(params["shard"]))
        return self._json({})

    def get_nodes(self, params, query, body):
        return self._json(self.api.hosts())

    def get_internal_probe(self, params, query, body):
        """Indirect liveness probe (memberlist indirect ping): probe the
        given peer uri on the requester's behalf and report whether it
        answered /status. Lets a suspecting node distinguish a dead peer
        from a broken link between itself and that peer."""
        target = self._arg(query, "uri")
        if not target:
            raise ApiError("uri is required")
        alive = self.api.probe_peer(target)
        return self._json({"alive": alive})

    def post_query_batch(self, params, query, body):
        """Coalesced fan-out envelope (net/coalesce.py NodeCoalescer): N
        read-only (index, pql, shards) entries execute through the normal
        api/executor path — concurrently, so the device-side continuous
        batchers see the whole envelope at once and network coalescing
        compounds with device coalescing. Per-entry errors ride each
        entry's QueryResponse.Err; only a malformed envelope fails whole.
        Nodes that predate this route 404 it, and senders fall back to
        per-query /index/{index}/query (mixed-version clusters)."""
        try:
            entries = self.serializer.decode_query_batch_request(body)
        except ValueError as e:
            raise ApiError(str(e))
        results = self.api.query_batch(entries)
        return (200, "application/json",
                self.serializer.encode_query_batch_response(results))

    def get_shards_max(self, params, query, body):
        return self._json({"standard": self.api.max_shards()})

    def get_translate_data(self, params, query, body):
        offset = int(self._arg(query, "offset", "0"))
        return 200, "application/octet-stream", self.api.translate_data(offset)

    def post_translate_keys(self, params, query, body):
        if self._sends_proto():
            req = self.serializer.decode_translate_keys_request(body)
        else:
            req = self._body_json(body)
        ids = self.api.translate_keys(req.get("index"), req.get("field"),
                                      req.get("keys", []),
                                      create=req.get("create", True))
        if self._wants_proto():
            return (200, PROTO_CONTENT_TYPE,
                    self.serializer.encode_translate_keys_response(ids))
        return self._json({"ids": ids})


class _RequestHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    # TCP_NODELAY, like the Go reference's net/http listener: _handle
    # writes every response in two segments (header block, then payload),
    # and with Nagle on, the payload write stalls behind the client's
    # delayed ACK of the header segment on keep-alive connections — a
    # ~40ms floor per request (measured on loopback) that dwarfs every
    # network RTT the coalescer/ICI layers exist to remove.
    disable_nagle_algorithm = True
    handler: Handler = None  # injected by server factory

    def _handle(self, method: str):
        if getattr(self.server, "shutting_down", False):
            # the server was close()d but this keep-alive connection's
            # thread outlived it (ThreadingHTTPServer only closes the
            # LISTENER): drop the connection without answering, exactly
            # as a process exit would — answering from a torn-down
            # handler would serve stale lifecycle state (e.g. a dead
            # drain flag) to clients that already reached the restarted
            # listener on this same port
            self.close_connection = True
            return
        # http.request: the server-side whole of one request, the root of
        # its span tree, from the parsed request line to the last byte
        # written (the contextvar is this connection thread's: reset it,
        # the next keep-alive request starts its own tree)
        tracer_token = tracing.current_tracer.set(self.handler.tracer)
        try:
            with tracing.span("http.request", trace_id=self.headers.get(
                    tracing.TRACE_HEADER)):
                self._serve(method)
        finally:
            tracing.current_tracer.reset(tracer_token)

    def _serve(self, method: str):
        with tracing.span("http.read"):
            parsed = urlparse(self.path)
            length = int(self.headers.get("Content-Length", 0) or 0)
            body = self.rfile.read(length) if length else b""
            query = parse_qs(parsed.query)
        out = self.handler.dispatch(
            method, parsed.path, query, body,
            headers=self.headers, client_addr=self.client_address[0])
        # dispatch returns (status, ctype, payload[, extra-headers]) —
        # the 4th element carries e.g. Retry-After on QoS rejections
        status, ctype, payload = out[0], out[1], out[2]
        extra = out[3] if len(out) > 3 else None
        with tracing.span("http.write"):
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(payload)))
            if self.handler.events is not None:
                # HLC piggyback on every response: the caller merges it so
                # its later events sort after anything this node recorded
                # while serving (utils/events.py)
                from pilosa_tpu.utils import events as _events
                self.send_header(
                    _events.HLC_HEADER,
                    _events.encode_hlc(self.handler.events.clock.now()))
            if extra:
                for k, v in extra.items():
                    self.send_header(k, v)
            self.end_headers()
            self.wfile.write(payload)

    def do_GET(self):
        self._handle("GET")

    def do_POST(self):
        self._handle("POST")

    def do_DELETE(self):
        self._handle("DELETE")

    def log_message(self, fmt, *args):  # quiet; logging goes through utils
        pass


class _Server(ThreadingHTTPServer):
    # listen backlog: the stdlib default of 5 resets connections under a
    # concurrent-client burst (the Go reference's net/http listener has no
    # such cap); raised so serving benchmarks and real fan-in don't shed
    # connections at accept time
    request_queue_size = 1024


class HTTPServer:
    """Threaded HTTP server wrapper with lifecycle (Handler.Serve,
    http/handler.go:150)."""

    def __init__(self, handler: Handler, host: str = "localhost", port: int = 0,
                 tls_certificate: str = "", tls_key: str = ""):
        cls = type("BoundHandler", (_RequestHandler,), {"handler": handler})
        self._srv = _Server((host, port), cls)
        self._scheme = "http"
        if tls_certificate and tls_key:  # getListener (server/server.go:375-393)
            import ssl
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(tls_certificate, tls_key)
            self._srv.socket = ctx.wrap_socket(self._srv.socket, server_side=True)
            self._scheme = "https"
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self._srv.server_address[1]

    @property
    def uri(self) -> str:
        host = self._srv.server_address[0]
        return f"{self._scheme}://{host}:{self.port}"

    def serve_background(self) -> None:
        self._thread = _threads.spawn(self._srv.serve_forever,
                                      name="pilosa-http")

    def close(self) -> None:
        # flag FIRST: lingering per-connection threads must stop
        # answering before the listener goes away (see _handle)
        self._srv.shutting_down = True
        self._srv.shutdown()
        self._srv.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
