"""API: the validated façade over holder + cluster + executor.

Reference: api.go — ~40 methods, each gated on cluster state
(api.validate, api.go:93; state table api.go:1212-1278). Handlers (HTTP or
CLI) call only this surface; it owns key translation at the query boundary
(translateCalls/translateResults, executor.go:2323-2590) and existence
tracking on imports.
"""

from __future__ import annotations

import csv
import io
import os
import time
from datetime import datetime, timezone
from typing import Optional

from pilosa_tpu import __version__
from pilosa_tpu.constants import SHARD_WIDTH
from pilosa_tpu.executor import (
    ExecutionError,
    Executor,
    GroupCounts,
    Pairs,
    RowIdentifiers,
    ValCount,
)
from pilosa_tpu.models import FieldOptions, Holder
from pilosa_tpu.models.row import Row
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.parallel.cluster import (
    STATE_DEGRADED,
    STATE_NORMAL,
    STATE_RESIZING,
    STATE_STARTING,
    Cluster,
)
from pilosa_tpu import qos
from pilosa_tpu.utils import accounting
from pilosa_tpu.utils import profile as qprofile
from pilosa_tpu.utils import qctx, tracing
from pilosa_tpu.utils.translate import TranslateStore


class ApiError(Exception):
    def __init__(self, msg: str, status: int = 400, code: str = ""):
        super().__init__(msg)
        self.status = status
        # machine-readable discriminator carried in the JSON error body —
        # peers dispatch on it (e.g. anti-entropy distinguishes a missing
        # fragment from deleted schema) without parsing prose
        self.code = code


class NotFoundError(ApiError):
    def __init__(self, msg: str, code: str = ""):
        super().__init__(msg, status=404, code=code)


class ConflictError(ApiError):
    def __init__(self, msg: str):
        super().__init__(msg, status=409)


# method -> states in which it is permitted (api.go:1247-1278: methodsCommon
# always; methodsNormal in NORMAL+DEGRADED; methodsResizing adds FragmentData
# + ResizeAbort during RESIZING). Methods not listed are permitted in NORMAL
# and DEGRADED.
_STATE_GATES = {
    "query": (STATE_NORMAL, STATE_DEGRADED),
    "write": (STATE_NORMAL, STATE_DEGRADED),
    "schema_read": (STATE_NORMAL, STATE_DEGRADED, STATE_RESIZING, STATE_STARTING),
    "resize": (STATE_NORMAL, STATE_DEGRADED, STATE_RESIZING),
}


class API:
    def __init__(self, holder: Holder, cluster: Cluster,
                 executor: Optional[Executor] = None,
                 translate_store: Optional[TranslateStore] = None):
        self.holder = holder
        self.cluster = cluster
        self.translate = translate_store or TranslateStore().open()
        self.executor = executor or Executor(holder, translator=self.translate)
        if self.executor.translator is None:
            self.executor.translator = self.translate
        # DDL broadcast hook; set by Server on multi-node clusters
        # (broadcaster.SendSync, broadcast.go:30)
        self.broadcast_fn = None
        # resize execution hooks; set by Server. resize_fn(event, node)
        # routes node removal through the coordinator's resize engine
        # (cluster.go:1150-1515) instead of mutating membership locally;
        # abort_fn() cancels the coordinator's active job.
        self.resize_fn = None
        self.abort_fn = None
        # import forwarding hooks; set by Server to client.import_bits /
        # client.import_roaring. Imports are split by shard and routed to
        # every owning replica (the reference's client-side shard routing +
        # api.validateShardOwnership, api.go:804)
        self.forward_import_fn = None
        self.forward_roaring_fn = None
        # indirect liveness probe hook (memberlist indirect ping): probes
        # the given uri's /status on a requester's behalf; wired by the
        # server (returns False when unwired — a lone API can't vouch)
        self.probe_peer_fn = None
        # slow-query logging (cluster.longQueryTime, api.go:1038; server
        # option server.go:121). 0 disables.
        self.long_query_time = 0.0
        self.max_writes_per_request = 5000  # server/config.go:47 default
        self.logger = None
        # distributed query profiler (utils/profile.py). Modes:
        #   "off"  — never profile (even ?profile=true returns no tree)
        #   "auto" — profile when the request asks (?profile=true /
        #            QueryRequest.Profile) or when long-query-time is set
        #            (so the slow-query history carries full profiles)
        #   "on"   — profile every query
        # PILOSA_TPU_PROFILE=0 is the kill switch over any mode.
        self.profile_mode = "auto"
        self._profile_killed = os.environ.get(
            "PILOSA_TPU_PROFILE", "1") == "0"
        # structured slow-query ring (GET /debug/query-history): replaces
        # the one-line printf as the operator surface; size is the
        # [cluster] query-history-size knob
        self.query_history = qprofile.QueryHistory(100)
        # fleet telemetry hooks (utils/telemetry.py); set by Server.
        # health_fn() -> the node's own health score, reported on /status
        # so load balancers and the /cluster/stats federation share ONE
        # health definition; node_stats_fn() -> this node's stats document
        # (GET /internal/stats); cluster_stats_fn() -> the merged fleet
        # document (GET /cluster/stats, coordinator-or-any-node fan-out).
        self.health_fn = None
        self.node_stats_fn = None
        self.cluster_stats_fn = None
        # uptimeSeconds on /status is ELAPSED time: monotonic, so an NTP
        # step can never report a negative or jumped uptime
        self.start_time = time.monotonic()
        # per-principal resource accounting (utils/accounting.py): the
        # HTTP layer installs an Account per request against this ledger;
        # every charge site in the stack (batchers, residency, plan
        # cache, RPC client) attributes through the contextvar. A bare
        # API gets the default-bounded ledger; Server re-sizes it from
        # the [metric] usage-* knobs.
        self.usage_ledger = accounting.UsageLedger()
        # [slo] objectives evaluated with multi-window burn rates; the
        # default availability objective keeps the slo/* families alive
        # on every deployment (Server replaces with the configured set)
        self.slo = accounting.SLOTracker(
            [accounting.Objective("availability", None, None, 0.999)])
        # external trace egress ([metric] trace-export; utils/tracing.py
        # TraceExporter): finished cross-node profile trees ship as
        # Jaeger/OTLP-JSON span batches. None = export off.
        self.trace_exporter = None
        # federation hook for GET /cluster/usage (Server.cluster_usage)
        self.cluster_usage_fn = None
        # federation hook for GET /cluster/heat (Server.cluster_heat):
        # the fleet's merged fragment heat map, same degradation
        # contract (404 peers are "legacy", never an error)
        self.cluster_heat_fn = None
        # federation hook for GET /cluster/events (Server.cluster_events):
        # the merged HLC-sorted cluster timeline, same degradation
        # contract (404 peers are "legacy", never an error)
        self.cluster_events_fn = None
        # federation hook for GET /cluster/hbm (Server.cluster_hbm): the
        # fleet's per-node HBM residency maps, same degradation contract
        self.cluster_hbm_fn = None
        # multi-tenant QoS plane (pilosa_tpu/qos.py QosPlane); set by
        # Server. The HTTP layer runs admission against it; here it
        # collects execution-boundary sheds (expired deadlines — local
        # and remote envelope entries — and doomed-cost sheds) and the
        # per-class service-cost observations its estimates feed on.
        self.qos_plane = None
        # graceful-drain hooks (server.py drain lifecycle); set by
        # Server. drain_fn(abort=) starts/cancels a drain and returns
        # the status doc; node_state_fn() -> "READY" | "DRAINING" rides
        # /status so load balancers and probing peers see the lifecycle.
        self.drain_fn = None
        self.drain_status_fn = None
        self.node_state_fn = None

    def _broadcast(self, msg: dict) -> None:
        if self.broadcast_fn is not None:
            self.broadcast_fn(msg)

    # -- validation ---------------------------------------------------------

    def _validate(self, gate: str) -> None:
        allowed = _STATE_GATES.get(gate, (STATE_NORMAL, STATE_DEGRADED))
        if self.cluster.state not in allowed:
            raise ApiError(
                f"api method unavailable in cluster state {self.cluster.state}",
                status=503)

    # -- queries ------------------------------------------------------------

    def _should_profile(self, explicit: bool) -> bool:
        """Whether this query gets a QueryProfile (see profile_mode)."""
        if self._profile_killed or self.profile_mode == "off":
            return False
        if self.profile_mode == "on":
            return True
        return explicit or self.long_query_time > 0

    @staticmethod
    def _parse(pql: str):
        """PQL text -> Query through the parse cache, under the
        `pql.parse` span (tag `cached`; a concurrent thread's miss can
        read as this one's)."""
        from pilosa_tpu.pql import parse_string_cached
        with tracing.span("pql.parse") as sp:
            misses = parse_string_cached.cache_info().misses
            try:
                query = parse_string_cached(pql)
            except ValueError as e:
                raise ApiError(str(e))
            sp.set_tag("cached",
                       misses == parse_string_cached.cache_info().misses)
        return query

    def query_results(self, index_name: str, pql: str,
                      shards: Optional[list[int]] = None,
                      remote: bool = False,
                      exclude_row_attrs: bool = False,
                      exclude_columns: bool = False,
                      profile: bool = False) -> list:
        """Execute PQL and return raw result objects (Row/Pairs/ValCount/...).

        Both wire writers consume this: query() renders JSON, the protobuf
        path encodes with encoding.protobuf.Serializer (api.Query, api.go:102).

        `profile=True` (the ?profile=true / QueryRequest.Profile request
        flag) asks for a QueryProfile; whether one is recorded also depends
        on profile_mode. The finished profile is published through
        `utils.profile.last_profile` (same context, so the calling handler
        reads it after return without a return-type change), and queries
        over long-query-time land in `query_history` with it attached.
        """
        self._validate("query")
        index = self.holder.index(index_name)
        if index is None:
            raise NotFoundError(f"index not found: {index_name}")
        query = pql
        if isinstance(pql, str):
            query = self._parse(pql)
        if self.max_writes_per_request > 0:
            # reject oversized write batches up front (MaxWritesPerRequest,
            # api.go / http handler validation; server/config.go:47);
            # Options() wraps a single call — unwrap so wrapped writes count
            writes = sum(
                1 for c in query.calls
                if (c.children[0] if c.name == "Options" and c.children
                    else c).name in self.executor.WRITE_CALLS)
            if writes > self.max_writes_per_request:
                raise ApiError(
                    f"too many writes in a single request: {writes} > "
                    f"{self.max_writes_per_request}")
        import time as _time
        # QoS execution-boundary checks (pilosa_tpu/qos.py). (1) A query
        # whose deadline ALREADY expired is shed here — before planning,
        # residency uploads or any device dispatch. Remote envelope
        # entries hit this with the coordinator's shrunken budget, so a
        # doomed distributed query stops burning device time on every
        # node it fanned to. (2) Under enforce, a query whose class's
        # observed device cost alone exceeds the remaining budget is
        # shed as doomed (503 + code so clients back off, not retry-storm).
        plane = self.qos_plane
        rem = qctx.remaining()
        if rem is not None and rem <= 0:
            if plane is not None:
                plane.record_expired(remote)
            raise qctx.QueryTimeoutError("query deadline exceeded")
        if (plane is not None and plane.mode == "enforce" and not remote
                and rem is not None):
            est_ms = plane.class_cost_ms(accounting.classify_query(query))
            if est_ms > 0 and rem * 1e3 < est_ms:
                plane.record_cost_shed()
                raise ApiError(
                    f"query shed: estimated cost {est_ms:.0f} ms exceeds "
                    f"remaining deadline {rem * 1e3:.0f} ms",
                    status=503, code="shed")
        profiling = self._should_profile(profile)
        slow_armed = self.long_query_time > 0
        trace_tok = None
        if ((profiling or slow_armed)
                and tracing.current_trace_id.get() is None):
            # mint one trace id for the whole request so the slow-query
            # log line, /debug/query-history and exported spans (local AND
            # remote — the id fans out via X-Pilosa-Trace-Id) all join;
            # without it each span mints its own and nothing correlates
            trace_tok = tracing.current_trace_id.set(tracing.new_trace_id())
        prof = None
        prof_tok = None
        if profiling and qprofile.current_profile.get() is None:
            prof = qprofile.QueryProfile(
                trace_id=tracing.current_trace_id.get() or "",
                node_id=self.cluster.local_id, index=index_name,
                pql=qprofile.truncate_pql(pql))
            pr = qos.current_priority.get() if qos.enabled() else None
            if pr is not None or plane is not None:
                # QoS ride-along on the profile tree: the class this
                # query ran under, its deadline budget at execution, and
                # the admission-time wait estimate it beat
                prof.qos = {
                    "priority": pr or (plane.default_priority
                                       if plane is not None else None),
                    "deadlineMs": (round(rem * 1e3, 1)
                                   if rem is not None else None),
                    "estimatedWaitMs": (round(plane.estimated_wait_ms(), 3)
                                        if plane is not None else None),
                }
            prof_tok = qprofile.current_profile.set(prof)
        start = _time.perf_counter()
        ok = False
        try:
            results = self.executor.execute(index_name, query, shards=shards,
                                            remote=remote)
            if exclude_row_attrs or exclude_columns:
                # request-level flags apply to every Row result
                # (QueryRequest.ExcludeRowAttrs/ExcludeColumns,
                # internal/public.proto; handler exec options)
                for r in results:
                    if isinstance(r, Row):
                        if exclude_columns:
                            r.segments = {}
                        if exclude_row_attrs:
                            r.attrs = {}
            ok = True
            return results
        except (ExecutionError, ValueError) as e:
            raise ApiError(str(e))
        finally:
            elapsed = _time.perf_counter() - start
            if prof_tok is not None:
                qprofile.current_profile.reset(prof_tok)
            if prof is not None:
                prof.finish()
                if ok and not remote:
                    # EXPLAIN calibration: pair the profile's recorded
                    # plan estimates with the scalar results they
                    # predicted (planner.calibration ring — what makes
                    # ?explain=true estimates auditable, ISSUE 18)
                    from pilosa_tpu import planner as _planner
                    _planner.record_calibration(prof, query.calls, results)
            qprofile.last_profile.set(prof)
            # per-principal query/error counts (the device/HBM/RPC
            # charges landed at their own sites while the query ran)
            acct = accounting.current_account.get()
            if acct is not None:
                acct.charge(queries=1, errors=0 if ok else 1)
            # SLO observation by query class; coordinator-side only —
            # remote sub-requests are an implementation detail of the
            # same user-visible query and must not dilute the objective
            if not remote:
                qclass = accounting.classify_query(query)
                if self.slo is not None:
                    self.slo.observe(qclass, elapsed, ok)
                if plane is not None and ok:
                    # per-class device-cost EWMA: what the doomed-query
                    # shed and the admission wait estimate are fed by
                    plane.observe_service(qclass, elapsed * 1e3)
            if (prof is not None and not remote
                    and self.trace_exporter is not None):
                # coordinator-only export: the finished tree already
                # contains the remote fragments, so one export carries
                # every node's spans under one trace id (a remote
                # exporting its fragment too would duplicate spans)
                self.trace_exporter.export_profile(prof.to_dict())
            if slow_armed and elapsed > self.long_query_time:
                trace_id = tracing.current_trace_id.get() or "-"
                short_pql = qprofile.truncate_pql(pql)
                self.query_history.append({
                    "time": datetime.now(timezone.utc).isoformat(),
                    "index": index_name,
                    "pql": short_pql,
                    "elapsed": round(elapsed, 6),
                    "traceId": trace_id,
                    "profile": prof.to_dict() if prof is not None else None,
                })
                if self.logger is not None:
                    # truncated PQL (an import-sized query must not flood
                    # the log) + trace= so the line joins to
                    # /debug/query-history and exported spans
                    self.logger.printf("%.3fs SLOW QUERY %s %s trace=%s",
                                       elapsed, index_name, short_pql,
                                       trace_id)
            if trace_tok is not None:
                tracing.current_trace_id.reset(trace_tok)

    def query(self, index_name: str, pql: str,
              shards: Optional[list[int]] = None, remote: bool = False,
              column_attrs: bool = False,
              exclude_row_attrs: bool = False,
              exclude_columns: bool = False,
              profile: bool = False) -> dict:
        """POST /index/{index}/query (api.Query, api.go:102)."""
        results = self.query_results(index_name, pql, shards=shards,
                                     remote=remote,
                                     exclude_row_attrs=exclude_row_attrs,
                                     exclude_columns=exclude_columns,
                                     profile=profile)
        index = self.holder.index(index_name)
        out = {"results": [self._result_to_json(index, r) for r in results]}
        if column_attrs:
            out["columnAttrSets"] = self.column_attr_sets(index_name, results)
        if profile:
            prof = qprofile.last_profile.get()
            if prof is not None:
                out["profile"] = prof.to_dict()
        return out

    def explain(self, index_name: str, pql: str,
                shards: Optional[list[int]] = None) -> dict:
        """POST /index/{index}/query?explain=true: plan the query and
        return the planned tree — per-operand representation, residency
        state, predicted kernel family and estimated h2d bytes — WITHOUT
        executing it. No device program is dispatched, no row ids are
        minted, no planner hysteresis advances (the executor's explain
        walk peeks every decision), so EXPLAIN is safe against a
        production node at any rate. Write calls plan to nothing."""
        self._validate("query")
        index = self.holder.index(index_name)
        if index is None:
            raise NotFoundError(f"index not found: {index_name}")
        query = pql
        if isinstance(pql, str):
            query = self._parse(pql)
        from pilosa_tpu import planner as _planner
        out = []
        for call in query.calls:
            if call.name in self.executor.WRITE_CALLS:
                out.append({"call": call.name, "planned": False,
                            "note": "write call: nothing to plan"})
                continue
            if (call.name not in _planner.PLANNED_CALLS
                    and call.name not in _planner.BITMAP_CALLS):
                out.append({"call": call.name, "planned": False,
                            "note": "call is executed host-side; no "
                                    "device plan"})
                continue
            try:
                out.append(self.executor.explain_call(index, call, shards))
            except (ExecutionError, ValueError) as e:
                raise ApiError(str(e))
        return {"index": index_name, "explain": out,
                "calibration": _planner.calibration.snapshot(limit=0)}

    def query_batch(self, entries: list[dict]) -> list[tuple]:
        """Execute a coalesced fan-out envelope (POST /internal/query-batch,
        net/coalesce.py): N read-only query entries, answered in order as
        (results, err[, profile]) tuples (profile = this node's
        QueryProfile fragment dict when the entry asked for one). Entries run through query_results — the same
        validation/translation path as the per-query route — but
        CONCURRENTLY on the executor's inbound batch pool, so the
        envelope's device dispatches coalesce in CountBatcher /
        PlaneSumBatcher exactly as N separate requests would, minus the
        N-1 HTTP round trips. Write calls are rejected per-entry: the
        sender retries a coalesced envelope on a stale keep-alive
        (net/client.py single-retry rule), which is only safe while every
        entry is idempotent."""
        self._validate("query")
        import contextvars
        import time as _time

        from pilosa_tpu.pql import parse_string_cached
        from pilosa_tpu.utils import qctx

        def one(e: dict) -> tuple:
            dl_token = None
            tr_token = None
            acct_token = None
            prio_token = None
            try:
                timeout = e.get("timeout")
                if timeout is not None:
                    # per-entry deadline: each coalesced caller's remaining
                    # budget rides its own entry, not the envelope leader's
                    # (the leader's header-adopted deadline still caps it —
                    # strictest source wins, as in Handler._set_deadline)
                    entry_dl = _time.monotonic() + float(timeout)
                    cur = qctx.deadline.get()
                    dl_token = qctx.deadline.set(
                        entry_dl if cur is None else min(entry_dl, cur))
                trace_id = e.get("traceId")
                if trace_id:
                    # per-entry trace context (the deadline's twin): the
                    # envelope leader's header carried ITS trace id, but
                    # each coalesced caller's spans must join the caller's
                    # own trace, not the leader's
                    tr_token = tracing.current_trace_id.set(str(trace_id))
                principal = e.get("principal")
                if principal and self.usage_ledger is not None \
                        and self.usage_ledger.enabled \
                        and accounting.enabled():
                    # per-entry principal (the trace id's twin again):
                    # the envelope arrived under the LEADER's inherited
                    # header, but this entry's device/HBM charges belong
                    # to the caller whose query rode it
                    acct_token = accounting.current_account.set(
                        accounting.Account(self.usage_ledger,
                                           accounting._sanitize(
                                               str(principal))))
                priority = e.get("priority")
                if priority and qos.enabled():
                    # per-entry QoS priority (trace id / principal twin):
                    # this entry's device batcher cuts and pool submits
                    # order under the ORIGINAL caller's class
                    prio_token = qos.current_priority.set(str(priority))
                pql = e.get("query", "")
                query = parse_string_cached(pql)
                for c in query.calls:
                    inner = (c.children[0]
                             if c.name == "Options" and c.children else c)
                    if inner.name in self.executor.WRITE_CALLS:
                        return (None, f"{inner.name}() cannot ride a "
                                      "coalesced query batch (not idempotent)")
                want_prof = bool(e.get("profile"))
                # pass the RAW string (re-parse is a cache hit): profiles,
                # history entries and slow-log lines must show the PQL the
                # coordinator sent, not a parsed Query repr
                results = self.query_results(
                    e.get("index", ""), pql, shards=e.get("shards"),
                    remote=bool(e.get("remote", True)), profile=want_prof)
                prof = qprofile.last_profile.get() if want_prof else None
                return (results, "",
                        prof.to_dict() if prof is not None else None)
            except qctx.QueryTimeoutError as exc:
                return (None, str(exc) or "query deadline exceeded")
            except (ApiError, ValueError) as exc:
                return (None, str(exc))
            except Exception as exc:  # noqa: BLE001 — per-entry isolation
                return (None, f"{type(exc).__name__}: {exc}")
            finally:
                if dl_token is not None:
                    qctx.deadline.reset(dl_token)
                if tr_token is not None:
                    tracing.current_trace_id.reset(tr_token)
                if acct_token is not None:
                    accounting.current_account.reset(acct_token)
                if prio_token is not None:
                    qos.current_priority.reset(prio_token)

        if len(entries) <= 1:
            return [one(e) for e in entries]
        # copied contexts: pool threads must see the request's trace id /
        # adopted deadline (the same rule as the executor's fan-out pool)
        pool = self.executor.batch_exec_pool
        futs = [pool.submit(contextvars.copy_context().run, one, e)
                for e in entries]
        return [f.result() for f in futs]

    def column_attr_sets(self, index_name: str, results: list) -> list[dict]:
        """Attrs for every column appearing in Row results — the
        QueryRequest.ColumnAttrs option (executor/handler attach
        ColumnAttrSets to the response, internal/public.proto:70)."""
        index = self.holder.index(index_name)
        if index is None:
            return []
        cols: set[int] = set()
        for r in results:
            if isinstance(r, Row):
                cols.update(int(c) for c in r.columns())
        out = []
        for c in sorted(cols):
            attrs = index.column_attrs.attrs(c)
            if attrs:
                entry = {"id": c, "attrs": attrs}
                if index.keys:
                    key = self.translate.translate_column_to_string(
                        index.name, c)
                    if key is not None:
                        entry["key"] = key
                out.append(entry)
        return out

    def _result_to_json(self, index, result):
        if isinstance(result, Row):
            d = result.to_json_dict()
            if index.keys:
                d["keys"] = [
                    self.translate.translate_column_to_string(index.name, int(c)) or str(c)
                    for c in d.pop("columns")
                ]
            if "attrs" not in d:
                d["attrs"] = {}
            return d
        if isinstance(result, ValCount):
            return result.to_json_dict()
        if isinstance(result, Pairs):
            if result.row_keys is not None:
                # keyed field: Pair.Key replaces the id (cache.go:317-321,
                # key has json omitempty but id is always present in the Go
                # struct; the reference emits id=0 alongside key)
                return [{"id": int(i), "key": k, "count": c}
                        for (i, c), k in zip(result, result.row_keys)]
            return [{"id": i, "count": c} for i, c in result]
        if isinstance(result, RowIdentifiers):
            if result.row_keys is not None:
                # keyed: Rows is nil in the reference (executor.go:2570)
                return {"rows": None, "keys": list(result.row_keys)}
            return {"rows": list(result)}
        if isinstance(result, GroupCounts):
            return list(result)
        if isinstance(result, list):
            # untyped list (shouldn't happen from the executor, but keep the
            # legacy heuristics as a fallback)
            if result and isinstance(result[0], tuple):
                return [{"id": i, "count": c} for i, c in result]
            return result
        if result is None:
            return None
        return result  # bool / int

    # -- schema DDL ---------------------------------------------------------

    def create_index(self, name: str, keys: bool = False,
                     track_existence: bool = True):
        self._validate("write")
        if self.holder.index(name) is not None:
            raise ConflictError(f"index already exists: {name}")
        try:
            idx = self.holder.create_index(name, keys=keys,
                                           track_existence=track_existence)
        except ValueError as e:
            raise ApiError(str(e))
        self._broadcast({"type": "create-index", "index": name, "keys": keys,
                         "trackExistence": track_existence})
        return idx

    def delete_index(self, name: str) -> None:
        self._validate("write")
        try:
            self.holder.delete_index(name)
        except KeyError as e:
            raise NotFoundError(str(e))
        self.executor.clear_caches()
        self._broadcast({"type": "delete-index", "index": name})

    def create_field(self, index_name: str, field_name: str,
                     options: Optional[FieldOptions] = None):
        self._validate("write")
        index = self.holder.index(index_name)
        if index is None:
            raise NotFoundError(f"index not found: {index_name}")
        if index.field(field_name) is not None:
            raise ConflictError(f"field already exists: {field_name}")
        try:
            f = index.create_field(field_name, options)
        except ValueError as e:
            raise ApiError(str(e))
        from dataclasses import asdict
        self._broadcast({"type": "create-field", "index": index_name,
                         "field": field_name,
                         "options": asdict(f.options)})
        return f

    def delete_field(self, index_name: str, field_name: str) -> None:
        self._validate("write")
        index = self.holder.index(index_name)
        if index is None:
            raise NotFoundError(f"index not found: {index_name}")
        try:
            index.delete_field(field_name)
        except KeyError as e:
            raise NotFoundError(str(e))
        self.executor.clear_caches()
        self._broadcast({"type": "delete-field", "index": index_name,
                         "field": field_name})

    def schema(self) -> dict:
        self._validate("schema_read")
        return {"indexes": self.holder.schema()}

    def views(self, index_name: str, field_name: str) -> list[str]:
        self._validate("schema_read")
        f = self._field(index_name, field_name)
        return sorted(f.views)

    def _field(self, index_name: str, field_name: str):
        index = self.holder.index(index_name)
        if index is None:
            raise NotFoundError(f"index not found: {index_name}")
        f = index.field(field_name)
        if f is None:
            raise NotFoundError(f"field not found: {field_name}")
        return f

    # -- imports (api.go:804-1045) ------------------------------------------

    def import_bits(self, index_name: str, field_name: str,
                    row_ids=None, column_ids=None,
                    row_keys=None, column_keys=None,
                    timestamps=None, remote: bool = False,
                    clear: bool = False) -> None:
        self._validate("write")
        index = self.holder.index(index_name)
        if index is None:
            raise NotFoundError(f"index not found: {index_name}")
        f = self._field(index_name, field_name)
        if row_keys:
            row_ids = self.translate.translate_rows(index_name, field_name, list(row_keys))
        if column_keys:
            column_ids = self.translate.translate_columns(index_name, list(column_keys))
        if row_ids is None or column_ids is None:
            raise ApiError("import requires rows and columns")
        row_ids, column_ids = list(row_ids), list(column_ids)
        timestamps = list(timestamps) if timestamps else None
        if timestamps:
            # normalize to epoch numbers BEFORE routing: forwarded payloads
            # are JSON and must not carry datetime objects. The reference
            # wire uses epoch numbers; ISO-8601 strings are accepted as a
            # convenience — anything else fails loudly instead of silently
            # dropping the timestamp (and with it the time views)
            def _epoch(t):
                if isinstance(t, str):
                    try:
                        # Python < 3.11 fromisoformat rejects the Zulu
                        # suffix; normalize it so "…T00:00:00Z" imports
                        # parse on every supported interpreter
                        t = datetime.fromisoformat(
                            t[:-1] + "+00:00" if t[-1:] in ("Z", "z")
                            else t)
                    except ValueError:
                        raise ApiError(f"invalid import timestamp: {t!r}")
                if isinstance(t, datetime):
                    if t.tzinfo is None:
                        t = t.replace(tzinfo=timezone.utc)
                    return t.timestamp()
                if t is None or isinstance(t, (int, float)) \
                        and not isinstance(t, bool):
                    return t
                raise ApiError(f"invalid import timestamp: {t!r}")

            timestamps = [_epoch(t) for t in timestamps]
        if not remote:
            row_ids, column_ids, timestamps = self._route_import(
                index_name, field_name, row_ids, column_ids, timestamps,
                clear=clear)
            if not column_ids:
                return
        ts = None
        if timestamps:
            # 0 means "no timestamp" (wire zero value), not epoch 0
            ts = [datetime.fromtimestamp(t, tz=timezone.utc).replace(tzinfo=None)
                  if isinstance(t, (int, float)) and not isinstance(t, bool)
                  and t else
                  (t if isinstance(t, datetime) else None)
                  for t in timestamps]
        f.import_bits(row_ids, column_ids, ts, clear=clear)
        if not clear:
            # clears do NOT retract existence: other fields may still hold
            # the column (the reference also only imports existence on set)
            self._import_existence(index, column_ids)

    def _live_shard_owners(self, index_name: str, shard: int) -> list:
        """Owning replicas minus probe-detected-down nodes — the shared
        routing policy of every import path: a down replica is skipped (it
        heals via anti-entropy on return), and zero live owners is a hard
        503 (an acked import must land somewhere)."""
        all_owners = self.cluster.shard_nodes(index_name, shard)
        owners = [n for n in all_owners if not self.cluster.is_down(n.id)]
        if all_owners and not owners:
            raise ApiError(f"all replicas down for shard {shard}", status=503)
        return owners

    def _route_import(self, index_name: str, field_name: str,
                      a_ids: list, column_ids: list, extra,
                      values: bool = False, clear: bool = False):
        """Split an import by shard and forward each shard's batch to every
        owning replica; returns the locally-owned remainder (possibly empty
        lists). a_ids is rowIDs (set import) or the values list (see
        import_values)."""
        if self.forward_import_fn is None or len(self.cluster.nodes) <= 1:
            return a_ids, column_ids, extra
        by_node: dict[str, dict] = {}
        local_idx: list[int] = []
        owners_by_shard: dict[int, list] = {}
        for i, col in enumerate(column_ids):
            shard = int(col) // SHARD_WIDTH
            owners = owners_by_shard.get(shard)
            if owners is None:
                owners = owners_by_shard[shard] = \
                    self._live_shard_owners(index_name, shard)
            for node in owners:
                if node.id == self.cluster.local_id:
                    local_idx.append(i)
                else:
                    by_node.setdefault(node.id, {"uri": node.uri,
                                                 "idx": []})["idx"].append(i)
        for group in by_node.values():
            sel = group["idx"]
            if values:
                payload = {"columnIDs": [column_ids[i] for i in sel],
                           "values": [a_ids[i] for i in sel],
                           "remote": True}
            else:
                payload = {"rowIDs": [a_ids[i] for i in sel],
                           "columnIDs": [column_ids[i] for i in sel],
                           "remote": True}
                if extra:
                    payload["timestamps"] = [extra[i] for i in sel]
                if clear:
                    payload["clear"] = True
            try:
                self.forward_import_fn(group["uri"], index_name, field_name,
                                       payload)
            except Exception as e:  # noqa: BLE001 — surface as a 502, not 500
                raise ApiError(
                    f"forwarding import to {group['uri']}: {e}", status=502)
        if by_node:
            # first-hand knowledge: the forwarded batches landed on their
            # owners, so those shards exist cluster-wide — merge them into
            # this coordinator's availability view now; the owners' async
            # announcements still propagate to the other nodes
            # (AddRemoteAvailableShards, field.go:283). ONLY shards with no
            # local owner: for a shard this node owns, the local import
            # below must do the (non-quiet) add so the create-shard
            # announcement fires — a quiet pre-add would swallow it.
            idx = self.holder.index(index_name)
            f = idx.field(field_name) if idx is not None else None
            if f is not None:
                for shard, owners in owners_by_shard.items():
                    if all(n.id != self.cluster.local_id for n in owners):
                        f.add_available_shard(shard, quiet=True)
        return ([a_ids[i] for i in local_idx],
                [column_ids[i] for i in local_idx],
                [extra[i] for i in local_idx] if extra else None)

    def import_values(self, index_name: str, field_name: str,
                      column_ids=None, values=None, column_keys=None,
                      remote: bool = False) -> None:
        self._validate("write")
        index = self.holder.index(index_name)
        if index is None:
            raise NotFoundError(f"index not found: {index_name}")
        f = self._field(index_name, field_name)
        if column_keys:
            column_ids = self.translate.translate_columns(index_name, list(column_keys))
        if column_ids is None or values is None:
            raise ApiError("import requires columns and values")
        column_ids, values = list(column_ids), list(values)
        if not remote:
            values, column_ids, _ = self._route_import(
                index_name, field_name, values, column_ids, None, values=True)
            if not column_ids:
                return
        try:
            f.import_values(column_ids, values)
        except ValueError as e:
            raise ApiError(str(e))
        self._import_existence(index, column_ids)

    def import_roaring(self, index_name: str, field_name: str, shard: int,
                       views: dict[str, bytes], clear: bool = False,
                       remote: bool = False) -> None:
        """POST /index/{i}/field/{f}/import-roaring/{shard}: pre-serialized
        roaring payloads per view (api.go:290)."""
        self._validate("write")
        f = self._field(index_name, field_name)
        if not remote and self.forward_roaring_fn is not None \
                and len(self.cluster.nodes) > 1:
            owners = self._live_shard_owners(index_name, shard)
            for node in owners:
                if node.id != self.cluster.local_id:
                    try:
                        self.forward_roaring_fn(node.uri, index_name,
                                                field_name, shard, views,
                                                clear)
                    except Exception as e:  # noqa: BLE001
                        raise ApiError(
                            f"forwarding import to {node.uri}: {e}",
                            status=502)
            if not any(n.id == self.cluster.local_id for n in owners):
                return
        for vname, data in views.items():
            vname = vname or VIEW_STANDARD
            view = f.create_view_if_not_exists(vname)
            frag = view.create_fragment_if_not_exists(shard)
            try:
                frag.import_roaring(data, clear=clear)
            except ValueError as e:
                raise ApiError(f"unmarshalling roaring data: {e}")
            view.refresh_rank_cache(shard)
        f.add_available_shard(shard)

    def _import_existence(self, index, column_ids) -> None:
        ef = index.existence_field()
        if ef is not None and column_ids is not None and len(column_ids):
            ef.import_bits([0] * len(column_ids), list(column_ids))

    # -- export (api.go ExportCSV) ------------------------------------------

    def export_csv(self, index_name: str, field_name: str, shard: int) -> str:
        self._validate("query")
        f = self._field(index_name, field_name)
        view = f.view(VIEW_STANDARD)
        buf = io.StringIO()
        w = csv.writer(buf)
        frag = view.fragment(shard) if view else None
        if frag is not None:
            for rid in frag.row_ids():
                for col in frag.row_columns(rid):
                    w.writerow([rid, int(col) + shard * SHARD_WIDTH])
        return buf.getvalue()

    # -- cluster / info -----------------------------------------------------

    def hosts(self) -> list[dict]:
        return [n.to_dict() for n in self.cluster.nodes]

    def probe_peer(self, target_uri: str) -> bool:
        """Probe a peer's /status on a requester's behalf (indirect ping)."""
        if self.probe_peer_fn is None:
            return False
        try:
            return bool(self.probe_peer_fn(target_uri))
        except Exception:  # noqa: BLE001 — any failure means not-alive
            return False

    def node(self) -> dict:
        n = self.cluster.local_node
        return n.to_dict() if n else {"id": self.cluster.local_id}

    def state(self) -> str:
        return self.cluster.state

    def status(self) -> dict:
        out = {"state": self.cluster.state, "nodes": self.hosts(),
               "localID": self.cluster.local_id,
               # each node's coordinator claim; the probe loop converges
               # divergent claims onto the electoral authority's (see
               # Server._probe_peers)
               "coordinatorID": self.cluster.coordinator_id,
               # load-balancer surface: uptime + version + the node's own
               # health score — the SAME health_score() the /cluster/stats
               # federation computes, so the two can never disagree
               "uptimeSeconds": int(time.monotonic() - self.start_time),
               "version": __version__}
        if self.node_state_fn is not None:
            # lifecycle state of THIS node ("READY" | "DRAINING"): load
            # balancers stop sending here on DRAINING, and a probing
            # peer uses it to tell a restarted node from a draining one
            out["nodeState"] = self.node_state_fn()
        if self.health_fn is not None:
            try:
                out["health"] = self.health_fn()
            except Exception:  # noqa: BLE001 — a health-input failure must
                # not take down the liveness probe surface itself
                out["health"] = {"score": "unknown", "reasons": []}
        return out

    def info(self) -> dict:
        import os
        runner = getattr(self.executor, "runner", None)
        return {"shardWidth": SHARD_WIDTH, "cpuPhysicalCores": os.cpu_count(),
                "meshDevices": runner.n_devices if runner else 1,
                "version": __version__}

    def version(self) -> str:
        return __version__

    def max_shards(self) -> dict[str, int]:
        """GET /internal/shards/max (api.go MaxShards)."""
        out = {}
        for name, idx in self.holder.indexes.items():
            m = idx.available_shards().max()
            out[name] = int(m) if m is not None else 0
        return out

    def shard_nodes(self, index_name: str, shard: int) -> list[dict]:
        return [n.to_dict() for n in self.cluster.shard_nodes(index_name, shard)]

    def set_coordinator(self, node_id: str) -> None:
        self._validate("resize")
        if self.cluster.node_by_id(node_id) is None:
            raise NotFoundError(f"node not found: {node_id}")
        self.cluster.adopt_coordinator(node_id)
        # cluster-wide adoption (SetCoordinatorMessage, api.go
        # SetCoordinator → SendSync): without it, a later failover would
        # leave resize coordination split across divergent coordinators
        self._broadcast({"type": "set-coordinator", "id": node_id})

    def remove_node(self, node_id: str):
        self._validate("resize")
        node = self.cluster.node_by_id(node_id)
        if node is None:
            raise NotFoundError(f"node not found: {node_id}")
        try:
            if self.resize_fn is not None:
                return self.resize_fn("leave", node)
            return self.cluster.node_leave(node_id)
        except ValueError as e:
            raise ApiError(str(e))

    def resize_abort(self) -> None:
        if self.cluster.state != STATE_RESIZING:
            raise ApiError("no resize job currently running")
        if self.abort_fn is not None:
            # route through the coordinator so the active job is actually
            # cancelled before peers are un-gated (api.ResizeAbort runs on
            # the coordinator, api.go:1131)
            try:
                self.abort_fn()
            except ValueError as e:
                raise ApiError(str(e))
            return
        self.cluster.abort_resize()

    def drain(self, abort: bool = False) -> dict:
        """POST /cluster/drain: begin a graceful drain of this node (or
        cancel one with abort=True). The drain runs in the background —
        the returned status document reflects progress; operators poll
        /status (nodeState) for completion before restarting the
        process. Deliberately NOT state-gated: draining must work in any
        cluster state (that is the point of a lifecycle plane)."""
        if self.drain_fn is None:
            raise ApiError("drain not supported", status=501)
        return self.drain_fn(abort=abort)

    def recalculate_caches(self) -> None:
        for idx in self.holder.indexes.values():
            for f in idx.fields.values():
                for v in f.views.values():
                    for shard in v.shards():
                        v.refresh_rank_cache(shard)

    # -- fragment internals (anti-entropy RPC surface) ----------------------

    def fragment_blocks(self, index_name: str, field_name: str, view_name: str,
                        shard: int) -> list[dict]:
        f = self._field(index_name, field_name)
        view = f.view(view_name)
        frag = view.fragment(shard) if view else None
        if frag is None:
            raise NotFoundError("fragment not found", code="fragment-not-found")
        return [{"id": b, "checksum": chk.hex()} for b, chk in frag.blocks()]

    def fragment_block_data(self, index_name: str, field_name: str,
                            view_name: str, shard: int, block: int) -> dict:
        f = self._field(index_name, field_name)
        view = f.view(view_name)
        frag = view.fragment(shard) if view else None
        if frag is None:
            raise NotFoundError("fragment not found", code="fragment-not-found")
        rows, cols = frag.block_data(block)
        return {"rowIDs": rows.tolist(), "columnIDs": cols.tolist()}

    def column_attr_diff(self, index_name: str, blocks: list[dict],
                         block_range=None) -> dict:
        """Attrs in blocks whose checksum differs from the caller's
        (api.ColumnAttrDiff — the attr anti-entropy pull, holder.go:726)."""
        index = self.holder.index(index_name)
        if index is None:
            raise NotFoundError(f"index not found: {index_name}")
        return _attr_diff(index.column_attrs, blocks, block_range)

    def row_attr_diff(self, index_name: str, field_name: str,
                      blocks: list[dict], block_range=None) -> dict:
        """api.RowAttrDiff (holder.go:772 syncField)."""
        f = self._field(index_name, field_name)
        return _attr_diff(f.row_attrs, blocks, block_range)

    def fragment_views(self, index_name: str, field_name: str,
                       shard: int) -> list[str]:
        """View names holding a fragment for `shard` — the donor-side
        enumeration behind resize field/shard copies."""
        f = self._field(index_name, field_name)
        return sorted(v.name for v in f.views.values()
                      if v.fragment(shard) is not None)

    def fragment_data(self, index_name: str, field_name: str, view_name: str,
                      shard: int) -> bytes:
        f = self._field(index_name, field_name)
        view = f.view(view_name)
        frag = view.fragment(shard) if view else None
        if frag is None:
            raise NotFoundError("fragment not found", code="fragment-not-found")
        return frag.storage.to_bytes()

    def delete_remote_available_shard(self, index_name: str, field_name: str,
                                      shard: int) -> None:
        f = self._field(index_name, field_name)
        f.remove_available_shard(shard)

    # -- translation --------------------------------------------------------

    def translate_keys(self, index_name: str, field_name: Optional[str],
                       keys: list[str], create: bool = True) -> list:
        if field_name:
            return self.translate.translate_rows(index_name, field_name, keys,
                                                 create=create)
        return self.translate.translate_columns(index_name, keys, create=create)

    def translate_data(self, offset: int = 0) -> bytes:
        return self.translate.log_bytes(offset)


def _attr_diff(store, blocks: list[dict], block_range=None) -> dict:
    """Return {id: attrs} for every local block whose checksum differs from
    the caller's view (attr.go blocks; boltdb/attrstore.go BlockData).

    block_range = [lo, hi) restricts the diff to local block ids in that
    range — the pagination contract: a caller pulling a large store pages
    through tiling ranges, each request carrying only its range's blocks,
    and the responses cover exactly the peer's blocks once (hi None =
    unbounded)."""
    lo, hi = (block_range if block_range else (None, None))
    remote = {int(b["id"]): b.get("checksum", "") for b in blocks}
    out: dict[int, dict] = {}
    for blk, chk in store.blocks():
        if lo is not None and blk < lo:
            continue
        if hi is not None and blk >= hi:
            continue
        if remote.get(blk) == chk.hex():
            continue
        out.update(store.block_data(blk))
    return out
