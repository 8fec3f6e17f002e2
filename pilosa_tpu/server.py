"""Server: process lifecycle wiring holder + cluster + executor + transport.

Reference: server.go — functional options (server.go:84-246), Open() sequence
(§3.1 of SURVEY.md), cluster message dispatch (server.go:485-580), anti-
entropy ticker (server.go:430-483). One Server is one "node": a host process
that owns a data dir and drives the local device mesh slice.
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Optional

from pilosa_tpu.api import API
from pilosa_tpu.executor import Executor
from pilosa_tpu.models import FieldOptions, Holder
from pilosa_tpu.net.client import ClientError, InternalClient
from pilosa_tpu.net.http_server import Handler, HTTPServer
from pilosa_tpu.parallel.cluster import (
    Cluster,
    EVENT_LEAVE,
    Node,
    ResizeJob,
    STATE_NORMAL,
    STATE_RESIZING,
    STATE_STARTING,
)
from pilosa_tpu.parallel.mesh import DeviceRunner
from pilosa_tpu.utils import threads as _threads
from pilosa_tpu.utils.translate import TranslateStore

import os


class Server:
    """One node of the index. With `cluster_hosts` empty: single-node static
    cluster (the reference's `cluster.disabled` mode, server/config.go:65)."""

    def __init__(self, data_dir: str, host: str = "localhost", port: int = 0,
                 node_id: Optional[str] = None,
                 cluster_hosts: Optional[list[str]] = None,
                 replica_n: int = 1,
                 anti_entropy_interval: float = 0.0,
                 anti_entropy_jitter: float = 0.25,
                 anti_entropy_pace: float = 0.0,
                 anti_entropy_max_blocks: int = 0,
                 wal_fsync: str = "off",
                 cache_flush_interval: float = 60.0,
                 membership_interval: float = 5.0,
                 liveness_threshold: int = 3,
                 probe_timeout: float = 2.0,
                 join: bool = False,
                 resize_timeout: float = 120.0,
                 mesh=None,
                 long_query_time: float = 0.0,
                 query_timeout: float = 0.0,
                 max_writes_per_request: int = 5000,
                 metric_service: str = "expvar",
                 metric_host: str = "127.0.0.1:8125",
                 metric_poll_interval: float = 0.0,
                 diagnostics_url: str = "",
                 diagnostics_interval: float = 0.0,
                 tls_certificate: str = "",
                 tls_key: str = "",
                 tls_skip_verify: bool = False,
                 tracing_sampler_type: str = "off",
                 tracing_sampler_param: float = 0.0,
                 tracing_endpoint: str = "",
                 gossip_port: Optional[int] = None,
                 gossip_seeds: Optional[list[str]] = None,
                 gossip_config=None,
                 fanout_pool_size: int = 32,
                 fanout_coalesce_window: float = 0.002,
                 fanout_coalesce_max_batch: int = 64,
                 hedge_delay: float = 0.0,
                 ici_serving: str = "auto",
                 profile_mode: str = "auto",
                 query_history_size: int = 100,
                 telemetry_interval: float = 5.0,
                 telemetry_ring: int = 720,
                 log_format: str = "plain",
                 plan: str = "on",
                 plan_cache_bytes: int = 256 << 20,
                 sparse_threshold: int = 4096,
                 run_threshold: int = 2048,
                 usage_max_principals: int = 256,
                 usage_ring: int = 360,
                 slo_read_latency_ms: float = 0.0,
                 slo_count_latency_ms: float = 0.0,
                 slo_topn_latency_ms: float = 0.0,
                 slo_groupby_latency_ms: float = 0.0,
                 slo_latency_target: float = 0.99,
                 slo_availability_target: float = 0.999,
                 slo_burn_yellow: float = 6.0,
                 slo_burn_red: float = 14.4,
                 slo_window_short: float = 300.0,
                 slo_window_long: float = 3600.0,
                 trace_export: str = "off",
                 trace_export_path: str = "",
                 trace_export_endpoint: str = "",
                 trace_export_format: str = "jaeger",
                 trace_export_sample: float = 1.0,
                 qos_mode: str = "off",
                 qos_default_priority: str = "interactive",
                 qos_default_deadline: float = 0.0,
                 qos_queries_per_s: float = 0.0,
                 qos_device_ms_per_s: float = 0.0,
                 qos_bytes_per_s: float = 0.0,
                 qos_burst: float = 2.0,
                 qos_max_principals: int = 256,
                 qos_principals: Optional[dict] = None,
                 gossip_secret: str = "",
                 hint_max_bytes: int = 64 << 20,
                 hint_max_age: float = 3600.0,
                 drain_timeout: float = 30.0,
                 eviction: str = "lru",
                 events_ring: int = 2048,
                 events_spool: int = 0,
                 ingest_batch_window: float = 0.0,
                 ingest_max_batch: int = 4096):
        self.data_dir = data_dir
        # [storage] wal-fsync, plumbed down the model tree to every
        # Fragment (PILOSA_TPU_WAL_FSYNC env overrides per fragment —
        # precedence documented in docs/operations.md)
        if wal_fsync not in ("off", "always"):
            raise ValueError(
                f"invalid [storage] wal-fsync {wal_fsync!r} "
                "(expected off | always)")
        if plan not in ("on", "off"):
            # a typo'd mode must fail the boot, not silently act as "on"
            raise ValueError(
                f"invalid [query] plan {plan!r} (expected on | off)")
        if eviction not in ("lru", "heat"):
            raise ValueError(
                f"invalid [storage] eviction {eviction!r} "
                "(expected lru | heat)")
        if ici_serving not in ("off", "auto", "on"):
            # a typo'd mode must fail the boot, not silently act as "auto"
            raise ValueError(
                f"invalid [cluster] ici-serving {ici_serving!r} "
                "(expected off | auto | on)")
        self.wal_fsync = wal_fsync
        self.holder = Holder(data_dir, wal_fsync=(wal_fsync == "always"))
        self.node_id = node_id or self._load_or_create_id()
        self.cluster = Cluster(
            self.node_id, replica_n=replica_n,
            schema_fn=self._schema_shards,
            topology_path=os.path.join(data_dir, ".topology"))
        self.translate = TranslateStore(os.path.join(data_dir, ".keys"))
        self.runner = DeviceRunner(mesh)
        self.client = InternalClient(tls_skip_verify=tls_skip_verify)
        from pilosa_tpu.utils.logger import Logger
        from pilosa_tpu.utils.stats import new_stats_client
        from pilosa_tpu.utils.tracing import TraceExporter, Tracer
        self.stats = new_stats_client(metric_service, metric_host)
        # [tracing] config (server/config.go:96-104): an endpoint enables
        # batched span export; sampler gates which traces ship. Accepts a
        # full URL or the reference's bare agent "host:port" form. The
        # exporter is the one [metric] trace-export builds too.
        if tracing_endpoint and "://" not in tracing_endpoint:
            tracing_endpoint = f"http://{tracing_endpoint}/api/traces"
        exporter = (TraceExporter(mode="http", endpoint=tracing_endpoint,
                                  fmt="jaeger")
                    if tracing_endpoint else None)
        self.tracer = Tracer(exporter=exporter,
                             sampler_type=tracing_sampler_type,
                             sampler_param=tracing_sampler_param)
        # --log-format=json emits structured lines carrying trace=<id> as
        # a proper field (utils/logger.py); Logger validates the mode
        self.logger = Logger(fmt=log_format)
        # cluster flight recorder (utils/events.py; docs/operations.md
        # "Flight recorder and incident timelines"): a typed, HLC-stamped
        # event journal every state-transition choke point emits into.
        # The HLC piggybacks on internal RPCs and gossip so the merged
        # /cluster/events timeline is causal, not wall-clock. Knobs:
        # [metric] events-ring (per-lane bound) / events-spool (durable
        # JSONL byte cap, 0 = off); PILOSA_TPU_EVENTS=0 kills recording.
        from pilosa_tpu.utils.events import (
            EventJournal,
            HybridLogicalClock,
            register_crash_dump,
        )
        if events_ring < 1:
            raise ValueError(
                f"invalid [metric] events-ring {events_ring!r} "
                "(expected >= 1)")
        if events_spool < 0:
            raise ValueError("[metric] events-spool must be >= 0")
        self.clock = HybridLogicalClock()
        self.events = EventJournal(
            node_id=self.node_id, ring_size=events_ring, clock=self.clock,
            spool_path=(os.path.join(data_dir, "events.spool.jsonl")
                        if events_spool > 0 else ""),
            spool_max_bytes=events_spool, stats=self.stats)
        # warn/error log lines land on the merged timeline too (bounded
        # LOG lane: a log storm can't evict lifecycle events)
        self.logger.journal = self.events
        # every outbound RPC piggybacks this node's HLC; responses merge
        self.client.hlc = self.clock
        # crash forensics: SIGQUIT (and any fatal path calling
        # spill_all_crash_dumps) spills the ring next to the data dir
        register_crash_dump(self.events, data_dir)
        from pilosa_tpu.utils.diagnostics import (
            DiagnosticsCollector,
            RuntimeMonitor,
        )
        from pilosa_tpu import __version__
        self.runtime_monitor = RuntimeMonitor(self.stats,
                                              metric_poll_interval)
        self.diagnostics = DiagnosticsCollector(
            __version__, url=diagnostics_url, interval=diagnostics_interval,
            holder=self.holder, cluster=self.cluster, logger=self.logger)
        from pilosa_tpu.utils.cluster_translate import ClusterTranslator
        self.cluster_translate = ClusterTranslator(self.translate, self.cluster,
                                                   self.client)
        self.executor = Executor(self.holder, runner=self.runner,
                                 translator=self.cluster_translate,
                                 cluster=self.cluster, client=self.client)
        self.executor.stats = self.stats
        # distributed fan-out knobs (net/coalesce.py; docs/operations.md
        # "Fan-out and hedging"): persistent pool size, coalesce window /
        # envelope cap, hedged-read delay (0 disables hedging)
        self.executor.fanout_pool_size = fanout_pool_size
        self.executor.hedge_delay = hedge_delay
        # [cluster] ici-serving: slice-local routing mode (docs
        # "ICI-native serving"). The PILOSA_TPU_ICI=0 env kill switch
        # (read at Executor/DeviceRunner construction) wins over config —
        # the emergency toggle needs no rollout. ici-serving=off also
        # keeps the runner on the GSPMD jit kernels (no shard_map
        # serving-mode programs), so off truly is the pre-ICI engine.
        self.executor.ici_mode = ici_serving
        if ici_serving == "off":
            self.runner.ici_serving = False
        # [query] planner + plan-cache knobs (docs/operations.md "Query
        # planning"). The env kill switches (PILOSA_TPU_PLANNER=0 /
        # PILOSA_TPU_PLAN_CACHE=0, read at Executor construction) win over
        # config — the emergency toggles need no config rollout.
        if plan == "off":
            self.executor.planner = None
        if plan_cache_bytes <= 0:
            self.executor.plan_cache = None
        elif self.executor.plan_cache is not None:
            self.executor.plan_cache.budget = plan_cache_bytes
        # [query] sparse-threshold: hybrid sparse/dense device containers
        # (docs/operations.md "Hybrid containers"); 0 = pure dense. The
        # PILOSA_TPU_HYBRID=0 env kill switch is read per decision and
        # wins over any threshold — no rollout needed.
        if sparse_threshold < 0:
            raise ValueError(
                f"invalid [query] sparse-threshold {sparse_threshold!r} "
                "(expected >= 0)")
        self.executor.hybrid.threshold = sparse_threshold
        # [query] run-threshold: run (interval-pair) device containers
        # for long-run rows above the sparse threshold; 0 = never run.
        if run_threshold < 0:
            raise ValueError(
                f"invalid [query] run-threshold {run_threshold!r} "
                "(expected >= 0)")
        self.executor.hybrid.run_threshold = run_threshold
        if self.executor.coalescer is not None:
            self.executor.coalescer.admission_s = fanout_coalesce_window
            self.executor.coalescer.max_batch = max(
                1, fanout_coalesce_max_batch)
        # [ingest] — write-side continuous batching (docs/operations.md
        # "Streaming ingest"); window 0 = self-clocked group commit. The
        # PILOSA_TPU_INGEST=0 kill switch is read per call and wins.
        if ingest_batch_window < 0:
            raise ValueError(
                f"invalid [ingest] batch-window {ingest_batch_window!r} "
                "(expected >= 0)")
        self.executor.ingest.admission_s = float(ingest_batch_window)
        self.executor.ingest.max_batch = max(1, ingest_max_batch)
        # [storage] eviction = lru|heat: heat steers DeviceResidency to
        # evict coldest-by-fragment-heat instead of LRU (utils/heat.py).
        # The PILOSA_TPU_HEAT=0 kill switch wins structurally: with it
        # set the Executor built no tracker and the residency manager
        # falls back to lru regardless of this knob.
        self.executor.residency.eviction = eviction
        # durable hinted handoff (storage/hints.py): replica writes
        # skipped because the target is down/draining append here and
        # replay in order when the target returns ([cluster]
        # hint-max-bytes / hint-max-age knobs; fsync follows wal-fsync —
        # a hint guards an acked write, so it gets the WAL's durability)
        from pilosa_tpu.storage.hints import HintStore
        if hint_max_age < 0 or drain_timeout < 0:
            raise ValueError(
                "[cluster] hint-max-age and drain-timeout must be >= 0")
        self.hints = HintStore(os.path.join(data_dir, ".hints"),
                               max_bytes=hint_max_bytes,
                               max_age=hint_max_age,
                               fsync=(wal_fsync == "always"),
                               stats=self.stats, logger=self.logger,
                               journal=self.events)
        self.executor.hints = self.hints
        # flight-recorder hook for topology-fingerprint flips and
        # slice-local route flips (executor._ici_co_resident)
        self.executor.journal = self.events
        # graceful-drain lifecycle (docs/operations.md "Rolling restarts
        # and drains"): SIGTERM / POST /cluster/drain moves this node to
        # a broadcast DRAINING state, sheds new external queries with
        # 503 + X-Pilosa-Shed-Reason: draining, waits out in-flight work
        # and queue flushes, then lands a final snapshot per dirty
        # fragment so the restart replays no WAL.
        self.drain_timeout = drain_timeout
        self.draining = False
        self.drained = False
        self._drain_lock = threading.Lock()
        self._drain_thread: Optional[threading.Thread] = None
        self._drain_abort = threading.Event()
        self._drain_info: dict = {}
        # rejoin read fence: how long a fenced shard may wait for parity
        # verification before availability wins and the fence lifts loudly
        self.rejoin_fence_timeout = 120.0
        self._fence_thread: Optional[threading.Thread] = None
        self._fence_wake = threading.Event()
        self.api = API(self.holder, self.cluster, executor=self.executor,
                       translate_store=self.cluster_translate)
        # distributed query profiler knobs ([cluster] profile /
        # query-history-size; PILOSA_TPU_PROFILE=0 kill switch is read by
        # the API itself): mode gates when a QueryProfile is recorded, the
        # ring holds the /debug/query-history entries
        if profile_mode not in ("off", "auto", "on"):
            # a typo'd mode must fail the boot, not silently act as "auto"
            raise ValueError(
                f"invalid [cluster] profile mode {profile_mode!r} "
                "(expected off | auto | on)")
        self.api.profile_mode = profile_mode
        from pilosa_tpu.utils.profile import QueryHistory
        self.api.query_history = QueryHistory(query_history_size)
        # per-principal resource accounting (utils/accounting.py): the
        # bounded usage ledger every charge site in the stack attributes
        # into ([metric] usage-max-principals / usage-ring knobs;
        # PILOSA_TPU_ACCOUNTING=0 kill switch read per request), plus the
        # [slo] objectives evaluated with multi-window burn-rate math.
        from pilosa_tpu.utils import accounting as _accounting
        self.usage = _accounting.UsageLedger(
            max_principals=usage_max_principals, ring_size=usage_ring)
        self.api.usage_ledger = self.usage
        objectives = []
        if slo_availability_target > 0:
            objectives.append(_accounting.Objective(
                "availability", None, None, slo_availability_target))
        for cls, ms in (("read", slo_read_latency_ms),
                        ("count", slo_count_latency_ms),
                        ("topn", slo_topn_latency_ms),
                        ("groupby", slo_groupby_latency_ms)):
            if ms > 0:
                objectives.append(_accounting.Objective(
                    f"{cls}-latency", cls, ms, slo_latency_target))
        self.slo = _accounting.SLOTracker(
            objectives, short_window=slo_window_short,
            long_window=slo_window_long, burn_yellow=slo_burn_yellow,
            burn_red=slo_burn_red)
        self.api.slo = self.slo
        # external trace export ([metric] trace-export = off|file|http):
        # finished cross-node profile trees — and, when no [tracing]
        # endpoint claimed the recording tracer, its finished spans too —
        # ship as Jaeger/OTLP-JSON batches to a spool file or collector.
        # PILOSA_TPU_TRACE_EXPORT=0 is the kill switch (read per batch).
        if trace_export not in ("off", "file", "http"):
            raise ValueError(
                f"invalid [metric] trace-export {trace_export!r} "
                "(expected off | file | http)")
        self.trace_exporter = None
        if trace_export != "off":
            spool = trace_export_path or os.path.join(
                data_dir, "trace-spool.jsonl")
            self.trace_exporter = TraceExporter(
                mode=trace_export, path=spool,
                endpoint=trace_export_endpoint, fmt=trace_export_format,
                sample=trace_export_sample)
            self.api.trace_exporter = self.trace_exporter
            if exporter is None:
                # the recording tracer ships its spans through the same
                # egress; sampling follows trace-export-sample unless the
                # operator configured an explicit [tracing] sampler
                self.tracer.exporter = self.trace_exporter
                if tracing_sampler_type == "off":
                    self.tracer.sampler_type = "probabilistic"
                    self.tracer.sampler_param = trace_export_sample
        # fleet telemetry (utils/telemetry.py): background sampler ->
        # bounded ring served at GET /debug/timeseries; [metric]
        # telemetry-interval / telemetry-ring knobs, PILOSA_TPU_TELEMETRY=0
        # kill switch. The federation + /status share node_health().
        from pilosa_tpu.utils.telemetry import TelemetrySampler
        if telemetry_ring < 1:
            raise ValueError(
                f"invalid [metric] telemetry-ring {telemetry_ring!r} "
                "(expected >= 1)")
        self.telemetry = TelemetrySampler(interval=telemetry_interval,
                                          ring_size=telemetry_ring,
                                          source=self.sample_gauges,
                                          logger=self.logger)
        self._telemetry_prev: tuple = (None, 0.0)
        self._last_hit_rate = 1.0  # carried through zero-lookup windows
        self._last_plan_hit_rate = 0.0  # plan cache starts cold
        self._last_ici_share = 0.0  # slice-local share of routed reads
        self._last_hybrid_share = 0.0  # sparse share of row-leaf uploads
        self._last_hybrid_run_share = 0.0  # run share of row-leaf uploads
        self.api.health_fn = self.node_health
        self.api.node_stats_fn = self.node_stats
        self.api.cluster_stats_fn = self.cluster_stats
        self.api.cluster_usage_fn = self.cluster_usage
        self.api.cluster_heat_fn = self.cluster_heat
        self.api.cluster_events_fn = self.cluster_events
        self.api.cluster_hbm_fn = self.cluster_hbm
        # last health score seen by the sampler: a change emits a
        # health.transition event onto the timeline
        self._last_health: Optional[str] = None
        # multi-tenant QoS plane (pilosa_tpu/qos.py): per-principal quota
        # buckets refilled against the usage ledger, priority classes the
        # batchers/pools order by, deadline-aware admission + shedding.
        # Built unconditionally (mode="off" = zero behavior change) so
        # the qos/* observability families always exist; QosPlane
        # validates mode/priority/overrides and fails the boot on typos.
        # PILOSA_TPU_QOS=0 is the env kill switch over any mode.
        from pilosa_tpu.qos import QosPlane
        self.qos = QosPlane(
            mode=qos_mode, default_priority=qos_default_priority,
            default_deadline=qos_default_deadline,
            queries_per_s=qos_queries_per_s,
            device_ms_per_s=qos_device_ms_per_s,
            bytes_per_s=qos_bytes_per_s, burst_s=qos_burst,
            max_principals=qos_max_principals, principals=qos_principals,
            executor=self.executor, ledger=self.usage,
            health_fn=self.node_health, logger=self.logger)
        # shed-storm onset/end + quota-debt events ride the journal
        self.qos.journal = self.events
        self.api.qos_plane = self.qos
        self.api.drain_fn = self.request_drain
        self.api.drain_status_fn = self.drain_status
        self.api.node_state_fn = (
            lambda: "DRAINING" if self.draining else "READY")
        self.handler = Handler(self.api, cluster_message_fn=self.receive_message,
                               stats=self.stats, query_timeout=query_timeout,
                               telemetry=self.telemetry, qos_plane=self.qos,
                               events=self.events, tracer=self.tracer)
        self.http = HTTPServer(self.handler, host=host, port=port,
                               tls_certificate=tls_certificate, tls_key=tls_key)
        self._bind_host = host
        self.cluster_hosts = cluster_hosts or []
        self.long_query_time = long_query_time
        self.max_writes_per_request = max_writes_per_request
        self.anti_entropy_interval = anti_entropy_interval
        # scrubber tuning (docs/operations.md "Failure modes and
        # recovery"): jitter de-synchronizes the nodes' scrub passes (a
        # cluster whose replicas all scrub at the same instant doubles its
        # own fan-out load spike); pace sleeps between per-fragment scrubs
        # so a pass never starves the query fan-out pool; max_blocks
        # bounds the blocks merged per fragment per pass (0 = unbounded)
        if not 0.0 <= anti_entropy_jitter < 1.0:
            # a FRACTION of the interval, not seconds — jitter >= 1 would
            # sample negative intervals, i.e. a continuous scrub storm
            raise ValueError(
                f"invalid [anti-entropy] jitter {anti_entropy_jitter!r} "
                "(a fraction: expected 0 <= jitter < 1)")
        if anti_entropy_pace < 0 or anti_entropy_max_blocks < 0:
            raise ValueError("[anti-entropy] pace and max-blocks must be >= 0")
        self.anti_entropy_jitter = anti_entropy_jitter
        self.anti_entropy_pace = anti_entropy_pace
        self.anti_entropy_max_blocks = anti_entropy_max_blocks
        self._scrub_passes = 0
        self.cache_flush_interval = cache_flush_interval
        self._cache_flush_timer: Optional[threading.Timer] = None
        self.membership_interval = membership_interval
        # liveness probing (the memberlist probe/suspicion analog,
        # gossip/gossip.go:488-519): after `liveness_threshold` consecutive
        # failed /status probes a peer is marked down and routed around
        self.liveness_threshold = liveness_threshold
        self.probe_timeout = probe_timeout
        self._probe_failures: dict[str, int] = {}
        # consecutive successful probes of a DOWN node (anti-flap: one
        # lucky probe must not flip a struggling peer back into placement
        # only to flap out again next tick)
        self._probe_successes: dict[str, int] = {}
        # successes required to revive a down node (memberlist-style
        # hysteresis; 1 = the old instant-revive behavior)
        self.revive_threshold = 2
        # peers asked to confirm a suspected-dead node before we mark it
        # down (memberlist indirect ping fan-out)
        self.indirect_probes = 2
        # node ids with an in-flight return-heal (single-flight per node)
        self._return_sync_running: set[str] = set()
        # optional SWIM gossip failure detector (gossip/gossip.go:42-541):
        # gossip_port switches liveness from the HTTP probe loop to UDP
        # probe/ack + suspicion + refutation; both drive the same
        # mark_down/mark_up hooks. 0 = bind an ephemeral port.
        self.gossip = None
        self._gossip_port = gossip_port
        self._gossip_seeds = gossip_seeds or []
        self._gossip_config = gossip_config
        # [gossip] secret: non-empty -> every gossip datagram is AES-GCM
        # encrypted under a key derived from the shared passphrase
        # (parallel/gossip.py; utils/aesgcm.py)
        self._gossip_secret = gossip_secret
        # join=True: this node is being added to an existing cluster —
        # cluster_hosts are seed URIs (the gossip-seeds analog). It announces
        # itself and stays STARTING until the coordinator's resize completes
        # and a topology broadcast admits it (nodeJoin, cluster.go:1715).
        self.join = join
        self._ae_timer: Optional[threading.Timer] = None
        self._member_timer: Optional[threading.Timer] = None
        # coordinator-side queue of membership events that arrived while a
        # resize was already running (listenForJoins, cluster.go:1095-1148)
        self._pending_resizes: list[tuple[str, Node]] = []
        self._resize_lock = threading.Lock()
        # tombstones: ids removed by resize. Without these the additive
        # membership merge would resurrect a removed-but-still-running node
        # (the memberlist leave-event analog for static clusters).
        self._removed_ids: set[str] = set()
        self._left = False  # this node itself was removed from the cluster
        # a lost resize-complete ack must not wedge the cluster in RESIZING
        # forever: the coordinator aborts the job after resize_timeout
        self.resize_timeout = resize_timeout
        self._resize_watchdog: Optional[threading.Timer] = None
        # async broadcast plane (SendAsync, broadcast.go:30-36): writes
        # announce shards through a queue drained off the request thread,
        # so a slow/hung peer never adds latency to Set()/imports
        import queue as _queue
        self._bcast_queue: "_queue.Queue" = _queue.Queue()
        self._bcast_thread: Optional[threading.Thread] = None
        self._bcast_dropped = 0  # per-peer queue overflow drops (AE heals)
        self.closed = False

    # -- lifecycle (server.go Open, §3.1) -----------------------------------

    def _load_or_create_id(self) -> str:
        """Persistent node id (.id file, holder.go:576)."""
        os.makedirs(self.data_dir, exist_ok=True)
        path = os.path.join(self.data_dir, ".id")
        if os.path.exists(path):
            with open(path) as f:
                return f.read().strip()
        node_id = str(uuid.uuid4())
        with open(path, "w") as f:
            f.write(node_id)
        return node_id

    def _schema_shards(self) -> dict:
        """{index: {field: [shards]}} from the cluster-wide available-shards
        bitmaps (broadcast-synced), NOT local fragments — a shard that was
        migrated away must still be planned over on the next resize."""
        out: dict = {}
        for iname, idx in self.holder.indexes.items():
            for fname, field in idx.fields.items():
                out.setdefault(iname, {})[fname] = [
                    int(s) for s in field.available_shards.slice()]
        return out

    def open(self) -> "Server":
        self.translate.open()
        self.holder.open()
        for d in self.holder.damaged_fragments():
            # recovery happened inside Fragment.open; make it LOUD for the
            # operator (also surfaced in /debug/vars damagedFragments and
            # on the flight-recorder timeline)
            frag_key = (f"{d['index']}/{d['field']}/{d['view']}"
                        f"/{d['shard']}")
            if d["quarantinePath"]:
                self.logger.errorf(
                    "storage: fragment %s failed its integrity "
                    "check (%s): quarantined to %s, reopened empty — the "
                    "scrubber will rebuild it from a replica",
                    frag_key, d["corruptionError"], d["quarantinePath"])
                self.events.emit("snapshot.quarantined", fragment=frag_key,
                                 error=str(d["corruptionError"])[:200],
                                 quarantinePath=d["quarantinePath"])
            if d["walTruncatedBytes"]:
                self.logger.warnf(
                    "storage: fragment %s had a torn WAL tail "
                    "(%s): truncated %d un-acked bytes",
                    frag_key, d["walTruncateError"],
                    d["walTruncatedBytes"])
                self.events.emit("wal.truncated", fragment=frag_key,
                                 bytes=int(d["walTruncatedBytes"]))
        self.holder.set_shard_hook(self._on_shard_added)
        self.http.serve_background()
        me = Node(id=self.node_id, uri=self.http.uri,
                  is_coordinator=not self.cluster_hosts)
        if self.join and self.cluster_hosts:
            # dynamic member: knock on the seeds and wait in STARTING for the
            # coordinator's resize + topology broadcast to admit us
            self.cluster.nodes = [me]
            self.request_join()
            if self.membership_interval > 0:
                self._schedule_membership_refresh()
        elif not self.cluster_hosts:
            self.cluster.set_static([me])
            self.cluster.coordinator_id = self.node_id
        else:
            # static multi-node (all hosts known up front; nodes ordered by
            # id). Peers may not be up yet: start with self, converge via
            # refresh_membership once peers answer /internal/nodes.
            self.cluster.set_static([me])
            self.refresh_membership()
            # peers may come up later: keep refreshing until everyone answers
            # (the gossip-convergence analog for static clusters)
            if self.membership_interval > 0:
                self._schedule_membership_refresh()
        self.api.broadcast_fn = self.broadcast
        # shard-CREATING Set writes announce before the ack
        # (read-your-writes through any node; see executor.py) — bulk
        # imports keep the async _on_shard_added queue
        self.executor.announce_shard_fn = self._announce_shard_bounded
        self.api.resize_fn = self._resize_request
        self.api.abort_fn = self._abort_request
        self.api.forward_import_fn = self.client.import_bits
        self.api.forward_roaring_fn = (
            lambda uri, index, field, shard, views, clear:
            self.client.import_roaring(uri, index, field, shard, views,
                                       clear=clear, remote=True))
        self.api.long_query_time = self.long_query_time
        self.api.max_writes_per_request = self.max_writes_per_request
        self.api.logger = self.logger
        self.api.probe_peer_fn = (
            lambda target_uri: bool(
                self.client.status(target_uri, timeout=self.probe_timeout)))
        if self._gossip_port is not None:
            self._open_gossip()
        if self.anti_entropy_interval > 0:
            self._schedule_anti_entropy()
        if self.cache_flush_interval > 0:
            self._schedule_cache_flush()
        self._bcast_thread = _threads.spawn(self._bcast_worker,
                                            name="pilosa-bcast")
        self.runtime_monitor.start()
        self.diagnostics.start()
        # route recompile-storm warnings into the server log (process-
        # global counters: the first server's logger wins, later in-process
        # servers — a test pattern — keep it)
        from pilosa_tpu.utils import telemetry as _telemetry
        if _telemetry.xla.log_fn is None:
            _telemetry.xla.log_fn = self.logger.printf
        if _telemetry.xla.event_fn is None:
            # recompile storms land on the flight-recorder timeline too
            # (process-global counters: first server's journal wins,
            # exactly like log_fn)
            _telemetry.xla.event_fn = self._xla_storm_event
        self.telemetry.start()
        # rejoin protocol (docs/operations.md "Rolling restarts and
        # drains"): (1) read-fence local fragments that may have missed
        # writes while this process was away, until parity with a replica
        # is verified; (2) announce the return so peers clear our
        # DRAINING/down mark and replay queued hints immediately instead
        # of waiting a probe cycle.
        self._arm_read_fence()
        self.events.emit("node.start", uri=self.http.uri,
                         cluster=bool(self.cluster_hosts))
        if self.cluster_hosts and not self.join:
            self.broadcast({"type": "node-state", "id": self.node_id,
                            "state": "READY"})
        return self

    def _schedule_membership_refresh(self) -> None:
        if self.closed:
            return
        self._member_timer = _threads.ctx_timer(self.membership_interval,
                                                self._membership_tick)
        self._member_timer.start()

    def _membership_tick(self) -> None:
        try:
            if self.join and self.cluster.state == STATE_STARTING \
                    and not self.cluster.down_ids:
                # keep knocking until admitted — but only when STARTING
                # means "not yet joined"; liveness-induced STARTING (peers
                # down >= ReplicaN) must fall through so probing can detect
                # their return and mark them back up
                self.request_join()
            else:
                # fetch over the network WITHOUT the lock, then apply the
                # merge under it so it cannot interleave with a join/leave
                # job flipping state (set_static would un-gate writes
                # mid-resize and orphan the active job)
                reports = self._fetch_peer_nodes()
                if reports is not None:
                    with self._resize_lock:
                        if self.cluster.state != STATE_RESIZING \
                                and self.cluster.active_job is None:
                            self._apply_membership(reports)
                if self.gossip is None:
                    # otherwise gossip is the failure detector; the HTTP
                    # probe loop would fight its suspicion timing
                    self._probe_peers()
            # hinted-handoff retry: a replay that failed mid-stream (the
            # target flapped, an injected fault) keeps its log; if the
            # target is alive NOW, re-run the return-heal rather than
            # waiting for another down/up transition that may never come
            self._retry_pending_hints()
        finally:
            self._schedule_membership_refresh()

    # -- SWIM gossip failure detector (optional backend) --------------------

    def _open_gossip(self) -> None:
        """Start the UDP gossip endpoint and join the seeds. The node's
        HTTP URI rides the alive record's meta (the NodeMeta channel the
        reference uses for the same purpose, gossip/gossip.go:248-257), so
        peers discovered purely by gossip can be admitted to membership."""
        from pilosa_tpu.parallel.gossip import Gossip, parse_seed
        from pilosa_tpu.utils.aesgcm import derive_key
        self.gossip = Gossip(self.node_id, bind_host=self._bind_host,
                             bind_port=self._gossip_port,
                             meta={"uri": self.http.uri},
                             config=self._gossip_config,
                             on_alive=self._on_gossip_alive,
                             on_dead=self._on_gossip_dead,
                             secret_key=(derive_key(self._gossip_secret)
                                         if self._gossip_secret else None),
                             logger=self.logger)
        # gossip datagrams piggyback the flight-recorder HLC (the UDP
        # twin of the HTTP plane's X-Pilosa-HLC header)
        self.gossip.clock = self.clock
        self.gossip.open(seeds=[parse_seed(s) for s in self._gossip_seeds])
        self.logger.printf("gossip: listening on %s:%d (seeds: %s)",
                           self.gossip.host, self.gossip.port,
                           ",".join(self._gossip_seeds) or "none")

    def _on_gossip_dead(self, member) -> None:
        """Gossip declared a peer dead (suspicion expired un-refuted):
        the NodeLeave -> route-around path (cluster.go:1690-1703)."""
        if self.closed or member.id == self.node_id:
            return
        if any(n.id == member.id for n in self.cluster.nodes) \
                and not self.cluster.is_down(member.id):
            self.logger.printf("gossip: node %s dead (suspicion expired), "
                               "marking down", member.id)
            self.cluster.mark_down(member.id)
            self.stats.count("liveness/node_down")
            self.events.emit("peer.down", peer=member.id,
                             detector="gossip")

    def _on_gossip_alive(self, member) -> None:
        """A peer (re)entered alive state: revive it if it was down, or
        admit a gossip-discovered node to membership (NotifyJoin,
        gossip/gossip.go:335-342)."""
        if self.closed or member.id == self.node_id:
            return
        node = next((n for n in self.cluster.nodes if n.id == member.id),
                    None)
        if node is None:
            uri = member.meta.get("uri")
            if uri and member.id not in self._removed_ids:
                with self._resize_lock:
                    if self.cluster.state != STATE_RESIZING \
                            and self.cluster.active_job is None:
                        self._apply_membership([{"id": member.id,
                                                 "uri": uri}])
        elif self.cluster.is_down(member.id):
            self.logger.printf("gossip: node %s back up", member.id)
            self.cluster.mark_up(member.id)
            self.events.emit("peer.up", peer=member.id,
                             detector="gossip")
            self._on_node_return(node)

    def refresh_membership(self) -> None:
        """Merge peer node lists from all configured hosts (the static-mode
        analog of a gossip LocalState/MergeRemoteState sync,
        gossip/gossip.go:274-316)."""
        reports = self._fetch_peer_nodes()
        if reports is None:
            return
        self._apply_membership(reports)

    def _fetch_peer_nodes(self) -> Optional[list[dict]]:
        """Network half of refresh_membership: peer reports, no locks, no
        cluster mutation (safe to run outside _resize_lock)."""
        if not self.cluster_hosts or self._left:
            return None
        reports: list[dict] = []
        for huri in self.cluster_hosts:
            if huri == self.http.uri:
                continue
            try:
                # short timeout: a SIGSTOP'd/hung seed must not stall the
                # membership tick for the client's default 30s — liveness
                # probing downstream of this fetch depends on ticks firing
                reports.extend(
                    self.client.nodes(huri, timeout=self.probe_timeout) or [])
            except ClientError:
                pass
        return reports

    def _apply_membership(self, reports: list[dict]) -> None:
        me = Node(id=self.node_id, uri=self.http.uri)
        # seed with current membership: nodes admitted dynamically (topology
        # broadcasts) stay known even when a seed host is briefly down
        nodes = {n.id: n for n in self.cluster.nodes
                 if n.id not in self._removed_ids}
        nodes[self.node_id] = me
        for nd in reports:
            if nd["id"] not in nodes and nd["id"] not in self._removed_ids:
                nodes[nd["id"]] = Node.from_dict(nd)
        self.cluster.set_static(list(nodes.values()))
        # sticky explicit coordinator; lowest node id otherwise
        self.cluster.elect_coordinator()

    def _probe_peers(self) -> None:
        """Liveness detection: probe every known peer's /status each
        membership tick. `liveness_threshold` consecutive failures mark the
        node down (memberlist probe -> suspicion -> NodeLeave,
        gossip/gossip.go:488-519); placement then routes around it and the
        cluster state recomputes (DEGRADED / STARTING, cluster.go:522-533).
        A later successful probe marks it back up — the reference treats
        this as 'temporarily unavailable... expect it to come back up'
        (cluster.go:1694-1696)."""
        if self._left or self.closed:
            return
        peers = [n for n in list(self.cluster.nodes)
                 if n.id != self.node_id and n.uri]
        # drop counters for nodes no longer in membership, so a node that
        # is removed and later re-added starts from a clean slate
        peer_ids = {n.id for n in peers}
        for stale in set(self._probe_failures) - peer_ids:
            del self._probe_failures[stale]
        for stale in set(self._probe_successes) - peer_ids:
            del self._probe_successes[stale]
        if not peers:
            return

        # probe concurrently: N down peers must cost one probe_timeout per
        # tick, not N of them (the membership timer is a single thread)
        claims: dict[str, str] = {}  # live peer -> its coordinator claim
        node_states: dict[str, str] = {}  # live peer -> its nodeState

        def probe(node):
            try:
                st = self.client.status(node.uri, timeout=self.probe_timeout)
                claim = st.get("coordinatorID")
                if claim:
                    claims[node.id] = claim
                node_states[node.id] = st.get("nodeState", "")
                return True
            except Exception:  # noqa: BLE001 — ANY probe failure means
                # not-alive (ClientError, socket teardown mid-close, ...);
                # an escaping exception would kill the probe thread and
                # count as dead anyway, minus the noise
                return False

        results: dict[str, bool] = {}
        threads = []
        for node in peers:
            threads.append(_threads.spawn(
                lambda n=node: results.__setitem__(n.id, probe(n))))
        for t in threads:
            t.join(self.probe_timeout + 1.0)
        suspects: list = []
        for node in peers:
            alive = results.get(node.id, False)
            if alive:
                self._probe_failures.pop(node.id, None)
                if self.cluster.is_down(node.id):
                    # anti-flap hysteresis: a down node needs
                    # revive_threshold CONSECUTIVE good probes before it
                    # re-enters placement (memberlist's suspicion decay —
                    # one lucky probe of a struggling peer must not flap
                    # it up only to fall out again next tick)
                    ok = self._probe_successes.get(node.id, 0) + 1
                    if ok < self.revive_threshold:
                        self._probe_successes[node.id] = ok
                        continue
                    self._probe_successes.pop(node.id, None)
                    self.logger.printf("liveness: node %s (%s) back up",
                                       node.id, node.uri)
                    self.cluster.mark_up(node.id)
                    self.events.emit("peer.up", peer=node.id,
                                     detector="probe")
                    self._on_node_return(node)
                elif self.cluster.is_draining(node.id) \
                        and node_states.get(node.id) == "READY":
                    # the drained peer restarted and we missed its rejoin
                    # broadcast: its own /status says READY — clear the
                    # mark and run the return-heal (hint replay first)
                    self.logger.printf(
                        "drain: peer %s back from drain (probe)", node.id)
                    self.cluster.clear_draining(node.id)
                    self._on_node_return(node)
            else:
                self._probe_successes.pop(node.id, None)
                n = self._probe_failures.get(node.id, 0) + 1
                self._probe_failures[node.id] = n
                if (n >= self.liveness_threshold
                        and not self.cluster.is_down(node.id)):
                    suspects.append(node)
        # coordinator convergence: adopt the claim of the lowest-id LIVE
        # node (the deterministic electoral authority — its own claim is
        # sticky via elect_coordinator), so an explicit set-coordinator
        # reaches nodes that missed the broadcast within one probe tick
        live_ids = {self.node_id} | {n.id for n in peers
                                     if results.get(n.id)}
        authority = min(live_ids)
        if authority != self.node_id:
            claim = claims.get(authority)
            if claim and self.cluster.node_by_id(claim) is not None:
                self.cluster.adopt_coordinator(claim)
        if not suspects:
            return
        # SUSPECT phase: before declaring a peer dead, ask other live
        # peers to probe it for us (memberlist indirect ping) — a broken
        # link between us and the peer must not evict a node the rest of
        # the cluster can reach. All suspects are checked concurrently
        # (same rule as the direct probes: N suspects must not serialize
        # N timeouts on the membership-tick thread).
        refuted: dict[str, bool] = {}
        checkers = []
        for node in suspects:
            checkers.append(_threads.spawn(
                lambda nd=node: refuted.__setitem__(
                    nd.id,
                    self._indirect_confirms_alive(nd, peers, results))))
        deadline = 3 * self.probe_timeout + 3.0
        for t in checkers:
            t.join(deadline)
        for node in suspects:
            if refuted.get(node.id):
                self.logger.printf(
                    "liveness: node %s (%s) suspected after %d failed "
                    "probes but refuted by indirect probe (link problem, "
                    "not node death)", node.id, node.uri,
                    self._probe_failures.get(node.id, 0))
                self._probe_failures.pop(node.id, None)
                self.stats.count("liveness/suspect_refuted")
                continue
            self.logger.printf(
                "liveness: node %s (%s) failed %d probes, marking "
                "down (cluster -> %s)", node.id, node.uri,
                self._probe_failures.get(node.id, 0),
                "DEGRADED" if len(self.cluster.down_ids) + 1
                < self.cluster.replica_n else "STARTING")
            self.cluster.mark_down(node.id)
            self.stats.count("liveness/node_down")
            self.events.emit("peer.down", peer=node.id, detector="probe",
                             failedProbes=self._probe_failures.get(
                                 node.id, 0))

    def _indirect_confirms_alive(self, target, peers, results) -> bool:
        """Ask up to `indirect_probes` live peers whether THEY can reach
        the suspected node (gossip/gossip.go probe path). True if any
        vouches for it. Helpers are asked CONCURRENTLY (same rule as the
        direct probes: N suspects must not serialize N timeouts on the
        membership-tick thread), and the outer RPC deadline leaves room
        for the helper's own nested probe_timeout — a genuine vouch for a
        slow-but-alive node must not be discarded by our socket closing
        first."""
        helpers = [p for p in peers
                   if p.id != target.id and results.get(p.id)
                   and not self.cluster.is_down(p.id)][:self.indirect_probes]
        if not helpers:
            return False
        outer_timeout = 2 * self.probe_timeout + 1.0
        vouched = threading.Event()  # set by the FIRST positive vote
        done = threading.Event()  # set when every helper has answered
        votes: dict[str, bool] = {}

        def ask(helper):
            try:
                votes[helper.id] = self.client.probe_indirect(
                    helper.uri, target.uri, timeout=outer_timeout)
            except Exception:  # noqa: BLE001 — helper unreachable: no vote
                votes[helper.id] = False
            if votes[helper.id]:
                vouched.set()
            if len(votes) == len(helpers):
                done.set()

        for h in helpers:
            _threads.spawn(ask, h)
        # one vouch settles it — don't hold the membership tick hostage to
        # the slowest helper's full timeout (a recurring-suspect peer would
        # stall liveness detection for every OTHER peer each round)
        deadline = time.monotonic() + outer_timeout + 1.0
        while time.monotonic() < deadline:
            if vouched.wait(0.05) or done.is_set():
                break
        return vouched.is_set() or any(votes.values())

    def _on_node_return(self, node) -> None:
        """Heal a peer that was probe-marked down and came back: broadcasts
        skipped it while down, so (a) the coordinator re-pushes schema DDL +
        available shards it may have missed, and (b) this node runs one
        anti-entropy pass — even when the periodic ticker is disabled — so
        writes acked during the outage reach the returning replica (the
        reference's returning memberlist node gets the cluster status on
        re-join, cluster.go:1755-1765, and heals via anti-entropy).

        Every observer pushes (not just the coordinator — the down node may
        BE the coordinator); the sync applies via create-if-not-exists, so
        duplicate pushes are idempotent. Missed delete-index/delete-field
        broadcasts are NOT replayed — the returning node keeps the deleted
        schema objects, matching the reference (a memberlist node that was
        partitioned through a DeleteIndex keeps it too; holder.go has no
        delete reconciliation) — but stale fragments are never pushed back
        to peers (the peer's 404 distinguishes missing-fragment from
        missing-field, _sync_fragment).

        The entire heal runs on a background thread (the probe tick must
        never block on the returning node), is single-flight PER RETURNING
        NODE (two nodes returning together each get their own heal), and
        syncs only the shards this node co-owns with the returner — not a
        full cluster-wide pass per observer."""
        if node.id in self._return_sync_running:
            return
        self._return_sync_running.add(node.id)

        def heal():
            try:
                try:
                    self.client.send_message(node.uri, {
                        "type": "schema-sync",
                        "schema": self.holder.schema(),
                        "availableShards": {
                            iname: {fname: [int(s)
                                            for s in f.available_shards.slice()]
                                    for fname, f in idx.fields.items()}
                            for iname, idx in self.holder.indexes.items()},
                    })
                except ClientError as e:
                    self.logger.printf(
                        "liveness: schema re-sync to %s failed: %s",
                        node.id, e)
                if self.cluster._explicit_claim:
                    # the returning node missed the set-coordinator
                    # broadcast while down — re-push the explicit CLAIM
                    # (heals the gossip backend too, where the probe-tick
                    # claim convergence does not run; the receiver keeps it
                    # pending until it knows the claimed node)
                    try:
                        self.client.send_message(node.uri, {
                            "type": "set-coordinator",
                            "id": self.cluster._explicit_claim})
                    except ClientError as e:
                        self.logger.printf(
                            "liveness: coordinator re-push to %s failed: %s",
                            node.id, e)
                # durable hinted handoff first: writes skipped while the
                # node was away stream back in order (idempotent apply).
                # The O(blocks) anti-entropy sync runs ONLY when hints
                # were dropped (byte/age caps, torn log) — a clean replay
                # IS the heal, no scrub pass required.
                complete = True
                try:
                    _r, _d, complete = self.replay_hints(node)
                except Exception as e:  # noqa: BLE001 — replay failure
                    # falls back to the full sync below
                    complete = False
                    self.logger.printf(
                        "hints: replay to %s failed: %s", node.id, e)
                try:
                    if not complete:
                        self._sync_with_node(node.id)
                except Exception as e:  # noqa: BLE001 — best-effort healing
                    self.logger.printf(
                        "liveness: post-return sync failed: %s", e)
                # tell the returning node its hints are in, so its rejoin
                # read fence verifies and lifts now, not at the next poll
                try:
                    self.client.send_message(node.uri, {
                        "type": "hints-replayed", "target": node.id,
                        "from": self.node_id, "complete": complete})
                except ClientError:
                    pass
            finally:
                self._return_sync_running.discard(node.id)

        _threads.spawn(heal)

    def _sync_with_node(self, node_id: str) -> int:
        """One anti-entropy pass scoped to fragments co-owned with one peer
        (the returning-node heal: full sync_holder per observer would be an
        O(N^2) RPC storm per return event)."""
        merged = 0
        for iname, idx in self.holder.indexes.items():
            for fname, field in idx.fields.items():
                for vname, view in field.views.items():
                    for shard in view.shards():
                        owners = {n.id for n in
                                  self.cluster.shard_nodes(iname, shard)}
                        if self.node_id in owners and node_id in owners:
                            merged += self._sync_fragment(
                                iname, fname, vname, shard)
        return merged

    # -- graceful drain + rejoin (docs/operations.md "Rolling restarts") ----

    def _handle_node_state(self, msg: dict) -> None:
        """A peer's lifecycle announcement: DRAINING routes around it
        immediately (no probe-timeout wait); READY is the rejoin — clear
        its marks and run the return-heal (hint replay first, anti-entropy
        only if hints were dropped)."""
        nid = msg.get("id")
        state = msg.get("state")
        if not nid or nid == self.node_id:
            return
        node = self.cluster.node_by_id(nid)
        if state == "DRAINING":
            if node is not None and not self.cluster.is_draining(nid):
                self.logger.printf(
                    "drain: peer %s is draining — routing around it", nid)
                self.cluster.mark_draining(nid)
                self.stats.count("drain/peerDraining")
                self.events.emit("peer.draining", peer=nid)
        elif state == "READY":
            was_away = (self.cluster.is_down(nid)
                        or self.cluster.is_draining(nid))
            self.cluster.mark_up(nid)
            self.cluster.clear_draining(nid)
            self._probe_failures.pop(nid, None)
            self._probe_successes.pop(nid, None)
            if was_away and node is not None:
                self.logger.printf(
                    "drain: peer %s rejoined — replaying hints", nid)
                self.events.emit("peer.rejoined", peer=nid)
                self._on_node_return(node)

    def request_drain(self, abort: bool = False,
                      timeout: Optional[float] = None) -> dict:
        """API hook for POST /cluster/drain (and the CLI's SIGTERM path):
        start the drain on a background thread — the endpoint answers
        immediately with the status document; operators poll /status
        (nodeState) for completion. abort=True cancels an in-progress
        drain and re-announces READY."""
        if abort:
            self.abort_drain()
            return self.drain_status()
        with self._drain_lock:
            if self._drain_thread is None or not self._drain_thread.is_alive():
                self._drain_abort.clear()
                self._drain_thread = _threads.spawn(
                    self.drain, timeout, name="pilosa-drain")
        return self.drain_status()

    def abort_drain(self) -> None:
        """Cancel a drain: stop shedding, re-announce READY so peers
        restore routing (an operator's change of heart must not leave the
        node half-out of the cluster)."""
        if not self.draining:
            return
        self._drain_abort.set()
        self.draining = False
        self.handler.draining = False
        me = self.cluster.local_node
        if me is not None and me.state == "DRAINING":
            me.state = "READY"
        self.logger.printf("drain: aborted — resuming service")
        self.events.emit("drain.abort")
        self.broadcast({"type": "node-state", "id": self.node_id,
                        "state": "READY"})

    def _drain_wait(self, cond, deadline: Optional[float]) -> bool:
        while not cond():
            if self._drain_abort.is_set() or self.closed:
                return False
            if deadline is not None and time.monotonic() >= deadline:
                return False
            time.sleep(0.01)
        return True

    def drain(self, timeout: Optional[float] = None) -> dict:
        """The graceful-drain sequence, run to completion (synchronous;
        request_drain wraps it in a thread):

          1. shed — new external queries get 503 + Retry-After +
             X-Pilosa-Shed-Reason: draining; internal RPCs (replica
             writes, fragment retrieval, stats, hint replay) keep working
          2. announce — peers mark this node DRAINING and route/hedge/
             coalesce around it immediately
          3. settle — in-flight external queries finish, then the device
             batchers and the network coalescer flush their queues
          4. persist — rank caches flush, every fragment with pending WAL
             ops (or volatile bulk loads) lands a final snapshot, so the
             restarted process replays nothing
        The node then reports nodeState=DRAINING until the process exits
        (the operator's signal to proceed with the restart)."""
        timeout = self.drain_timeout if timeout is None else timeout
        t0 = time.monotonic()
        deadline = t0 + timeout if timeout and timeout > 0 else None
        first = not self.draining
        if first:
            self.draining = True
            self.handler.draining = True
            me = self.cluster.local_node
            if me is not None:
                me.state = "DRAINING"
            self.stats.count("drain/started")
            self.logger.printf(
                "drain: shedding new external queries (timeout %.1fs)",
                timeout)
            self.events.emit("drain.start", timeoutSeconds=timeout)
            self.broadcast({"type": "node-state", "id": self.node_id,
                            "state": "DRAINING"})
        inflight_ok = self._drain_wait(
            lambda: self.handler.active_queries == 0, deadline)

        def queues_empty() -> bool:
            depth = 0
            for attr in ("batcher", "sum_batcher", "minmax_batcher",
                         "coalescer"):
                b = getattr(self.executor, attr, None)
                if b is not None:
                    depth += b.queue_depth()
            return depth == 0

        flushed_ok = self._drain_wait(queues_empty, deadline)
        if self._drain_abort.is_set():
            return self.drain_status()
        snapshotted = 0
        snapshot_errors = 0
        try:
            self.holder.flush_caches()
        except Exception as e:  # noqa: BLE001 — caches are rebuildable
            self.logger.printf("drain: cache flush failed: %s", e)
        for iname, fname, vname, shard, frag in \
                list(self.holder.walk_fragments()):
            dirty = (int(getattr(frag.storage, "op_n", 0) or 0) > 0
                     or getattr(frag, "_volatile", False))
            if not dirty:
                continue
            try:
                frag.snapshot()
                snapshotted += 1
            except (OSError, ValueError) as e:
                snapshot_errors += 1
                self.logger.printf(
                    "drain: final snapshot of %s/%s/%s/%d failed: %s",
                    iname, fname, vname, shard, e)
        self.drained = True
        self._drain_info = {
            "inflightDrained": inflight_ok,
            "queuesFlushed": flushed_ok,
            "snapshotted": snapshotted,
            "snapshotErrors": snapshot_errors,
            "durationSeconds": round(time.monotonic() - t0, 3),
        }
        self.stats.count("drain/completed")
        self.logger.printf(
            "drain: complete in %.2fs (inflight=%s queues=%s snapshots=%d)"
            " — safe to stop the process",
            self._drain_info["durationSeconds"], inflight_ok, flushed_ok,
            snapshotted)
        self.events.emit("drain.complete", snapshotted=snapshotted,
                         snapshotErrors=snapshot_errors,
                         durationSeconds=self._drain_info[
                             "durationSeconds"])
        return self.drain_status()

    def drain_status(self) -> dict:
        """The drain/* observability block (/debug/vars, /cluster/drain
        responses, unconditional /metrics gauges)."""
        out = {
            "draining": self.draining,
            "drained": self.drained,
            "shedQueries": self.handler.drain_sheds,
            "activeQueries": self.handler.active_queries,
            "timeoutSeconds": self.drain_timeout,
        }
        out.update(self._drain_info)
        return out

    # -- read-fenced rejoin --------------------------------------------------

    def _arm_read_fence(self) -> None:
        """Fence every local fragment's (index, shard) at startup when
        this node is (re)joining a multi-node cluster: the fragments may
        have missed writes while the process was away, and a fenced read
        routes to a peer replica until block checksums confirm parity
        (or a scrub heals the divergence). Single-node clusters and empty
        data dirs have nothing to fence."""
        if not self.cluster_hosts and not self.join:
            return
        keys = {(iname, shard) for iname, _f, _v, shard, _frag
                in self.holder.walk_fragments()}
        if not keys:
            return
        n = self.executor.fence_reads(keys)
        if not n:
            return
        self.stats.count("readFence/fenced", n)
        self.logger.printf(
            "rejoin: read-fenced %d shard(s) pending parity verification "
            "(reads route to replicas until hints replay or a checksum "
            "scrub confirms)", n)
        self.events.emit("fence.armed", shards=n)
        self._start_fence_worker()

    def _start_fence_worker(self) -> None:
        self._fence_wake.set()
        t = self._fence_thread
        if t is not None and t.is_alive():
            return
        self._fence_thread = _threads.spawn(self._fence_worker,
                                            name="pilosa-fence")

    def _fence_worker(self) -> None:
        deadline = time.monotonic() + self.rejoin_fence_timeout
        while not self.closed and self.executor.read_fence:
            try:
                self._verify_fence_pass()
            except Exception as e:  # noqa: BLE001 — a verify failure
                # (peer mid-restart, transient RPC) retries next tick
                self.logger.printf("rejoin: fence verify pass failed: %s", e)
            if not self.executor.read_fence:
                break
            if time.monotonic() >= deadline:
                # availability wins over an unverifiable fence (e.g. every
                # replica stayed down): lift it LOUDLY — the anti-entropy
                # scrubber remains the backstop for any real divergence
                with self.executor._fence_lock:
                    n = len(self.executor.read_fence)
                    self.executor.read_fence.clear()
                self.stats.count("readFence/expired", n)
                self.logger.warnf(
                    "rejoin: fence expired after %.0fs with %d shard(s) "
                    "unverified — serving local data; anti-entropy will "
                    "heal any divergence", self.rejoin_fence_timeout, n)
                self.events.emit("fence.expired", shards=n,
                                 timeoutSeconds=self.rejoin_fence_timeout)
                break
            self._fence_wake.wait(0.25)
            self._fence_wake.clear()

    def _verify_fence_pass(self) -> int:
        """One pass over fenced shards: compare every local fragment's
        block checksums with a live replica — parity lifts the fence;
        divergence runs the block-majority scrub for that fragment first
        (the 'block-checksum-verified scrub' of the rejoin contract).
        Shards with no reachable replica stay fenced for the next pass."""
        lifted = 0
        fence = sorted(self.executor.read_fence)
        for iname, shard in fence:
            idx = self.holder.index(iname)
            if idx is None:
                self.executor.unfence_reads((iname, shard))
                lifted += 1
                continue
            owners = self.cluster.shard_nodes(iname, shard)
            # a draining peer still serves verification reads; only
            # probe-dead peers are unusable
            peers = [n for n in owners
                     if n.id != self.node_id and n.uri
                     and not self.cluster.is_down(n.id)]
            if not peers:
                if len(owners) <= 1 or all(n.id == self.node_id
                                           for n in owners):
                    # no replica configured for this shard: nothing to
                    # verify against, and nobody else can serve it
                    self.executor.unfence_reads((iname, shard))
                    lifted += 1
                continue
            peer = peers[0]
            verified = True
            healed = False
            for fname, field in idx.fields.items():
                for vname, view in field.views.items():
                    frag = view.fragment(shard)
                    if frag is None:
                        continue
                    try:
                        remote = {b["id"]: b["checksum"]
                                  for b in self.client.fragment_blocks(
                                      peer.uri, iname, fname, vname, shard)}
                    except ClientError as e:
                        if e.code == "fragment-not-found":
                            remote = {}
                        else:
                            verified = False  # unreachable: retry later
                            break
                    local = {b: c.hex() for b, c in frag.blocks()}
                    if local != remote:
                        # diverged: heal NOW via the block-majority sync,
                        # then the fence lifts on the healed state
                        self._sync_fragment(iname, fname, vname, shard)
                        healed = True
                if not verified:
                    break
            if verified:
                self.executor.unfence_reads((iname, shard))
                lifted += 1
                self.stats.count("readFence/verified")
                self.events.emit("fence.lifted", index=iname, shard=shard,
                                 healed=healed)
                if healed:
                    self.stats.count("readFence/healed")
        return lifted

    # -- hint replay ---------------------------------------------------------

    def _retry_pending_hints(self) -> None:
        """Re-drive the return-heal for any LIVE member that still has a
        queued hint log (a previous replay failed mid-stream). Runs on
        the membership tick; single-flight per target via the
        _return_sync_running guard inside _on_node_return."""
        if not self.hints.pending_targets():
            return
        for n in list(self.cluster.nodes):
            if (n.id != self.node_id and n.uri
                    and not self.cluster.is_unavailable(n.id)
                    and self.hints.pending(n.id)):
                self._on_node_return(n)

    def replay_hints(self, node) -> tuple[int, int, bool]:
        """Stream queued hints to a returned peer in order, applying each
        as the idempotent remote write it originally was. Returns
        (replayed, dropped, complete) — see HintStore.replay."""
        def apply(doc: dict) -> None:
            self.client.query_proto(node.uri, doc["index"], doc["pql"],
                                    shards=doc.get("shards"), remote=True)

        replayed, dropped, complete = self.hints.replay(node.id, apply)
        if replayed or dropped:
            self.events.emit("hint.replay", target=node.id,
                             replayed=replayed, dropped=dropped,
                             complete=complete)
            self.logger.printf(
                "hints: replayed %d hint(s) to %s, %d dropped%s",
                replayed, node.id, dropped,
                "" if complete else " — anti-entropy will finish the heal")
        return replayed, dropped, complete

    def _xla_storm_event(self, family: str, new_keys: int,
                         sig_diff=None) -> None:
        """XLACounters storm hook: a recompile storm is a health incident
        the merged timeline must show (utils/telemetry.py). `sig_diff`
        is the old-vs-new dispatch signature diff — the leaf whose
        shape/dtype churned — so the timeline entry is actionable."""
        try:
            payload = {"family": family, "newShapes": int(new_keys)}
            if sig_diff:
                payload["signatureDiff"] = sig_diff
            self.events.emit("xla.recompile_storm", **payload)
        except Exception:  # noqa: BLE001 — recording must never break
            pass  # the dispatch path that tripped the storm

    def close(self) -> None:
        self.closed = True
        from pilosa_tpu.utils.events import unregister_crash_dump
        self.events.emit("node.stop")
        unregister_crash_dump(self.events)
        if self.gossip is not None:
            self.gossip.close()
        if self._bcast_thread is not None:
            self._bcast_queue.put(None)  # wake + stop the worker
            self._bcast_thread.join(timeout=2.0)
        if self._ae_timer is not None:
            self._ae_timer.cancel()
        if self._cache_flush_timer is not None:
            self._cache_flush_timer.cancel()
        if self._member_timer is not None:
            self._member_timer.cancel()
        if self._resize_watchdog is not None:
            self._resize_watchdog.cancel()
        self.telemetry.close()
        self.executor.shutdown()  # persistent fan-out / batch-exec pools
        self.runtime_monitor.close()
        self.diagnostics.close()
        if self.tracer.exporter is not None:
            self.tracer.exporter.close()  # final flush
        if self.trace_exporter is not None:
            self.trace_exporter.close()  # idempotent when it IS the
            # tracer's exporter (TraceExporter.close guards re-entry)
        self.http.close()
        self.holder.close()
        self.translate.close()

    @property
    def uri(self) -> str:
        return self.http.uri

    # -- cluster message dispatch (server.go:485-580) -----------------------

    def receive_message(self, msg: dict) -> None:
        mtype = msg.get("type")
        if mtype == "create-index":
            if self.holder.index(msg["index"]) is None:
                self.holder.create_index(msg["index"], keys=msg.get("keys", False),
                                         track_existence=msg.get("trackExistence", True))
        elif mtype == "delete-index":
            if self.holder.index(msg["index"]) is not None:
                self.holder.delete_index(msg["index"])
                self.executor.clear_caches()
        elif mtype == "create-field":
            idx = self.holder.index(msg["index"])
            if idx is not None and idx.field(msg["field"]) is None:
                idx.create_field(msg["field"], FieldOptions(**msg.get("options", {})))
        elif mtype == "delete-field":
            idx = self.holder.index(msg["index"])
            if idx is not None and idx.field(msg["field"]) is not None:
                idx.delete_field(msg["field"])
                self.executor.clear_caches()
        elif mtype == "create-shard":
            idx = self.holder.index(msg["index"])
            f = idx.field(msg["field"]) if idx else None
            if f is not None:
                f.add_available_shard(int(msg["shard"]), quiet=True)
        elif mtype == "node-join":
            node = Node.from_dict(msg["node"])
            self.cluster.add_node(node)
        elif mtype == "recalculate-caches":
            self.api.recalculate_caches()
        elif mtype == "set-coordinator":
            # SetCoordinatorMessage (broadcast.go; api.go SetCoordinator):
            # every node adopts the new coordinator or resize plans after a
            # failover would be driven by divergent coordinators. Adopt
            # unconditionally (the id may be a node we learn of next tick);
            # elect_coordinator reverts an id that never materializes, and
            # the probe loop's authority claim converges stragglers.
            if msg.get("id"):
                self.cluster.adopt_coordinator(msg["id"])
        elif mtype == "node-join-request":
            self._handle_join_request(Node.from_dict(msg["node"]))
        elif mtype == "node-leave-request":
            self._handle_leave_request(msg["id"])
        elif mtype == "resize-instruction":
            # async: fetching fragments over HTTP must not block the
            # coordinator's send (followResizeInstruction runs in a
            # goroutine, cluster.go:1251)
            _threads.spawn(self.follow_resize_instruction, msg)
        elif mtype == "resize-complete":
            self._handle_resize_complete(msg)
        elif mtype == "resize-abort":
            self._abort_request()
        elif mtype == "node-state":
            self._handle_node_state(msg)
        elif mtype == "hints-replayed":
            # a peer finished streaming its queued hints to us: wake the
            # rejoin verifier so the read fence lifts as soon as block
            # checksums confirm parity (instead of at the next poll tick)
            if msg.get("target") == self.node_id:
                self._fence_wake.set()
                if self.executor.read_fence:
                    self._start_fence_worker()
        elif mtype == "topology":
            self._apply_topology(msg["nodes"], msg.get("removed"))
        elif mtype == "cluster-state":
            self.cluster._set_state(msg["state"])
        elif mtype == "schema-sync":
            # coordinator push to a node returning from down: DDL broadcasts
            # it missed while broadcasts skipped it (_on_node_return)
            self._apply_schema(msg.get("schema", []))
            for iname, fields in msg.get("availableShards", {}).items():
                idx = self.holder.index(iname)
                if idx is None:
                    continue
                for fname, shards in fields.items():
                    f = idx.field(fname)
                    if f is not None:
                        for s in shards:
                            f.add_available_shard(int(s), quiet=True)
        else:
            raise ValueError(f"unknown cluster message type: {mtype}")

    def _on_shard_added(self, index_name: str, field_name: str, shard: int) -> None:
        """Announce newly-available shards so every node's shard set stays
        complete for query fan-out (CreateShardMessage, view.go:208-263).

        Async: this hook fires from inside the FIRST write to a new shard,
        so the announcement must not ride the write path — the reference
        sends it over gossip (SendAsync, broadcast.go:30); here it goes
        through the broadcast queue and the write returns immediately."""
        self.broadcast_async({"type": "create-shard", "index": index_name,
                              "field": field_name, "shard": shard})

    def _peer_uris(self) -> list[str]:
        return [n.uri for n in self.cluster.nodes
                if n.id != self.node_id and n.uri
                and not self.cluster.is_down(n.id)]

    def broadcast(self, msg: dict) -> None:
        """SendSync: POST to every peer CONCURRENTLY and wait for all
        (server.go:582-604) — total latency is the slowest peer, not the
        sum. Failed peers are skipped; they converge via anti-entropy or
        the return-heal schema sync."""
        uris = self._peer_uris()
        if not uris:
            return
        if len(uris) == 1:  # no thread overhead for the 2-node case
            try:
                self.client.send_message(uris[0], msg)
            except ClientError:
                pass
            return
        threads = [_threads.spawn(self._send_quiet, u, msg)
                   for u in uris]
        for t in threads:
            t.join()

    def _send_quiet(self, uri: str, msg: dict) -> None:
        try:
            self.client.send_message(uri, msg)
        except ClientError:
            pass  # peers converge via anti-entropy

    # budget for the pre-ack create-shard announcement of a shard-CREATING
    # Set: healthy peers answer within ~1 RTT; a hung peer costs at most
    # this (once per new shard — its daemon sender keeps trying after the
    # ack, so delivery is attempted either way)
    ANNOUNCE_SHARD_BUDGET_S = 0.5

    def _announce_shard_bounded(self, iname: str, fname: str,
                                shard: int) -> None:
        """Concurrent create-shard broadcast with a bounded wait, run
        BEFORE a shard-creating Set() acks: an immediately-following read
        through any live node must not race the async announcement queue
        (PR-1 made the per-write announcement async precisely so a hung
        peer adds no write latency — that holds for the common case; only
        the once-per-shard-lifetime CREATING write pays a bounded wait)."""
        msg = {"type": "create-shard", "index": iname, "field": fname,
               "shard": shard}
        uris = self._peer_uris()
        if not uris:
            return
        threads = [_threads.spawn(self._send_quiet, u, msg)
                   for u in uris]
        deadline = time.monotonic() + self.ANNOUNCE_SHARD_BUDGET_S
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))

    # per-peer async queue bound: a long-hung peer must not grow its queue
    # without limit — dropped messages converge via anti-entropy / the
    # return-heal schema sync
    BCAST_PEER_QUEUE_MAX = 1024

    def broadcast_async(self, msg: dict) -> None:
        """SendAsync (broadcast.go:30-36): enqueue and return — delivery
        happens on per-peer sender workers with bounded retry; after that,
        anti-entropy converges. The caller (a write path) never blocks on
        a peer, and a hung peer head-of-line-blocks ONLY its own queue —
        announcements keep flowing to healthy peers."""
        if self.closed:
            return
        self._bcast_queue.put(msg)

    def _bcast_worker(self) -> None:
        """Fans the async broadcast queue out to one sender thread + queue
        per peer URI (created lazily; torn down on close and when the peer
        leaves the cluster — a departed peer must not keep a retrying
        sender alive for the rest of the server's life)."""
        import queue as _queue

        peer_queues: dict[str, "_queue.Queue"] = {}

        def peer_sender(uri: str, q: "_queue.Queue") -> None:
            while True:
                m = q.get()
                if m is None:
                    return
                try:
                    self.client.send_message(uri, m)
                except ClientError:
                    if self.closed:
                        continue
                    time.sleep(0.2)  # one retry, then let AE converge
                    self._send_quiet(uri, m)

        while True:
            msg = self._bcast_queue.get()
            if msg is None:  # close() sentinel: stop the peer senders too
                for q in peer_queues.values():
                    q.put(None)
                return
            # retire senders only for peers that LEFT the cluster; a
            # temporarily-down peer keeps its queue (it is just skipped
            # by _peer_uris until liveness marks it back up)
            member = {n.uri for n in self.cluster.nodes
                      if n.id != self.node_id and n.uri}
            for uri in [u for u in peer_queues if u not in member]:
                peer_queues.pop(uri).put(None)
            for uri in self._peer_uris():
                q = peer_queues.get(uri)
                if q is None:
                    q = peer_queues[uri] = _queue.Queue()
                    _threads.spawn(peer_sender, uri, q)
                if q.qsize() < self.BCAST_PEER_QUEUE_MAX:
                    q.put(msg)
                else:
                    self._bcast_dropped += 1

    # -- resize engine (cluster.go:1150-1515) -------------------------------

    def request_join(self) -> None:
        """Announce this node to the first answering seed; the request is
        forwarded to the coordinator which runs a resize job for us."""
        me = {"id": self.node_id, "uri": self.http.uri}
        for huri in self.cluster_hosts:
            if huri == self.http.uri:
                continue
            try:
                self.client.send_message(huri, {"type": "node-join-request",
                                                "node": me})
                return
            except ClientError:
                continue

    def _resize_request(self, event: str, node: Node):
        """API hook: route a membership change through the coordinator
        (api.RemoveNode → coordinator resize, api.go:1092). Raises
        ValueError so a refusal (e.g. too few replicas) surfaces to the
        operator's HTTP request instead of vanishing in forwarding."""
        if event != "leave":
            raise ValueError(f"unsupported resize event: {event}")
        if not self.cluster.is_coordinator():
            coord = self.cluster.node_by_id(self.cluster.coordinator_id)
            if coord is None or not coord.uri:
                raise ValueError("no coordinator available")
            try:
                self.client.send_message(coord.uri, {
                    "type": "node-leave-request", "id": node.id})
            except ClientError as e:
                raise ValueError(f"remove-node refused by coordinator: {e}")
            return None
        self._handle_leave_request(node.id)
        return self.cluster.active_job

    def _abort_request(self) -> None:
        """API hook for /cluster/resize/abort: cancel the coordinator's
        active job, then un-gate peers."""
        if not self.cluster.is_coordinator():
            coord = self.cluster.node_by_id(self.cluster.coordinator_id)
            if coord is None or not coord.uri:
                raise ValueError("no coordinator available")
            try:
                self.client.send_message(coord.uri, {"type": "resize-abort"})
            except ClientError as e:
                raise ValueError(f"abort refused by coordinator: {e}")
            return
        with self._resize_lock:
            self.cluster.abort_resize()
        if self._resize_watchdog is not None:
            self._resize_watchdog.cancel()
        self._resize_aborted()

    def _forward_to_coordinator(self, msg: dict) -> bool:
        coord = self.cluster.node_by_id(self.cluster.coordinator_id)
        if coord is None or coord.id == self.node_id or not coord.uri:
            return False
        try:
            self.client.send_message(coord.uri, msg)
            return True
        except ClientError:
            return False

    def _handle_join_request(self, node: Node) -> None:
        if node.id == self.node_id:
            return
        # a previously-removed node may rejoin: clear its tombstone
        self._removed_ids.discard(node.id)
        if self.cluster.node_by_id(node.id) is not None:
            # already a member (e.g. re-knock after a lost topology message):
            # resend the topology directly so the requester converges
            try:
                self.client.send_message(node.uri, {
                    "type": "topology",
                    "nodes": [n.to_dict() for n in self.cluster.nodes],
                    "removed": sorted(self._removed_ids)})
            except ClientError:
                pass
            return
        if not self.cluster.is_coordinator():
            self._forward_to_coordinator({"type": "node-join-request",
                                          "node": node.to_dict()})
            return
        with self._resize_lock:
            if self.cluster.state == STATE_RESIZING \
                    or self.cluster.active_job is not None:
                if all(n.id != node.id for _, n in self._pending_resizes):
                    self._pending_resizes.append(("join", node))
                return
            job = self.cluster.node_join(node)
        if job is not None:
            self._broadcast_state(STATE_RESIZING)
            self._distribute_resize(job)

    def _handle_leave_request(self, node_id: str) -> None:
        if not self.cluster.is_coordinator():
            self._forward_to_coordinator({"type": "node-leave-request",
                                          "id": node_id})
            return
        with self._resize_lock:
            victim = self.cluster.node_by_id(node_id)
            if self.cluster.state == STATE_RESIZING \
                    or self.cluster.active_job is not None:
                if victim is not None:
                    self._pending_resizes.append(("leave", victim))
                return
            job = self.cluster.node_leave(node_id)
        if job is not None:
            self._broadcast_state(STATE_RESIZING)
            self._distribute_resize(job)
        else:
            # degraded removal (too few nodes to rebuild replicas) — the
            # membership already changed; converge peers now
            self._removed_ids.add(node_id)
            self.hints.drop_target(node_id)  # never deliverable again
            self._broadcast_topology()
            # tell the victim it is out so it stops acting as a member
            if victim is not None and victim.uri:
                try:
                    self.client.send_message(victim.uri, {
                        "type": "topology",
                        "nodes": [n.to_dict() for n in self.cluster.nodes],
                        "removed": sorted(self._removed_ids)})
                except ClientError:
                    pass
            self.clean_holder()

    def _distribute_resize(self, job: ResizeJob) -> None:
        """Send each node its fetch instructions (distributeResizeInstructions,
        cluster.go:1499). Includes the schema so a joining node can apply DDL
        before loading fragments (followResizeInstruction applies schema
        first, cluster.go:1251-1340)."""
        uri_by_id = {n.id: n.uri for n in self.cluster.nodes}
        if job.node is not None:
            uri_by_id.setdefault(job.node.id, job.node.uri)
        self.events.emit("resize.start", job=job.id, event=job.event,
                         node=job.node_id)
        self._arm_watchdog(job.id)
        schema = self.holder.schema()
        # cluster-wide available-shards state rides along so a joining node
        # fans queries out over ALL shards, not just the ones it received
        # (the reference ships this in NodeStatus on join, server.go:485-580
        # → holder merge of remote available shards)
        avail = {
            iname: {fname: [int(s) for s in f.available_shards.slice()]
                    for fname, f in idx.fields.items()}
            for iname, idx in self.holder.indexes.items()
        }
        for target, sources in job.instructions.items():
            msg = {
                "type": "resize-instruction",
                "job": job.id,
                "coordinator": self.node_id,
                "coordinatorURI": self.http.uri,
                "schema": schema,
                "availableShards": avail,
                "sources": [dict(s.to_dict(),
                                 fromURI=uri_by_id.get(s.from_node, ""))
                            for s in sources],
            }
            if target == self.node_id:
                _threads.spawn(self.follow_resize_instruction, msg)
            else:
                try:
                    self.client.send_message(uri_by_id[target], msg)
                except ClientError as e:
                    self.logger.printf("resize: instruction undeliverable to "
                                       "%s: %s — aborting job", target, e)
                    with self._resize_lock:
                        self.cluster.abort_resize()
                    self._resize_aborted()
                    return

    def follow_resize_instruction(self, msg: dict) -> None:
        """Apply schema, stream each source fragment from its donor, ack the
        coordinator (followResizeInstruction, cluster.go:1251-1393)."""
        done = {"type": "resize-complete", "job": msg["job"],
                "node": self.node_id}
        try:
            self._apply_schema(msg.get("schema", []))
            for iname, fields in msg.get("availableShards", {}).items():
                idx = self.holder.index(iname)
                if idx is None:
                    continue
                for fname, shards in fields.items():
                    f = idx.field(fname)
                    if f is not None:
                        for s in shards:
                            f.add_available_shard(int(s), quiet=True)
            for src in msg.get("sources", []):
                idx = self.holder.index(src["index"])
                f = idx.field(src["field"]) if idx is not None else None
                if f is None:
                    raise ClientError(
                        f"schema missing for {src['index']}/{src['field']}")
                # the donor enumerates which views hold this shard; stream
                # each (fragment tar-walk analog, fragment.go:1823-1998)
                views = self.client.fragment_views(
                    src["fromURI"], src["index"], src["field"], src["shard"])
                for vname in views:
                    try:
                        data = self.client.retrieve_shard(
                            src["fromURI"], src["index"], src["field"],
                            vname, src["shard"])
                    except ClientError as e:
                        if e.status == 404:
                            continue  # raced away; anti-entropy will heal
                        raise
                    view = f.create_view_if_not_exists(vname)
                    frag = view.create_fragment_if_not_exists(src["shard"])
                    frag.import_roaring(data)
                    view.refresh_rank_cache(src["shard"])
                f.add_available_shard(src["shard"], quiet=True)
        except (ClientError, ValueError, OSError) as e:
            done["error"] = str(e)
        if msg.get("coordinator") == self.node_id:
            self._handle_resize_complete(done)
        else:
            # the ack must arrive or the cluster wedges in RESIZING until
            # the watchdog aborts — retry transient failures
            import time as _time
            for attempt in range(5):
                try:
                    self.client.send_message(msg["coordinatorURI"], done)
                    break
                except ClientError:
                    _time.sleep(0.5 * (attempt + 1))

    def _apply_schema(self, schema: list[dict]) -> None:
        """Create any indexes/fields we don't have yet from schema dicts
        (the resize instruction's Schema payload)."""
        for idx_d in schema:
            opts = idx_d.get("options", {})
            idx = self.holder.create_index_if_not_exists(
                idx_d["name"], keys=opts.get("keys", False),
                track_existence=opts.get("trackExistence", True))
            for fd in idx_d.get("fields", []):
                o = fd.get("options", {})
                idx.create_field_if_not_exists(fd["name"], FieldOptions(
                    type=o.get("type", "set"),
                    cache_type=o.get("cacheType", "ranked"),
                    cache_size=o.get("cacheSize", 50000),
                    min=o.get("min", 0),
                    max=o.get("max", 0),
                    time_quantum=o.get("timeQuantum", ""),
                    keys=o.get("keys", False)))

    def _handle_resize_complete(self, msg: dict) -> None:
        with self._resize_lock:
            job = self.cluster.active_job
            if job is None or job.id != msg.get("job"):
                return
            if msg.get("error"):
                self.logger.printf("resize: job %s failed on %s: %s",
                                   job.id, msg.get("node"), msg["error"])
                self.cluster.abort_resize()
                aborted, finished = True, False
            else:
                aborted = False
                self.cluster.complete_resize(job, msg["node"])
                # done when the job cleared — the post-resize state may be
                # DEGRADED if an unrelated node is probe-marked down;
                # completion steps (topology broadcast, watchdog cancel,
                # pending-resize drain) must still run
                finished = self.cluster.active_job is None
                if finished and job.event == EVENT_LEAVE:
                    self._removed_ids.add(job.node_id)
        if aborted:
            if self._resize_watchdog is not None:
                self._resize_watchdog.cancel()
            self._resize_aborted()
            return
        if not finished:
            return
        if self._resize_watchdog is not None:
            self._resize_watchdog.cancel()
        self.events.emit("resize.complete", job=job.id, event=job.event,
                         node=job.node_id)
        if job.event == EVENT_LEAVE:
            # the departed node's queued hints are never deliverable
            self.hints.drop_target(job.node_id)
        self._broadcast_topology()
        # tell the departed node it is out so it stops acting as a member
        if job.event == EVENT_LEAVE and job.node is not None and job.node.uri:
            try:
                self.client.send_message(job.node.uri, {
                    "type": "topology",
                    "nodes": [n.to_dict() for n in self.cluster.nodes],
                    "removed": sorted(self._removed_ids)})
            except ClientError:
                pass
        self.clean_holder()
        self._drain_pending_resizes()

    def _arm_watchdog(self, job_id: str) -> None:
        if self._resize_watchdog is not None:
            self._resize_watchdog.cancel()
        if self.resize_timeout <= 0:
            return
        t = _threads.ctx_timer(self.resize_timeout, self._watchdog_fire,
                               args=(job_id,))
        t.start()
        self._resize_watchdog = t

    def _watchdog_fire(self, job_id: str) -> None:
        with self._resize_lock:
            job = self.cluster.active_job
            if job is None or job.id != job_id:
                return
            self.logger.printf("resize: job %s timed out after %.0fs — "
                               "aborting", job_id, self.resize_timeout)
            self.cluster.abort_resize()
        self._resize_aborted()

    def _broadcast_state(self, state: str) -> None:
        """Propagate the cluster state to every member so e.g. RESIZING
        blocks writes cluster-wide, not just on the coordinator (the
        reference's ClusterStatus broadcast, server.go:485-580)."""
        self.broadcast({"type": "cluster-state", "state": state})

    def _resize_aborted(self) -> None:
        """Un-wedge peers stuck in RESIZING, then try the next queued
        membership event (an aborted join self-heals by re-knocking)."""
        self.events.emit("resize.abort")
        self._broadcast_state(self.cluster.state)
        self._drain_pending_resizes()

    def _drain_pending_resizes(self) -> None:
        """Dispatch queued membership events one at a time (listenForJoins,
        cluster.go:1095-1148). A queued event that became invalid (e.g. a
        leave now refused for lack of replicas) is logged and skipped so it
        cannot wedge the rest of the queue."""
        while True:
            with self._resize_lock:
                if not self._pending_resizes:
                    return
                event, node = self._pending_resizes.pop(0)
            try:
                if event == "join":
                    self._handle_join_request(node)
                else:
                    self._handle_leave_request(node.id)
                with self._resize_lock:
                    started = self.cluster.active_job is not None
                if started:
                    return  # a job is running; its completion drains next
                # event completed synchronously (degraded removal,
                # already-member join) — keep draining
            except ValueError as e:
                self.logger.printf("resize: dropping queued %s(%s): %s",
                                   event, node.id, e)

    def _broadcast_topology(self) -> None:
        """Push the final membership to every node (the coordinator's
        cluster-status broadcast after a resize completes)."""
        nodes_d = [n.to_dict() for n in self.cluster.nodes]
        self.cluster.elect_coordinator()
        msg = {"type": "topology", "nodes": nodes_d,
               "removed": sorted(self._removed_ids)}
        for n in self.cluster.nodes:
            if n.id == self.node_id or not n.uri:
                continue
            try:
                self.client.send_message(n.uri, msg)
            except ClientError:
                pass

    def _apply_topology(self, nodes_d: list[dict],
                        removed: Optional[list[str]] = None) -> None:
        # the coordinator's removed-set is authoritative: REPLACE (a union
        # would tombstone a removed-then-rejoined node on peers forever,
        # silently diverging membership)
        if removed is not None:
            self._removed_ids = set(removed)
        if self.node_id in self._removed_ids:
            # we were removed: become a standalone node and stop merging
            # ourselves back into the cluster (operator shuts us down)
            self._left = True
            me = Node(id=self.node_id, uri=self.http.uri)
            self.cluster.set_static([me])
            self.cluster.coordinator_id = self.node_id
            return
        nodes = [Node.from_dict(d) for d in nodes_d
                 if d["id"] not in self._removed_ids]
        before = {n.id for n in self.cluster.nodes}
        self.cluster.set_static(nodes)
        self.cluster.elect_coordinator()
        after = {n.id for n in self.cluster.nodes}
        if after != before:
            self.events.emit("topology.change",
                             nodes=sorted(after),
                             added=sorted(after - before),
                             removed=sorted(before - after))
        self.clean_holder()

    def clean_holder(self) -> int:
        """Drop fragments this node no longer owns after a resize
        (holderCleaner, holder.go:855-906). Returns fragments dropped."""
        dropped = 0
        for iname, idx in self.holder.indexes.items():
            for f in idx.fields.values():
                for view in f.views.values():
                    for shard in view.shards():
                        if not self.cluster.owns_shard(self.node_id, iname,
                                                       shard):
                            view.delete_fragment(shard)
                            dropped += 1
        return dropped

    # -- fleet telemetry (utils/telemetry.py; docs/operations.md) -----------

    # time-series tail shipped inside the node stats document — enough for
    # the dashboard's fleet sparklines without re-fetching every ring
    STATS_TAIL_SAMPLES = 60

    def sample_gauges(self) -> dict:
        """One telemetry tick (the sampler's source): instantaneous gauges
        plus window rates derived from cumulative counters since the
        previous tick. Keys are dotted series names; the ring stores the
        returned dict verbatim."""
        from pilosa_tpu.utils import telemetry as _telemetry
        from pilosa_tpu.utils.diagnostics import process_rss

        now = time.monotonic()
        g: dict = {}
        raw: dict = {}
        ex = self.executor
        res = getattr(ex, "residency", None)
        if res is not None:
            snap = res.snapshot()
            g["residency.bytes"] = float(snap["bytes"])
            g["residency.budget"] = float(res.budget)
            g["residency.entries"] = float(snap["entries"])
            raw["residency.hits"] = snap["hits"]
            raw["residency.lookups"] = snap["hits"] + snap["misses"]
            raw["residency.evictions"] = snap["evictions"]
        pc = getattr(ex, "plan_cache", None)
        if pc is not None:
            cs = pc.snapshot()
            g["plancache.bytes"] = float(cs["bytes"])
            g["plancache.entries"] = float(cs["entries"])
            raw["plancache.hits"] = cs["hits"]
            raw["plancache.lookups"] = cs["hits"] + cs["misses"]
            raw["plancache.evictions"] = cs["evictions"]
        pl = getattr(ex, "planner", None)
        if pl is not None:
            ps = pl.snapshot()
            raw["planner.reorders"] = ps["reorders"]
            raw["planner.pushdowns"] = ps["pushdowns"]
            raw["planner.short_circuits"] = ps["shortCircuits"]
        # fragment heat map: tick the tracker's summary ring (the
        # /debug/heat since-cursor feed rides the sampler's clock) and
        # publish the aggregate temperature gauges the dashboard's
        # skew sparkline reads
        tracker = getattr(ex, "heat", None)
        if tracker is not None:
            hsum = tracker.sample_tick()
            g["heat.hot_fragments"] = float(hsum["hotFragments"])
            g["heat.skew"] = float(hsum["skew"])
            g["heat.tracker_entries"] = float(hsum["trackerEntries"])
        # per-principal usage ledger: tick its delta ring (the
        # /debug/usage since-cursor feed rides the sampler's clock) and
        # sample fleet-level gauges; SLO burn rates per objective
        usage = getattr(self.api, "usage_ledger", None)
        if usage is not None:
            usage.sample_tick()
            ut = usage.totals()
            g["usage.tracked_principals"] = float(
                usage.snapshot(top=1)["trackedPrincipals"])
            raw["usage.queries"] = ut["queries"]
            raw["usage.device_ms"] = ut["deviceMs"]
            raw["usage.rpc_bytes"] = ut["rpcBytes"]
        slo = getattr(self.api, "slo", None)
        if slo is not None:
            worst = 0.0
            for name, ob in slo.evaluate().items():
                g[f"slo.{name}.burn_short"] = ob["burnShort"]
                g[f"slo.{name}.burn_long"] = ob["burnLong"]
                worst = max(worst, {"green": 0.0, "yellow": 1.0,
                                    "red": 2.0}[ob["status"]])
            g["slo.worst"] = worst
        # QoS plane: admission/shed/throttle totals (windowed to rates
        # below) + the live wait estimate admission decides against
        qp = getattr(self, "qos", None)
        if qp is not None:
            qt = qp.totals()
            raw["qos.admitted"] = qt["admitted"]
            raw["qos.shed"] = qt["shed"] + qt["wouldShed"]
            raw["qos.throttled"] = qt["throttled"]
            g["qos.estimated_wait_ms"] = round(qp.estimated_wait_ms(), 3)
        depth = 0
        for attr in ("batcher", "sum_batcher", "minmax_batcher"):
            b = getattr(ex, attr, None)
            if b is None:
                continue
            bs = b.snapshot()
            depth += bs["queue_depth"]
            raw["batcher.wait_ms_total"] = raw.get(
                "batcher.wait_ms_total", 0.0) + bs["wait_ms_total"]
            raw["batcher.waited"] = raw.get(
                "batcher.waited", 0) + bs["waited"]
            raw["batcher.batches"] = raw.get(
                "batcher.batches", 0) + bs["batches"]
        g["batcher.queue_depth"] = float(depth)
        ps = ex.fanout_pool_stats()
        g["fanout.pool_size"] = float(ps["size"])
        g["fanout.threads"] = float(ps["threads"])
        g["fanout.queued"] = float(ps["queued"])
        # occupancy approximation: threads are created on demand and
        # queued work means every thread is busy
        g["fanout.utilization"] = min(
            1.0, ps["threads"] / max(1, ps["size"])) if not ps["queued"] \
            else 1.0
        raw["hedges.fired"] = getattr(ex, "hedges_fired", 0)
        raw["hedges.won"] = getattr(ex, "hedges_won", 0)
        # ICI slice-local serving: route decision rates + the windowed
        # slice-local share (the dashboard's sparkline of how much of the
        # distributed read mix is escaping the HTTP plane)
        isnap = ex.ici_snapshot()
        raw["ici.slice_local"] = isnap["sliceLocal"]
        raw["ici.cross_slice"] = isnap["crossSlice"]
        raw["ici.fallback"] = isnap["fallback"]
        raw["ici.routed"] = (isnap["sliceLocal"] + isnap["crossSlice"]
                             + isnap["fallback"])
        # hybrid sparse/dense containers: live sparse occupancy gauges
        # plus the windowed sparse share of row-leaf uploads (the
        # dashboard's sparkline of how much of the leaf traffic escapes
        # the dense-plane cost)
        hy = ex.hybrid_snapshot()
        g["hybrid.sparse_bytes"] = float(hy["residentSparseBytes"])
        g["hybrid.sparse_leaves"] = float(hy["residentSparseLeaves"])
        g["hybrid.run_bytes"] = float(hy["residentRunBytes"])
        g["hybrid.run_leaves"] = float(hy["residentRunLeaves"])
        raw["hybrid.sparse_uploads"] = hy["sparseUploads"]
        raw["hybrid.run_uploads"] = hy["runUploads"]
        raw["hybrid.row_uploads"] = (hy["sparseUploads"]
                                     + hy["runUploads"]
                                     + hy["denseUploads"])
        # streaming ingest: coalesced write plane — mutation throughput
        # plus the WAL group-commit ratio (mutations per fsync-able WAL
        # append, the headline fsync-reduction evidence)
        ing = ex.ingest_snapshot()
        raw["ingest.mutations"] = ing["mutations"]
        raw["ingest.batches"] = ing["appliedBatches"]
        raw["ingest.wal_appends"] = ing["walAppends"]
        g["ingest.queue_depth"] = float(ing["queue_depth"])
        # hinted handoff + drain lifecycle + rejoin read fence
        hsnap = self.hints.snapshot()
        g["hints.pending_bytes"] = float(hsnap["pendingBytes"])
        g["hints.pending_targets"] = float(len(hsnap["pendingTargets"]))
        raw["hints.queued"] = hsnap["queued"]
        raw["hints.replayed"] = hsnap["replayed"]
        raw["hints.dropped"] = hsnap["dropped"]
        g["drain.draining"] = 1.0 if self.draining else 0.0
        raw["drain.shed"] = self.handler.drain_sheds
        esnap = self.events.snapshot()
        raw["events.emitted"] = esnap["emitted"]
        g["events.retained"] = float(sum(esnap["retained"].values()))
        g["fence.fenced_shards"] = float(
            ex.fence_snapshot()["fencedShards"])
        wal_bytes = 0
        wal_ops = 0
        poisoned = 0
        for _i, _f, _v, _s, frag in self.holder.walk_fragments():
            try:
                wal_bytes += os.path.getsize(frag.path)
            except (OSError, TypeError):
                pass
            wal_ops += int(getattr(frag.storage, "op_n", 0) or 0)
            if getattr(frag.storage, "wal_poisoned", False):
                poisoned += 1
        damaged = self.holder.damaged_fragments()
        g["wal.bytes"] = float(wal_bytes)
        g["wal.ops"] = float(wal_ops)
        g["wal.poisoned_fragments"] = float(poisoned)
        g["wal.damaged_fragments"] = float(len(damaged))
        g["wal.needs_rebuild"] = float(
            sum(1 for d in damaged if d["needsRebuild"]))
        g["process.rss_bytes"] = float(process_rss())
        g["process.threads"] = float(threading.active_count())
        raw["http.errors"] = float(self.handler.errors_5xx)
        xs = _telemetry.xla.snapshot()
        g["xla.compiles"] = float(xs["compiles"])
        g["xla.cached_dispatches"] = float(xs["cachedDispatches"])
        g["xla.storms"] = float(xs["storms"])
        raw["xla.compiles"] = xs["compiles"]
        for dev in _telemetry.device_memory_stats():
            ms = dev["memoryStats"]
            if ms and "bytes_in_use" in ms:
                # first device with a reporting backend (TPU HBM);
                # CPU backends return null stats and are skipped —
                # the dashboard's HBM sparkline degrades to absent
                g["device.bytes_in_use"] = float(ms["bytes_in_use"])
                g["device.hbm_bytes_in_use"] = float(ms["bytes_in_use"])
                g["device.hbm_limit"] = float(ms.get("bytes_limit", 0))
                break
        # device kernel attribution (telemetry.KernelStats): dispatch and
        # h2d throughput plus windowed per-dispatch wall / queue-wait
        ks = _telemetry.kernels.totals()
        raw["kernels.dispatches"] = ks["dispatches"]
        raw["kernels.dispatch_ms"] = ks["dispatch_ms_total"]
        raw["kernels.wait_ms"] = ks["wait_ms_total"]
        raw["kernels.waited"] = ks["waited"]
        raw["kernels.h2d_bytes"] = ks["h2d_bytes"]

        prev, prev_t = self._telemetry_prev
        dt = max(1e-9, now - prev_t)

        def rate(name: str) -> float:
            if prev is None or name not in prev or name not in raw:
                return 0.0
            return max(0.0, (raw[name] - prev[name]) / dt)

        if res is not None:
            if prev is not None:
                dlook = raw["residency.lookups"] - prev.get(
                    "residency.lookups", 0)
                dhits = raw["residency.hits"] - prev.get("residency.hits", 0)
                if dlook > 0:
                    self._last_hit_rate = max(0.0, dhits) / dlook
            g["residency.hit_rate"] = self._last_hit_rate
            g["residency.evictions_per_s"] = rate("residency.evictions")
        if pc is not None:
            # WINDOWED plan-cache hit rate, same rationale as residency's:
            # a lifetime ratio hides a cache that just started thrashing
            if prev is not None:
                dlook = raw["plancache.lookups"] - prev.get(
                    "plancache.lookups", 0)
                dhits = raw["plancache.hits"] - prev.get(
                    "plancache.hits", 0)
                if dlook > 0:
                    self._last_plan_hit_rate = max(0.0, dhits) / dlook
            g["plancache.hit_rate"] = self._last_plan_hit_rate
            g["plancache.evictions_per_s"] = rate("plancache.evictions")
        if pl is not None:
            g["planner.reorders_per_s"] = rate("planner.reorders")
            g["planner.pushdowns_per_s"] = rate("planner.pushdowns")
            g["planner.short_circuits_per_s"] = rate(
                "planner.short_circuits")
        if prev is not None:
            dwaited = raw.get("batcher.waited", 0) - prev.get(
                "batcher.waited", 0)
            dwait = raw.get("batcher.wait_ms_total", 0.0) - prev.get(
                "batcher.wait_ms_total", 0.0)
            g["batcher.avg_wait_ms"] = (max(0.0, dwait) / dwaited
                                        if dwaited > 0 else 0.0)
        g["batcher.batches_per_s"] = rate("batcher.batches")
        g["qos.admitted_per_s"] = rate("qos.admitted")
        g["qos.shed_per_s"] = rate("qos.shed")
        g["qos.throttled_per_s"] = rate("qos.throttled")
        g["ingest.sets_per_s"] = rate("ingest.mutations")
        g["ingest.batches_per_s"] = rate("ingest.batches")
        g["ingest.wal_appends_per_s"] = rate("ingest.wal_appends")
        g["hints.queued_per_s"] = rate("hints.queued")
        g["hints.replayed_per_s"] = rate("hints.replayed")
        g["hints.dropped_per_s"] = rate("hints.dropped")
        g["drain.shed_per_s"] = rate("drain.shed")
        g["events.emitted_per_s"] = rate("events.emitted")
        g["hedges.fired_per_s"] = rate("hedges.fired")
        g["ici.slice_local_per_s"] = rate("ici.slice_local")
        g["ici.cross_slice_per_s"] = rate("ici.cross_slice")
        if prev is not None:
            drouted = raw["ici.routed"] - prev.get("ici.routed", 0)
            dlocal = raw["ici.slice_local"] - prev.get(
                "ici.slice_local", 0)
            if drouted > 0:
                self._last_ici_share = max(0.0, dlocal) / drouted
        g["ici.slice_local_share"] = self._last_ici_share
        if prev is not None:
            dups = raw["hybrid.row_uploads"] - prev.get(
                "hybrid.row_uploads", 0)
            dsp = raw["hybrid.sparse_uploads"] - prev.get(
                "hybrid.sparse_uploads", 0)
            drn = raw["hybrid.run_uploads"] - prev.get(
                "hybrid.run_uploads", 0)
            if dups > 0:
                self._last_hybrid_share = max(0.0, dsp) / dups
                self._last_hybrid_run_share = max(0.0, drn) / dups
        g["hybrid.sparse_share"] = self._last_hybrid_share
        g["hybrid.run_share"] = self._last_hybrid_run_share
        g["http.errors_per_s"] = rate("http.errors")
        g["xla.compiles_per_s"] = rate("xla.compiles")
        g["kernels.dispatches_per_s"] = rate("kernels.dispatches")
        g["kernels.h2d_bytes_per_s"] = rate("kernels.h2d_bytes")
        # windowed per-dispatch dispatch wall and per-request queue wait
        # (dashboard sparklines): delta-over-delta, same discipline as
        # batcher.avg_wait_ms above
        g["kernels.avg_dispatch_ms"] = 0.0
        g["kernels.avg_wait_ms"] = 0.0
        if prev is not None:
            dd = raw["kernels.dispatches"] - prev.get(
                "kernels.dispatches", 0)
            dms = raw["kernels.dispatch_ms"] - prev.get(
                "kernels.dispatch_ms", 0.0)
            if dd > 0:
                g["kernels.avg_dispatch_ms"] = max(0.0, dms) / dd
            dw = raw["kernels.waited"] - prev.get("kernels.waited", 0)
            dwm = raw["kernels.wait_ms"] - prev.get("kernels.wait_ms", 0.0)
            if dw > 0:
                g["kernels.avg_wait_ms"] = max(0.0, dwm) / dw
        g["usage.queries_per_s"] = rate("usage.queries")
        g["usage.device_ms_per_s"] = rate("usage.device_ms")
        g["usage.rpc_bytes_per_s"] = rate("usage.rpc_bytes")
        self._telemetry_prev = (raw, now)
        # health-transition events: the sampler is the one periodic
        # observer of the shared health score, so a green->yellow->red
        # (or recovery) edge lands on the flight-recorder timeline with
        # its reasons exactly once per transition
        health = self.node_health()
        if self._last_health is not None \
                and health["score"] != self._last_health:
            self.events.emit("health.transition",
                             fromScore=self._last_health,
                             toScore=health["score"],
                             reasons=health["reasons"][:5])
        self._last_health = health["score"]
        return g

    def _health_inputs(self) -> dict:
        """Cheap live reads feeding telemetry.health_score — shared by
        /status (via api.health_fn) and the node stats document. /status
        is the load-balancer AND peer-probe hot path, so the O(fragments)
        storage walk is read from the sampler's last tick when one exists
        (staleness <= telemetry-interval); the direct walk is only the
        sampler-disabled fallback."""
        from pilosa_tpu.utils import telemetry as _telemetry

        latest = self.telemetry.ring.latest()
        if latest:
            poisoned = latest.get("wal.poisoned_fragments", 0.0) > 0
            needs_rebuild = int(latest.get("wal.needs_rebuild", 0.0))
            n_damaged = int(latest.get("wal.damaged_fragments", 0.0))
        else:
            damaged = self.holder.damaged_fragments()
            poisoned = any(
                getattr(frag.storage, "wal_poisoned", False)
                for _i, _f, _v, _s, frag in self.holder.walk_fragments())
            needs_rebuild = sum(1 for d in damaged if d["needsRebuild"])
            n_damaged = len(damaged)
        ps = self.executor.fanout_pool_stats()
        out = {
            "walPoisoned": poisoned,
            "needsRebuild": needs_rebuild,
            "damagedFragments": n_damaged,
            "errorRate": latest.get("http.errors_per_s", 0.0),
            "queueSaturation": ps["queued"] / max(1, ps["size"]),
            "recompileStormActive": _telemetry.xla.storm_active(),
            # lifecycle: a draining node is deliberately yellow (the
            # federation renders the restart as in-progress, not broken),
            # and unverified fenced shards keep the rejoin visible
            "draining": self.draining,
            "fencedShards": self.executor.fence_snapshot()["fencedShards"],
        }
        slo = getattr(self.api, "slo", None)
        if slo is not None:
            # an SLO burning its error budget makes the node yellow/red
            # on /status and in the federation — the same single health
            # definition load balancers act on
            status, reason = slo.worst()
            if status != "green":
                out["sloStatus"] = status
                out["sloReason"] = reason
        return out

    def node_health(self) -> dict:
        from pilosa_tpu.utils.telemetry import health_score
        return health_score(self._health_inputs())

    def node_stats(self) -> dict:
        """This node's fleet-telemetry document (GET /internal/stats):
        identity, health + its inputs, the latest sampled gauges, XLA
        counters, device memory, and a bounded time-series tail for the
        fleet dashboard's sparklines."""
        from pilosa_tpu import __version__
        from pilosa_tpu.utils import telemetry as _telemetry

        inputs = self._health_inputs()
        ring = self.telemetry.ring
        tail = ring.since(0, limit=self.STATS_TAIL_SAMPLES)
        return {
            "id": self.node_id,
            "uri": self.http.uri,
            # a draining node reports DRAINING (the federation renders it
            # yellow via the health inputs); otherwise the cluster state
            "state": "DRAINING" if self.draining else self.cluster.state,
            "version": __version__,
            "uptimeSeconds": int(time.monotonic() - self.api.start_time),
            "health": _telemetry.health_score(inputs),
            "healthInputs": inputs,
            "damagedFragments": inputs["damagedFragments"],
            "gauges": ring.latest(),
            "counters": {
                "http5xx": self.handler.errors_5xx,
                "hedgesFired": getattr(self.executor, "hedges_fired", 0),
                "sampleErrors": self.telemetry.sample_errors,
            },
            "xla": _telemetry.xla.snapshot(),
            "deviceMemory": _telemetry.device_memory_stats(),
            "timeseries": tail,
        }

    def cluster_stats(self) -> dict:
        """The merged fleet document (GET /cluster/stats): every live
        peer's node stats collected CONCURRENTLY over the persistent
        fan-out pool, scored per node. Peers that 404 the route degrade
        to "legacy" (mixed-version clusters stay green); down peers are
        red without burning an RPC on them; a transient fetch failure of
        a live peer is yellow, never an error."""
        local = self.node_stats()
        entries: dict[str, dict] = {self.node_id: local}
        order: list[str] = []
        timeout = max(2.0, self.probe_timeout)
        # dedicated short-lived threads, NOT the query fan-out pool: under
        # heavy query load that pool's queue is deep (the very condition
        # queueSaturation flags), and stats fetches queued behind query
        # RPCs would time out and paint healthy peers yellow exactly when
        # the operator looks at the fleet (same pattern as _probe_peers)
        fetchers: list[tuple] = []
        for n in list(self.cluster.nodes):
            order.append(n.id)
            if n.id == self.node_id:
                continue
            if self.cluster.is_down(n.id):
                if self.cluster.is_draining(n.id):
                    # a drained node that went away is mid-restart, not
                    # failed: yellow until it rejoins (or the drain mark
                    # ages into a plain down if it never comes back —
                    # probes clear the draining mark only via mark_up)
                    entries[n.id] = {
                        "id": n.id, "uri": n.uri, "state": "DRAINING",
                        "health": {"score": "yellow", "reasons": [
                            "node draining (rolling restart in "
                            "progress)"]}}
                else:
                    entries[n.id] = {
                        "id": n.id, "uri": n.uri, "state": "down",
                        "health": {"score": "red", "reasons": [
                            "node marked down (liveness)"]}}
                continue
            if not n.uri:
                entries[n.id] = {
                    "id": n.id, "uri": "", "state": "unknown",
                    "health": {"score": "yellow",
                               "reasons": ["no known URI"]}}
                continue

            def fetch(node=n):
                draining = self.cluster.is_draining(node.id)
                try:
                    doc = self.client.node_stats(node.uri, timeout)
                    doc.setdefault("id", node.id)
                    doc.setdefault("uri", node.uri)
                    entries[node.id] = doc
                except ClientError as e:
                    if draining:
                        # mid-restart: the drained process has stopped
                        # answering but has NOT failed — yellow, not red
                        entries[node.id] = {
                            "id": node.id, "uri": node.uri,
                            "state": "DRAINING",
                            "health": {"score": "yellow", "reasons": [
                                "node draining (rolling restart in "
                                "progress)"]}}
                    elif e.status == 404:
                        entries[node.id] = {
                            "id": node.id, "uri": node.uri, "state": "up",
                            "health": {"score": "legacy", "reasons": [
                                "peer predates /internal/stats "
                                "(legacy protocol)"]}}
                    else:
                        entries[node.id] = {
                            "id": node.id, "uri": node.uri, "state": "up",
                            "health": {"score": "yellow", "reasons": [
                                f"stats fetch failed: {e}"]}}
                except Exception as e:  # noqa: BLE001 — never fail whole
                    entries[node.id] = {
                        "id": node.id, "uri": node.uri, "state": "up",
                        "health": {"score": "yellow", "reasons": [
                            f"stats fetch failed: "
                            f"{type(e).__name__}: {e}"]}}

            fetchers.append((n, _threads.spawn(fetch)))
        for n, t in fetchers:
            t.join(timeout + 1.0)
            if n.id not in entries:
                entries[n.id] = {
                    "id": n.id, "uri": n.uri, "state": "up",
                    "health": {"score": "yellow", "reasons": [
                        f"stats fetch timed out after {timeout:.1f}s"]}}
        nodes = [entries[i] for i in order]
        counts: dict[str, int] = {}
        worst = "green"
        sev = {"green": 0, "yellow": 1, "red": 2}
        for nd in nodes:
            score = (nd.get("health") or {}).get("score", "unknown")
            counts[score] = counts.get(score, 0) + 1
            # legacy/unknown never degrade the fleet: a peer speaking the
            # old protocol is healthy by every signal it CAN emit
            if score in sev and sev[score] > sev[worst]:
                worst = score
        return {
            "fleet": {"health": worst, "counts": counts, "nodes": nodes},
            "generatedBy": self.node_id,
            "asOf": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }

    def cluster_usage(self) -> dict:
        """The fleet's merged per-principal usage (GET /cluster/usage):
        every live peer's /debug/usage ledger collected concurrently and
        summed per principal, so "who is spending the fleet" is one
        request from any node. Same degradation contract as
        cluster_stats: peers that 404 the route are "legacy" (never an
        error), down peers are skipped without an RPC, transient fetch
        failures mark the node and leave the merge partial-but-honest."""
        from pilosa_tpu.utils import accounting as _accounting

        docs: dict[str, dict] = {}
        nodes: list[dict] = []
        timeout = max(2.0, self.probe_timeout)
        fetchers: list[tuple] = []
        for n in list(self.cluster.nodes):
            if n.id == self.node_id:
                docs[n.id] = self.usage.snapshot()
                nodes.append({"id": n.id, "uri": self.uri, "status": "ok"})
                continue
            if self.cluster.is_down(n.id) or not n.uri:
                nodes.append({"id": n.id, "uri": n.uri or "",
                              "status": "down"})
                continue
            entry = {"id": n.id, "uri": n.uri, "status": "pending"}
            nodes.append(entry)

            def fetch(node=n, entry=entry):
                try:
                    docs[node.id] = self.client.debug_usage(node.uri,
                                                            timeout)
                    entry["status"] = "ok"
                except ClientError as e:
                    entry["status"] = ("legacy" if e.status == 404
                                       else "error")
                except Exception:  # noqa: BLE001 — never fail the merge
                    entry["status"] = "error"

            fetchers.append((entry, _threads.spawn(fetch)))
        for entry, t in fetchers:
            t.join(timeout + 1.0)
            if entry["status"] == "pending":
                entry["status"] = "error"
        merged: dict[str, dict] = {}
        totals = dict.fromkeys(_accounting.FIELDS, 0.0)
        spilled = 0
        for doc in docs.values():
            for p, e in (doc.get("principals") or {}).items():
                acc = merged.setdefault(
                    p, dict.fromkeys(_accounting.FIELDS, 0.0))
                for f in _accounting.FIELDS:
                    acc[f] += float(e.get(f, 0.0))
                acc["nodes"] = acc.get("nodes", 0) + 1
            for f in _accounting.FIELDS:
                totals[f] += float((doc.get("totals") or {}).get(f, 0.0))
            spilled += int(doc.get("spilledPrincipals", 0))
        ordered = dict(sorted(merged.items(),
                              key=lambda kv: (-kv[1]["deviceMs"],
                                              -kv[1]["queries"], kv[0])))
        return {
            "principals": ordered,
            "totals": totals,
            "spilledPrincipals": spilled,
            "nodes": nodes,
            "generatedBy": self.node_id,
            "asOf": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }

    def cluster_hbm(self) -> dict:
        """The fleet's HBM residency maps (GET /cluster/hbm): every live
        peer's /debug/hbm document collected concurrently, with fleet
        byte totals summed across nodes — "what is resident where, and
        how much headroom is left" from any node. Same degradation
        contract as cluster_stats: peers that 404 the route are "legacy"
        (never an error), down peers are skipped without an RPC,
        transient fetch failures leave the merge partial-but-honest."""
        docs: dict[str, dict] = {}
        nodes: list[dict] = []
        timeout = max(2.0, self.probe_timeout)
        fetchers: list[tuple] = []
        for n in list(self.cluster.nodes):
            if n.id == self.node_id:
                docs[n.id] = self.executor.hbm_snapshot()
                nodes.append({"id": n.id, "uri": self.uri, "status": "ok"})
                continue
            if self.cluster.is_down(n.id) or not n.uri:
                nodes.append({"id": n.id, "uri": n.uri or "",
                              "status": "down"})
                continue
            entry = {"id": n.id, "uri": n.uri, "status": "pending"}
            nodes.append(entry)

            def fetch(node=n, entry=entry):
                try:
                    docs[node.id] = self.client.debug_hbm(node.uri, timeout)
                    entry["status"] = "ok"
                except ClientError as e:
                    entry["status"] = ("legacy" if e.status == 404
                                       else "error")
                except Exception:  # noqa: BLE001 — never fail the merge
                    entry["status"] = "error"

            fetchers.append((entry, _threads.spawn(fetch)))
        for entry, t in fetchers:
            t.join(timeout + 1.0)
            if entry["status"] == "pending":
                entry["status"] = "error"
        totals = {"residentBytes": 0, "budgetBytes": 0, "headroomBytes": 0,
                  "planCacheBytes": 0, "entries": 0}
        drift = None
        for doc in docs.values():
            for f in totals:
                totals[f] += int(doc.get(f, 0) or 0)
            d = doc.get("hbmDriftBytes")
            if d is not None:
                drift = (drift or 0) + int(d)
        return {
            "byNode": docs,
            "totals": totals,
            "hbmDriftBytes": drift,
            "nodes": nodes,
            "generatedBy": self.node_id,
            "asOf": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }

    def cluster_events(self, limit: int = 0) -> dict:
        """The merged cluster timeline (GET /cluster/events): every live
        peer's /debug/events feed collected CONCURRENTLY and HLC-sorted
        into one causal event stream (utils/events.py merge_events) —
        "what happened, in order, across the fleet" from any node. Same
        degradation contract as cluster_stats: peers that 404 the route
        are "legacy" (never an error), down peers are skipped without an
        RPC, transient failures leave the merge partial-but-honest. The
        RPCs themselves piggyback HLC stamps, so the collecting node's
        clock catches up to every peer before it sorts."""
        from pilosa_tpu.utils import events as _events

        docs: dict[str, list[dict]] = {}
        nodes: list[dict] = []
        timeout = max(2.0, self.probe_timeout)
        fetchers: list[tuple] = []
        for n in list(self.cluster.nodes):
            if n.id == self.node_id:
                docs[n.id] = self.events.events(0)
                nodes.append({"id": n.id, "uri": self.uri,
                              "status": "ok"})
                continue
            if self.cluster.is_down(n.id) or not n.uri:
                nodes.append({"id": n.id, "uri": n.uri or "",
                              "status": "down"})
                continue
            entry = {"id": n.id, "uri": n.uri, "status": "pending"}
            nodes.append(entry)

            def fetch(node=n, entry=entry):
                try:
                    doc = self.client.debug_events(node.uri, timeout)
                    docs[node.id] = doc.get("events", [])
                    entry["status"] = "ok"
                except ClientError as e:
                    entry["status"] = ("legacy" if e.status == 404
                                       else "error")
                except Exception:  # noqa: BLE001 — never fail the merge
                    entry["status"] = "error"

            fetchers.append((entry, _threads.spawn(fetch)))
        for entry, t in fetchers:
            t.join(timeout + 1.0)
            if entry["status"] == "pending":
                entry["status"] = "error"
        merged = _events.merge_events(docs)
        if limit > 0:
            merged = merged[-limit:]
        return {
            "events": merged,
            "nodes": nodes,
            "generatedBy": self.node_id,
            "asOf": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }

    def cluster_heat(self) -> dict:
        """The fleet's merged fragment heat map (GET /cluster/heat):
        every live peer's /debug/heat document collected concurrently
        and merged per fragment coordinate (utils/heat.py
        merge_heat_docs — replica heat SUMS: two nodes serving a
        fragment's reads make it twice as hot fleet-wide, the signal
        rebalancing ranks by). Same degradation contract as
        cluster_stats/cluster_usage: peers that 404 the route are
        "legacy" (never an error), down peers are skipped without an
        RPC, transient failures leave the merge partial-but-honest.
        Per-node skew/health summaries ride along — the placement
        advisor's node-level input."""
        from pilosa_tpu.utils import heat as _heat

        docs: dict[str, dict] = {}
        nodes: list[dict] = []
        timeout = max(2.0, self.probe_timeout)
        fetchers: list[tuple] = []
        for n in list(self.cluster.nodes):
            if n.id == self.node_id:
                tracker = getattr(self.executor, "heat", None)
                docs[n.id] = (tracker.snapshot(top=0)
                              if tracker is not None else {})
                nodes.append({"id": n.id, "uri": self.uri,
                              "status": "ok"})
                continue
            if self.cluster.is_down(n.id) or not n.uri:
                nodes.append({"id": n.id, "uri": n.uri or "",
                              "status": "down"})
                continue
            entry = {"id": n.id, "uri": n.uri, "status": "pending"}
            nodes.append(entry)

            def fetch(node=n, entry=entry):
                try:
                    docs[node.id] = self.client.debug_heat(node.uri,
                                                           timeout)
                    entry["status"] = "ok"
                except ClientError as e:
                    entry["status"] = ("legacy" if e.status == 404
                                       else "error")
                except Exception:  # noqa: BLE001 — never fail the merge
                    entry["status"] = "error"

            fetchers.append((entry, _threads.spawn(fetch)))
        for entry, t in fetchers:
            t.join(timeout + 1.0)
            if entry["status"] == "pending":
                entry["status"] = "error"
        out = _heat.merge_heat_docs(docs)
        for entry in nodes:
            doc = docs.get(entry["id"])
            if doc:
                # node-level temperature summary: the advisor's
                # per-node hot-shard skew vs health input
                entry["skew"] = doc.get("skew", 1.0)
                entry["hotFragments"] = doc.get("hotFragments", 0)
                entry["trackedFragments"] = doc.get(
                    "trackedFragments", 0)
        out["nodes"] = nodes
        out["generatedBy"] = self.node_id
        out["asOf"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        return out

    # -- anti-entropy scrubber (server.go:430-483; fragment.go:2170) --------

    def _schedule_anti_entropy(self) -> None:
        if self.closed:
            return
        import random as _random
        interval = self.anti_entropy_interval
        if self.anti_entropy_jitter > 0:
            # de-synchronize replicas: every node scrubbing at the same
            # instant turns anti-entropy into a cluster-wide load spike
            interval *= 1.0 + _random.uniform(-self.anti_entropy_jitter,
                                              self.anti_entropy_jitter)
        self._ae_timer = _threads.ctx_timer(max(interval, 0.01),
                                            self._anti_entropy_tick)
        self._ae_timer.start()

    def _anti_entropy_tick(self) -> None:
        try:
            self.scrub_pass()
        except Exception as e:  # noqa: BLE001 — a failed pass (dead peer,
            # injected fault) must never kill the ticker: the next pass
            # retries everything from scratch
            self.logger.printf("anti-entropy: pass failed: %s", e)
        finally:
            self._schedule_anti_entropy()

    def _resize_active(self) -> bool:
        with self._resize_lock:
            return (self.cluster.state == STATE_RESIZING
                    or self.cluster.active_job is not None)

    def scrub_pass(self) -> int:
        """One full scrubber pass: rebuild quarantined fragments from live
        replicas, then walk owned fragments diffing block checksums against
        replicas and repairing divergence via merge_block_majority
        (sync_holder). Skipped while a resize is migrating fragments — the
        two would fight over the same shards (and sync_holder re-checks
        per fragment, since a paced pass can span minutes and a resize can
        start mid-pass). Returns blocks merged."""
        import time as _time
        if self._resize_active():
            return 0
        t0 = _time.monotonic()
        rebuilt = self.repair_quarantined()
        merged = self.sync_holder()
        self._scrub_passes += 1
        self.events.emit("scrub.pass", blocksMerged=merged,
                         fragmentsRebuilt=rebuilt,
                         seconds=round(_time.monotonic() - t0, 3))
        self.stats.count("antiEntropy/passes")
        if merged:
            self.stats.count("antiEntropy/blocksMerged", merged)
        if rebuilt:
            self.stats.count("antiEntropy/fragmentsRebuilt", rebuilt)
        self.stats.gauge("antiEntropy/lastPassSeconds",
                         _time.monotonic() - t0)
        return merged

    def repair_quarantined(self) -> int:
        """Rebuild fragments that open() quarantined (corrupt snapshot →
        emptied) by streaming a replica's full snapshot over the resize
        copy path (RetrieveShardFromURI analog). Block-level anti-entropy
        would converge them too, but a whole-fragment fetch is one RPC
        instead of a block-by-block vote, and it marks the fragment healthy
        immediately. No live replica → left empty; the next pass retries.
        Returns fragments rebuilt."""
        rebuilt = 0
        for iname, fname, vname, shard, frag in \
                list(self.holder.walk_fragments()):
            if not frag.needs_rebuild:
                continue
            for node in self.cluster.shard_nodes(iname, shard):
                if node.id == self.node_id or not node.uri \
                        or self.cluster.is_down(node.id):
                    continue
                try:
                    data = self.client.retrieve_shard(
                        node.uri, iname, fname, vname, shard)
                except ClientError:
                    continue  # replica has no copy / unreachable: next one
                try:
                    # bulk union into the emptied fragment; import_roaring
                    # auto-snapshots, so the rebuild is durable (fresh
                    # integrity trailer included) before we mark it healthy
                    frag.import_roaring(data)
                except (ValueError, OSError) as e:
                    self.logger.printf(
                        "scrubber: rebuild of %s/%s/%s/%d from %s failed: %s",
                        iname, fname, vname, shard, node.id, e)
                    continue
                frag.rebuilt_from = node.id
                rebuilt += 1
                self.logger.printf(
                    "scrubber: rebuilt quarantined fragment %s/%s/%s/%d "
                    "from replica %s (%d bits; corrupt file kept at %s)",
                    iname, fname, vname, shard, node.id, frag.bit_count(),
                    frag.quarantine_path)
                break
        return rebuilt

    def _schedule_cache_flush(self) -> None:
        if self.closed:
            return
        self._cache_flush_timer = _threads.ctx_timer(
            self.cache_flush_interval, self._cache_flush_tick)
        self._cache_flush_timer.start()

    def _cache_flush_tick(self) -> None:
        """Periodic rank-cache persistence (holder.monitorCacheFlush,
        holder.go:483-526)."""
        try:
            self.holder.flush_caches()
        except Exception as e:  # noqa: BLE001 — a failed flush must not kill the ticker
            self.logger.printf("cache flush: %s", e)
        finally:
            self._schedule_cache_flush()

    def sync_holder(self) -> int:
        """One full anti-entropy pass: index column attrs, field row attrs,
        then owned fragments; returns blocks merged (holderSyncer.SyncHolder,
        holder.go:633-853 — syncIndex :726, syncField :772, fragments :821)."""
        merged = 0
        for iname, idx in self.holder.indexes.items():
            merged += self._sync_attrs(
                idx.column_attrs,
                lambda uri, blocks, rng: self.client.column_attr_diff(
                    uri, iname, blocks, rng))
            for fname, field in idx.fields.items():
                merged += self._sync_attrs(
                    field.row_attrs,
                    lambda uri, blocks, rng, fn=fname:
                    self.client.row_attr_diff(uri, iname, fn, blocks, rng))
                for vname, view in field.views.items():
                    for shard in view.shards():
                        if self._resize_active():
                            # a resize started mid-pass (paced passes can
                            # span minutes): stop — merging blocks against
                            # a topology that is migrating under us would
                            # race the fragment copies. The next pass
                            # finishes the walk.
                            return merged
                        if not self.cluster.owns_shard(self.node_id, iname, shard):
                            continue
                        merged += self._sync_fragment(iname, fname, vname, shard)
                        if self.anti_entropy_pace > 0:
                            # paced: a scrub pass shares the node with live
                            # queries — it must trickle, not starve the
                            # fan-out pool / HTTP threads of CPU and peers
                            time.sleep(self.anti_entropy_pace)
        return merged

    # attr blocks per diff request: bounds both the request body and the
    # peer's response working set so one anti-entropy pass streams a large
    # attr store in pages instead of shipping the whole block list at once
    # (the reference pages via attr blocks, attr.go / holder.go:726-820)
    ATTR_SYNC_PAGE = 512

    def _sync_attrs(self, store, diff_fn) -> int:
        """Pull attr blocks that differ from each peer and merge them in
        (attrs replicate to every node; each node pulls on its own pass).

        Paged: local blocks are sent in ATTR_SYNC_PAGE chunks, each with a
        [lo, hi) block range that tiles the whole id space — so peer-only
        blocks between or beyond my chunks are still pulled exactly once."""
        merged = 0

        def make_pages():
            # rebuilt per peer: attrs merged from one peer change the
            # local checksums, and stale pages would make every later
            # peer resend data already merged
            all_blocks = [{"id": b, "checksum": chk.hex()}
                          for b, chk in store.blocks()]
            pages = []
            lo = 0
            for i in range(0, len(all_blocks), self.ATTR_SYNC_PAGE):
                chunk = all_blocks[i:i + self.ATTR_SYNC_PAGE]
                last = i + self.ATTR_SYNC_PAGE >= len(all_blocks)
                hi = None if last else int(chunk[-1]["id"]) + 1
                pages.append((chunk, [lo, hi]))
                lo = hi
            # no local blocks: one full unbounded pull
            return pages or [([], [0, None])]

        for node in self.cluster.nodes:
            if node.id == self.node_id or not node.uri \
                    or self.cluster.is_down(node.id):
                continue
            got = False
            try:
                for chunk, rng in make_pages():
                    attrs = diff_fn(node.uri, chunk, rng)
                    if attrs:
                        store.set_bulk_attrs(attrs.items())
                        got = True
            except ClientError:
                pass  # later pages lost; earlier merges still count
            if got:
                merged += 1
        return merged

    def _sync_fragment(self, iname: str, fname: str, vname: str, shard: int) -> int:
        """Majority-consensus fragment sync (syncBlock, fragment.go:2271-2356):
        fetch each out-of-sync block's pairset from EVERY reachable replica,
        run ONE merge with majorityN = (configured replicas + 1)//2, apply
        local sets AND clears, and push both delta directions to each peer
        (clears ride import_roaring(clear=True)). The threshold comes from
        the CONFIGURED replica count, and whenever any configured replica
        didn't vote (unreachable, marked down, deleted schema) the merge
        falls back to union — so clears only ever happen on the full
        replica set's evidence, and a dropped voter can never let a
        minority outvote the true majority."""
        import numpy as np
        from pilosa_tpu.storage.roaring import Bitmap
        from pilosa_tpu.constants import SHARD_WIDTH
        from pilosa_tpu.utils import failpoints

        failpoints.hit("server.scrub.fragment")
        frag = self.holder.index(iname).field(fname).view(vname).fragment(shard)
        if frag is None:
            return 0
        # collect every reachable replica's block-checksum map up front
        peers = []  # (node, {blk: checksum-hex}, has_fragment)
        for node in self.cluster.shard_nodes(iname, shard):
            if node.id == self.node_id or not node.uri \
                    or self.cluster.is_down(node.id):
                continue
            try:
                remote = {b["id"]: b["checksum"]
                          for b in self.client.fragment_blocks(
                              node.uri, iname, fname, vname, shard)}
                has_fragment = True
            except ClientError as e:
                if e.code != "fragment-not-found":
                    # a missing *index/field* on the peer means it was
                    # deleted there (we missed the broadcast while down):
                    # do NOT push — that would churn RPCs against the
                    # deleted schema every pass. An unreachable peer is
                    # likewise excluded: it can't vote or receive deltas.
                    continue
                # peer owns the shard but has no fragment at all (e.g. it
                # was down for the write that created it): it votes with
                # empty blocks, and the set-deltas we push create the
                # fragment remotely via the import
                remote, has_fragment = {}, False
            peers.append((node, remote, has_fragment))
        if not peers:
            return 0
        # clears need the FULL replica set's evidence: if any configured
        # replica isn't voting (down, unreachable, schema gone), fall back
        # to union (majority_n=1) instead of letting the remaining voters
        # clear bits the absent replica may hold the majority with
        configured = min(self.cluster.replica_n, len(self.cluster.nodes))
        if len(peers) + 1 == configured:
            majority_n = (configured + 1) // 2
        else:
            majority_n = 1
        local_blocks = dict(frag.blocks())
        all_blocks = set(local_blocks)
        for _, remote, _ in peers:
            all_blocks |= set(remote)
        merged = 0
        adopted = False  # any local change -> snapshot for the WAL
        sw = np.uint64(SHARD_WIDTH)
        for blk in sorted(all_blocks):
            if self.anti_entropy_max_blocks > 0 \
                    and merged >= self.anti_entropy_max_blocks:
                break  # bounded pass; the next pass picks up where diffs remain
            lc = local_blocks.get(blk)
            if lc is not None and all(remote.get(blk) == lc.hex()
                                      for _, remote, _ in peers):
                continue
            # every peer votes: absent block (or absent fragment) = empty
            # set; identical checksums mean identical pairsets, so each
            # DISTINCT checksum is fetched once — a peer matching local
            # votes the local copy, peers matching each other share one
            # fetch (each still votes individually)
            by_checksum: dict = {}
            if lc is not None:
                lr, lcols = frag.block_data(blk)
                by_checksum[lc.hex()] = (lr.astype(np.uint64) * sw
                                         + lcols.astype(np.uint64))
            voters, positions = [], []
            fetch_failed = False
            for node, remote, has_fragment in peers:
                if not has_fragment or blk not in remote:
                    pos = np.empty(0, dtype=np.uint64)
                elif remote[blk] in by_checksum:
                    pos = by_checksum[remote[blk]]
                else:
                    try:
                        data = self.client.block_data(node.uri, iname, fname,
                                                      vname, shard, blk)
                    except ClientError as e:
                        if e.status != 404:
                            if majority_n > 1:
                                # a correct majority needs this replica's
                                # vote; skip the block this pass rather
                                # than clear on partial evidence
                                fetch_failed = True
                                break
                            # union mode can't clear, so a flaky peer just
                            # drops out of this block: the remaining peers
                            # still heal (and it gets no delta push)
                            continue
                        data = None  # block raced away: empty vote
                    if data is None:
                        pos = np.empty(0, dtype=np.uint64)
                    else:
                        pos = (np.array(data.get("rowIDs", []),
                                        dtype=np.uint64) * sw
                               + np.array(data.get("columnIDs", []),
                                          dtype=np.uint64))
                        by_checksum[remote[blk]] = pos
                voters.append(node)
                positions.append(pos)
            if fetch_failed:
                continue
            n_sets, n_clears, deltas, durable = frag.merge_block_majority(
                blk, positions, majority_n=majority_n)
            # small adoptions WAL-append inside the merge; only a large
            # adoption asks for the one-snapshot-per-pass fallback
            adopted |= not durable
            merged += 1
            for node, (peer_sets, peer_clears) in zip(voters, deltas):
                for delta, clear in ((peer_sets, False), (peer_clears, True)):
                    if not delta.size:
                        continue
                    payload = Bitmap(delta).to_bytes()
                    try:
                        self.client.import_roaring(
                            node.uri, iname, fname, shard, {vname: payload},
                            remote=True, clear=clear)
                    except ClientError:
                        pass
        if adopted:
            # only LARGE adoptions on WAL-attached fragments land here
            # (durable=False): small ones WAL-appended inside
            # merge_block_majority, volatile fragments owe nothing by
            # contract — one snapshot per sync pass covers the rest
            frag.snapshot()
        return merged
