"""Server configuration: defaults <- TOML file <- env <- flags.

Reference: server/config.go:36-105 (the flag surface) and cmd/root.go:91-120
(viper merge order). Env vars use the PILOSA_TPU_ prefix with dots mapped to
underscores (PILOSA_TPU_CLUSTER_REPLICAS, matching the reference's PILOSA_*).
"""

from __future__ import annotations

import os
import tomllib
from dataclasses import dataclass, field

from pilosa_tpu.utils.duration import parse_duration


@dataclass
class TLSConfig:
    """server/config.go:26-33 — TLS section; certificate+key enable HTTPS
    serving, skip_verify disables peer verification on the internal client."""
    certificate: str = ""
    key: str = ""
    skip_verify: bool = False

    @property
    def enabled(self) -> bool:
        return bool(self.certificate and self.key)


@dataclass
class ClusterConfig:
    disabled: bool = True
    coordinator: bool = False
    replicas: int = 1
    hosts: list[str] = field(default_factory=list)
    long_query_time: float = 0.0
    # server-wide default query deadline (seconds/duration); 0 = none.
    # Overridden per request by ?timeout= or an adopted fan-out header.
    query_timeout: float = 0.0
    # liveness probing (gossip probe/suspicion analog,
    # gossip/gossip.go:488-519): consecutive failed /status probes before a
    # peer is marked down, and the per-probe timeout in seconds
    liveness_threshold: int = 3
    probe_timeout: float = 2.0
    # seconds between membership refresh + liveness probe ticks (the
    # memberlist ProbeInterval analog, gossip/gossip.go:508-519)
    membership_interval: float = 5.0
    # distributed fan-out (net/coalesce.py; docs/operations.md "Fan-out
    # and hedging"): persistent fan-out pool size, the coalesce window a
    # query-batch leader waits for co-destined queries (duration; flushes
    # earlier on an arrival lull or at max-batch), the per-envelope entry
    # cap, and the hedged-read delay after which a read-only node batch
    # re-issues to the next live replica (duration; 0 disables hedging)
    fanout_pool_size: int = 32
    fanout_coalesce_window: float = 0.002
    fanout_coalesce_max_batch: int = 64
    hedge_delay: float = 0.0
    # ICI-native slice-local serving (docs/operations.md "ICI-native
    # serving"): "auto" (default) serves a query as ONE sharded program
    # over the local mesh when this node holds a live replica of every
    # query shard; "on" routes slice-local even on a single-device runner
    # (still removes the fan-out RTTs); "off" always scatter-gathers.
    # PILOSA_TPU_ICI=0 is the env kill switch over any mode.
    ici_serving: str = "auto"
    # distributed query profiler (utils/profile.py): "off" never profiles,
    # "auto" (default) profiles when a request asks (?profile=true) or
    # when long-query-time is set (so /debug/query-history carries full
    # profile trees), "on" profiles every query. PILOSA_TPU_PROFILE=0 is
    # the env kill switch over any mode.
    profile: str = "auto"
    # slow-query ring size served at GET /debug/query-history
    query_history_size: int = 100
    # zero-downtime operations (docs/operations.md "Rolling restarts and
    # drains"): hint-max-bytes caps each down replica's on-disk hint log
    # (overflow drops the hint durably and forces the anti-entropy
    # fallback); hint-max-age (duration) expires hints at replay time;
    # drain-timeout (duration) bounds how long SIGTERM / POST
    # /cluster/drain waits for in-flight work and queue flushes before
    # snapshotting anyway
    hint_max_bytes: int = 64 * 1024 * 1024
    hint_max_age: float = 3600.0
    drain_timeout: float = 30.0


@dataclass
class QueryConfig:
    """[query] — cost-based planner + cross-query plan cache
    (pilosa_tpu/planner.py; docs/operations.md "Query planning").
    plan: "on" (default) reorders commutative chains cheapest-first,
    short-circuits provably-empty branches and marks Count/TopN
    pushdowns; "off" evaluates written order. plan-cache-bytes bounds the
    generation-keyed device-resident subexpression cache (0 disables).
    The PILOSA_TPU_PLANNER=0 / PILOSA_TPU_PLAN_CACHE=0 env kill switches
    override both to off (emergency toggles needing no config rollout).

    sparse-threshold: hybrid sparse/dense device containers
    (docs/operations.md "Hybrid containers") — rows at or below this many
    set bits per shard upload to HBM as padded sorted-index arrays
    instead of 128 KiB dense planes; 0 keeps every row dense. The
    PILOSA_TPU_HYBRID=0 env kill switch wins over any threshold.

    run-threshold: run (interval-pair) device containers — rows ABOVE
    sparse-threshold whose write-maintained interval count is at or
    below this upload as sorted [start, last] pairs instead of dense
    planes; 0 keeps such rows dense. Same PILOSA_TPU_HYBRID=0 kill
    switch."""
    plan: str = "on"
    plan_cache_bytes: int = 256 * 1024 * 1024
    sparse_threshold: int = 4096
    run_threshold: int = 2048


@dataclass
class QosConfig:
    """[qos] — multi-tenant QoS plane (pilosa_tpu/qos.py;
    docs/operations.md "Overload control and QoS").

    mode: "off" (default — no admission, no behavior change), "observe"
    (count + log every would-shed/would-throttle decision without
    rejecting: the safe rollout step), "enforce". default-priority is the
    class untagged requests run as; default-deadline (seconds/duration, 0
    = none) gives every query a budget so deadline shedding can act.
    queries-per-s / device-ms-per-s / bytes-per-s are the DEFAULT
    per-principal quotas (0 = unlimited); burst is the bucket depth in
    seconds of rate. Per-principal overrides (any quota key plus
    `priority`) live in [qos.principals."<principal>"] sub-tables keyed
    by the accounting principal (e.g. "key:dashboards").
    PILOSA_TPU_QOS=0 is the env kill switch over everything."""
    mode: str = "off"
    default_priority: str = "interactive"
    default_deadline: float = 0.0
    queries_per_s: float = 0.0
    device_ms_per_s: float = 0.0
    bytes_per_s: float = 0.0
    burst: float = 2.0
    max_principals: int = 256
    principals: dict = field(default_factory=dict)


@dataclass
class StorageConfig:
    """[storage] — durability knobs (docs/operations.md "Failure modes and
    recovery"). wal-fsync: "off" (default; matches the reference, which
    writes through an unbuffered file but does not fsync) or "always"
    (fsync per acked op: survives power loss, ~100x write cost).
    Precedence: the PILOSA_TPU_WAL_FSYNC env var, when set, overrides this
    setting per fragment (kept as the emergency toggle that needs no
    config rollout); unset env → this knob; neither → off.

    eviction: HBM residency victim selection — "lru" (default) or "heat"
    (evict coldest by the fragment heat map, utils/heat.py; requires
    heat tracking, so PILOSA_TPU_HEAT=0 forces lru regardless)."""
    wal_fsync: str = "off"
    eviction: str = "lru"


@dataclass
class IngestConfig:
    """[ingest] — write-side continuous batching (pilosa_tpu/parallel/
    ingest.py; docs/operations.md "Streaming ingest"). batch-window:
    admission window in seconds (duration strings accepted) a batch
    leader waits for stragglers before cutting; the default 0 is self-
    clocked group commit — a lone writer cuts immediately, and under
    concurrency arrivals accumulate behind the in-flight apply, so batch
    size tracks arrival_rate x apply_time. Raise it on fsync-heavy
    configs to trade lone-writer latency for larger group commits.
    max-batch bounds mutations per applied batch. PILOSA_TPU_INGEST=0 is
    the env kill switch (read per call — no restart): mutations take the
    per-bit write path with identical semantics."""
    batch_window: float = 0.0
    max_batch: int = 4096


@dataclass
class AntiEntropyConfig:
    interval: float = 0.0  # seconds; 0 disables (server.go:430-445)
    # scrubber tuning: jitter spreads node passes apart (fraction of the
    # interval, +/-); pace sleeps between per-fragment scrubs so a pass
    # never starves live queries; max-blocks bounds block repairs per
    # fragment per pass (0 = unbounded)
    jitter: float = 0.25
    pace: float = 0.0
    max_blocks: int = 0


@dataclass
class MetricConfig:
    service: str = "expvar"  # expvar | statsd | nop
    host: str = "127.0.0.1:8125"  # statsd agent address
    poll_interval: float = 0.0
    # fleet telemetry sampler (utils/telemetry.py): seconds between gauge
    # snapshots into the /debug/timeseries ring (0 disables; the
    # PILOSA_TPU_TELEMETRY=0 env var kills it regardless), and the ring's
    # bounded sample capacity (720 x 5s = one hour of history)
    telemetry_interval: float = 5.0
    telemetry_ring: int = 720
    # per-principal usage ledger bounds (utils/accounting.py; GET
    # /debug/usage): tracked-principal cap with lowest-spender spill and
    # the since-cursor delta ring's capacity. PILOSA_TPU_ACCOUNTING=0 is
    # the env kill switch.
    usage_max_principals: int = 256
    usage_ring: int = 360
    # external trace export (utils/tracing.py TraceExporter): "off"
    # (default), "file" (append Jaeger/OTLP-JSON batches to
    # trace-export-path, default <data-dir>/trace-spool.jsonl), or
    # "http" (POST batches to trace-export-endpoint). trace-export-sample
    # is the deterministic per-trace sampling fraction;
    # PILOSA_TPU_TRACE_EXPORT=0 is the env kill switch.
    trace_export: str = "off"
    trace_export_path: str = ""
    trace_export_endpoint: str = ""
    trace_export_format: str = "jaeger"  # jaeger | otlp
    trace_export_sample: float = 1.0
    # cluster flight recorder (utils/events.py; GET /debug/events and
    # the /cluster/events merged timeline): events-ring bounds the
    # in-memory lifecycle lane (the log lane gets a quarter of it);
    # events-spool > 0 additionally appends every event to a durable
    # <data-dir>/events.spool.jsonl capped at that many bytes (one
    # rotation kept). PILOSA_TPU_EVENTS=0 is the env kill switch.
    events_ring: int = 2048
    events_spool: int = 0


@dataclass
class SLOConfig:
    """[slo] — service-level objectives per query class, evaluated with
    multi-window (short/long) burn-rate math in the telemetry sampler
    (utils/accounting.py SLOTracker) and surfaced as slo/* gauges plus a
    red/yellow contribution to the shared health score.

    <class>-latency-ms (read / count / topn / groupby): a query of that
    class slower than the bound counts against the error budget; 0
    disables that objective. latency-target is the good fraction for
    every latency objective; availability-target covers all queries
    (errors only; 0 disables). An objective trips yellow/red when BOTH
    windows burn the budget faster than burn-yellow / burn-red."""
    read_latency_ms: float = 0.0
    count_latency_ms: float = 0.0
    topn_latency_ms: float = 0.0
    groupby_latency_ms: float = 0.0
    latency_target: float = 0.99
    availability_target: float = 0.999
    burn_yellow: float = 6.0
    burn_red: float = 14.4
    window_short: float = 300.0
    window_long: float = 3600.0


@dataclass
class DiagnosticsConfig:
    url: str = ""  # phone-home endpoint; empty disables
    interval: float = 0.0


@dataclass
class TracingConfig:
    sampler_type: str = "off"
    sampler_param: float = 0.0
    agent_host_port: str = ""


@dataclass
class GossipSection:
    """[gossip] — SWIM UDP failure detector (server/config.go:126 defaults
    Port to "14000"; seeds are host:port gossip addresses). port = -1 keeps
    the default HTTP probe liveness; 0 binds an ephemeral port (tests);
    period/probe-timeout scale the SWIM protocol clock
    (parallel/gossip.py GossipConfig)."""
    port: int = -1
    seeds: list[str] = field(default_factory=list)
    period: float = 1.0
    probe_timeout: float = 0.5
    push_pull_interval: float = 10.0
    # shared-key transport encryption (parallel/gossip.py): a non-empty
    # secret AES-GCM-encrypts every gossip datagram (key derived by
    # blake2b from this passphrase); nodes without the key — and
    # plaintext datagrams when a key is set — are silently dropped.
    secret: str = ""


@dataclass
class MeshConfig:
    """Device-mesh section — the TPU analog of the reference's intra-node
    shard concurrency (executor.go:2283): slabs shard over a 1-D GSPMD mesh
    of the node's local chips instead of goroutine-per-shard.

    devices: "auto" = use all local devices when >1, "none" = single-device
    runner, or an integer count (use the first N local devices).
    platform: force a jax platform before backend init ("cpu" for CI /
    virtual meshes; empty = default, i.e. the TPU plugin).
    host_devices: when >0, force N virtual CPU host devices via XLA_FLAGS —
    the 8-device test-mesh recipe, exposed as config for CI parity.
    replicas: when >1, fold the device list into a ("replica", "shard")
    mesh — data replicated per slice, query stream data-parallel over
    replicas (SURVEY §2.9 strategy 3; the on-mesh ReplicaN analog).
    0 = auto multi-slice: one replica per TPU slice, so the data-plane
    psum stays on ICI and only per-query scalars cross slices on DCN
    (make_multislice_mesh).
    """
    devices: str = "auto"
    platform: str = ""
    host_devices: int = 0
    replicas: int = 1


@dataclass
class Config:
    data_dir: str = "~/.pilosa-tpu"
    bind: str = "localhost:10101"
    max_writes_per_request: int = 5000
    log_path: str = ""
    # "plain" (default) or "json": structured log lines carrying the
    # active trace id as a proper `trace` field (utils/logger.py)
    log_format: str = "plain"
    verbose: bool = False
    tls: TLSConfig = field(default_factory=TLSConfig)
    query: QueryConfig = field(default_factory=QueryConfig)
    qos: QosConfig = field(default_factory=QosConfig)
    slo: SLOConfig = field(default_factory=SLOConfig)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    storage: StorageConfig = field(default_factory=StorageConfig)
    ingest: IngestConfig = field(default_factory=IngestConfig)
    anti_entropy: AntiEntropyConfig = field(default_factory=AntiEntropyConfig)
    metric: MetricConfig = field(default_factory=MetricConfig)
    diagnostics: DiagnosticsConfig = field(default_factory=DiagnosticsConfig)
    tracing: TracingConfig = field(default_factory=TracingConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    gossip: GossipSection = field(default_factory=GossipSection)

    @property
    def host(self) -> str:
        return self.bind.rsplit(":", 1)[0] or "localhost"

    @property
    def port(self) -> int:
        tail = self.bind.rsplit(":", 1)
        return int(tail[1]) if len(tail) == 2 and tail[1] else 10101

    # -- merge layers -------------------------------------------------------

    def apply_toml(self, path: str) -> None:
        with open(path, "rb") as f:
            data = tomllib.load(f)
        self._apply_dict(data)

    def _apply_dict(self, data: dict) -> None:
        for key, value in data.items():
            attr = key.replace("-", "_")
            if attr in ("tls", "query", "qos", "slo", "cluster", "storage", "ingest", "anti_entropy", "metric", "diagnostics", "tracing", "mesh", "gossip") and isinstance(value, dict):
                sub = getattr(self, attr)
                for k, v in value.items():
                    sk = k.replace("-", "_")
                    if hasattr(sub, sk):
                        if isinstance(getattr(sub, sk), float) and isinstance(v, str):
                            v = parse_duration(v)  # toml/toml.go durations
                        setattr(sub, sk, v)
            elif hasattr(self, attr):
                setattr(self, attr, value)

    def apply_env(self, environ=None) -> None:
        environ = environ if environ is not None else os.environ
        prefix = "PILOSA_TPU_"
        for name, raw in environ.items():
            if not name.startswith(prefix):
                continue
            parts = name[len(prefix):].lower().split("_")
            self._set_path(parts, raw)

    def _set_path(self, parts: list[str], raw: str) -> None:
        # try sub-config first (cluster_replicas -> cluster.replicas)
        for sub_name in ("tls", "query", "qos", "slo", "cluster", "storage", "ingest", "anti_entropy", "metric", "diagnostics", "tracing", "mesh", "gossip"):
            sub_parts = sub_name.split("_")
            if parts[: len(sub_parts)] == sub_parts and len(parts) > len(sub_parts):
                sub = getattr(self, sub_name)
                attr = "_".join(parts[len(sub_parts):])
                if hasattr(sub, attr):
                    setattr(sub, attr, _coerce(raw, getattr(sub, attr)))
                return
        attr = "_".join(parts)
        if attr in ("tls", "query", "qos", "slo", "cluster", "storage",
                    "ingest", "anti_entropy", "metric", "diagnostics",
                    "tracing", "mesh", "gossip"):
            # a bare section name is never a config path — notably
            # PILOSA_TPU_QOS=0 and PILOSA_TPU_INGEST=0 are runtime kill
            # switches (read per call by pilosa_tpu/qos.py and
            # parallel/ingest.py), and coercing one here would clobber
            # the whole section object with a string
            return
        if hasattr(self, attr):
            setattr(self, attr, _coerce(raw, getattr(self, attr)))

    def to_toml(self) -> str:
        lines = [
            f'data-dir = "{self.data_dir}"',
            f'bind = "{self.bind}"',
            f"max-writes-per-request = {self.max_writes_per_request}",
            f'log-path = "{self.log_path}"',
            f'log-format = "{self.log_format}"',
            f"verbose = {str(self.verbose).lower()}",
            "",
            "[tls]",
            f'certificate = "{self.tls.certificate}"',
            f'key = "{self.tls.key}"',
            f"skip-verify = {str(self.tls.skip_verify).lower()}",
            "",
            "[cluster]",
            f"disabled = {str(self.cluster.disabled).lower()}",
            f"coordinator = {str(self.cluster.coordinator).lower()}",
            f"replicas = {self.cluster.replicas}",
            f"hosts = [{', '.join(repr(h) for h in self.cluster.hosts)}]",
            f"long-query-time = {self.cluster.long_query_time}",
            f"query-timeout = {self.cluster.query_timeout}",
            f"liveness-threshold = {self.cluster.liveness_threshold}",
            f"probe-timeout = {self.cluster.probe_timeout}",
            f"membership-interval = {self.cluster.membership_interval}",
            f"fanout-pool-size = {self.cluster.fanout_pool_size}",
            f"fanout-coalesce-window = {self.cluster.fanout_coalesce_window}",
            f"fanout-coalesce-max-batch = {self.cluster.fanout_coalesce_max_batch}",
            f"hedge-delay = {self.cluster.hedge_delay}",
            f'ici-serving = "{self.cluster.ici_serving}"',
            f'profile = "{self.cluster.profile}"',
            f"query-history-size = {self.cluster.query_history_size}",
            f"hint-max-bytes = {self.cluster.hint_max_bytes}",
            f"hint-max-age = {self.cluster.hint_max_age}",
            f"drain-timeout = {self.cluster.drain_timeout}",
            "",
            "[query]",
            f'plan = "{self.query.plan}"',
            f"plan-cache-bytes = {self.query.plan_cache_bytes}",
            f"sparse-threshold = {self.query.sparse_threshold}",
            f"run-threshold = {self.query.run_threshold}",
            "",
            "[qos]",
            f'mode = "{self.qos.mode}"',
            f'default-priority = "{self.qos.default_priority}"',
            f"default-deadline = {self.qos.default_deadline}",
            f"queries-per-s = {self.qos.queries_per_s}",
            f"device-ms-per-s = {self.qos.device_ms_per_s}",
            f"bytes-per-s = {self.qos.bytes_per_s}",
            f"burst = {self.qos.burst}",
            f"max-principals = {self.qos.max_principals}",
            *[line
              for pname, over in self.qos.principals.items()
              for line in (
                  "",
                  f'[qos.principals."{pname}"]',
                  *(f"{str(k).replace('_', '-')} = "
                    + (f'"{v}"' if isinstance(v, str) else str(v))
                    for k, v in over.items()))],
            "",
            "[slo]",
            f"read-latency-ms = {self.slo.read_latency_ms}",
            f"count-latency-ms = {self.slo.count_latency_ms}",
            f"topn-latency-ms = {self.slo.topn_latency_ms}",
            f"groupby-latency-ms = {self.slo.groupby_latency_ms}",
            f"latency-target = {self.slo.latency_target}",
            f"availability-target = {self.slo.availability_target}",
            f"burn-yellow = {self.slo.burn_yellow}",
            f"burn-red = {self.slo.burn_red}",
            f"window-short = {self.slo.window_short}",
            f"window-long = {self.slo.window_long}",
            "",
            "[storage]",
            f'wal-fsync = "{self.storage.wal_fsync}"',
            f'eviction = "{self.storage.eviction}"',
            "",
            "[ingest]",
            f"batch-window = {self.ingest.batch_window}",
            f"max-batch = {self.ingest.max_batch}",
            "",
            "[anti-entropy]",
            f"interval = {self.anti_entropy.interval}",
            f"jitter = {self.anti_entropy.jitter}",
            f"pace = {self.anti_entropy.pace}",
            f"max-blocks = {self.anti_entropy.max_blocks}",
            "",
            "[metric]",
            f'service = "{self.metric.service}"',
            f'host = "{self.metric.host}"',
            f"poll-interval = {self.metric.poll_interval}",
            f"telemetry-interval = {self.metric.telemetry_interval}",
            f"telemetry-ring = {self.metric.telemetry_ring}",
            f"usage-max-principals = {self.metric.usage_max_principals}",
            f"usage-ring = {self.metric.usage_ring}",
            f'trace-export = "{self.metric.trace_export}"',
            f'trace-export-path = "{self.metric.trace_export_path}"',
            f'trace-export-endpoint = "{self.metric.trace_export_endpoint}"',
            f'trace-export-format = "{self.metric.trace_export_format}"',
            f"trace-export-sample = {self.metric.trace_export_sample}",
            f"events-ring = {self.metric.events_ring}",
            f"events-spool = {self.metric.events_spool}",
            "",
            "[diagnostics]",
            f'url = "{self.diagnostics.url}"',
            f"interval = {self.diagnostics.interval}",
            "",
            "[tracing]",
            f'sampler-type = "{self.tracing.sampler_type}"',
            f"sampler-param = {self.tracing.sampler_param}",
            f'agent-host-port = "{self.tracing.agent_host_port}"',
            "",
            "[gossip]",
            f"port = {self.gossip.port}",
            f"seeds = [{', '.join(repr(h) for h in self.gossip.seeds)}]",
            f"period = {self.gossip.period}",
            f"probe-timeout = {self.gossip.probe_timeout}",
            f"push-pull-interval = {self.gossip.push_pull_interval}",
            f'secret = "{self.gossip.secret}"',
            "",
            "[mesh]",
            f'devices = "{self.mesh.devices}"',
            f'platform = "{self.mesh.platform}"',
            f"host-devices = {self.mesh.host_devices}",
            f"replicas = {self.mesh.replicas}",
        ]
        return "\n".join(lines) + "\n"


def _coerce(raw: str, current):
    if isinstance(current, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(current, int):
        return int(raw)
    if isinstance(current, float):
        return parse_duration(raw)
    if isinstance(current, list):
        return [s for s in raw.split(",") if s]
    return raw


def load_config(config_path=None, environ=None) -> Config:
    cfg = Config()
    if config_path:
        cfg.apply_toml(config_path)
    cfg.apply_env(environ)
    return cfg
