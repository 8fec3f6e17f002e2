"""`pilosa-tpu` command family: server / import / export / inspect / check /
config / generate-config / advise.

Reference: cmd/*.go (cobra subcommands), ctl/*.go (implementations).
"""

from __future__ import annotations

import argparse
import csv
import json
import signal
import sys
import threading
import urllib.request

from pilosa_tpu import __version__
from pilosa_tpu.cli.config import Config, load_config



def _gossip_config(cfg: Config):
    """SWIM clock from the [gossip] section."""
    from pilosa_tpu.parallel.gossip import GossipConfig
    return GossipConfig(period=cfg.gossip.period,
                        probe_timeout=cfg.gossip.probe_timeout,
                        push_pull_interval=cfg.gossip.push_pull_interval)

def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="pilosa-tpu",
                                description="TPU-native distributed bitmap index")
    p.add_argument("--version", action="version", version=__version__)
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("server", help="run a node")
    sp.add_argument("--config", help="TOML config file")
    sp.add_argument("--data-dir", help="data directory")
    sp.add_argument("--bind", help="host:port to listen on")
    sp.add_argument("--cluster-hosts", help="comma-separated peer URIs")
    sp.add_argument("--cluster-replicas", type=int, help="replica count")
    sp.add_argument("--anti-entropy-interval", type=float,
                    help="seconds between anti-entropy passes (0 = off)")
    sp.add_argument("--wal-fsync", choices=["off", "always"],
                    help="fsync the WAL per acked op ([storage] wal-fsync; "
                         "the PILOSA_TPU_WAL_FSYNC env var overrides both)")
    sp.add_argument("--join", action="store_true",
                    help="join an existing cluster via --cluster-hosts seeds "
                         "(triggers a coordinator resize)")
    sp.add_argument("--mesh-devices",
                    help="device mesh: auto (all local devices when >1), "
                         "none, or an integer count")
    sp.add_argument("--log-format", choices=["plain", "json"],
                    help="log line format; json carries trace=<id> as a "
                         "proper field so logs join the query-history/"
                         "profile surfaces mechanically")
    sp.add_argument("--verbose", action="store_true")

    ip = sub.add_parser("import", help="bulk-import CSV (row,col or col,value)")
    ip.add_argument("--host", default="http://localhost:10101")
    ip.add_argument("--index", required=True)
    ip.add_argument("--field", required=True)
    ip.add_argument("--field-type", default="set", choices=["set", "int"])
    ip.add_argument("--create", action="store_true",
                    help="create index/field if missing")
    ip.add_argument("--batch-size", type=int, default=100000)
    ip.add_argument("--clear", action="store_true",
                    help="clear the imported bits instead of setting them")
    ip.add_argument("--min", type=int, default=0)
    ip.add_argument("--max", type=int, default=0)
    ip.add_argument("files", nargs="+")

    ep = sub.add_parser("export", help="export a field as CSV")
    ep.add_argument("--host", default="http://localhost:10101")
    ep.add_argument("--index", required=True)
    ep.add_argument("--field", required=True)
    ep.add_argument("-o", "--output", help="output file (default stdout)")

    np_ = sub.add_parser("inspect", help="dump fragment file stats offline")
    np_.add_argument("path")

    cp = sub.add_parser("check", help="integrity-check fragment files offline")
    cp.add_argument("paths", nargs="+")

    cfgp = sub.add_parser("config", help="print parsed config")
    cfgp.add_argument("--config", help="TOML config file")

    sub.add_parser("generate-config", help="print default TOML config")

    ap = sub.add_parser(
        "advise", help="fetch the fragment heat map and print the "
                       "placement advisor's dry-run recommendations")
    ap.add_argument("--host", default="http://localhost:10101")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print the raw advice document instead of the "
                         "rendered report")

    tl = sub.add_parser(
        "timeline", help="fetch the merged cluster event timeline "
                         "(GET /cluster/events) and render it as an "
                         "incident timeline with health-transition "
                         "annotations")
    tl.add_argument("--host", default="http://localhost:10101")
    tl.add_argument("--limit", type=int, default=0,
                    help="newest N events only (0 = everything retained)")
    tl.add_argument("--type", dest="etype",
                    help="only events of this registered type")
    tl.add_argument("--node", help="only events recorded by this node id")
    tl.add_argument("--json", action="store_true", dest="as_json",
                    help="print the raw merged document instead of the "
                         "rendered timeline")

    pcap = sub.add_parser(
        "profile-capture", help="capture an on-demand XLA device profile "
                                "on a live node (POST /debug/device-"
                                "profile) and print the spool path")
    pcap.add_argument("--host", default="http://localhost:10101")
    pcap.add_argument("--seconds", type=float, default=2.0,
                      help="trace window length (clamped server-side)")
    pcap.add_argument("--json", action="store_true", dest="as_json",
                      help="print the raw capture document")
    return p


# ---------------------------------------------------------------------------


def cmd_server(args) -> int:
    # SIGUSR1 dumps every thread's stack to stderr (hung-server triage —
    # the /debug/pprof analog when HTTP itself is wedged)
    import faulthandler
    faulthandler.register(signal.SIGUSR1, all_threads=True)
    try:
        cfg = load_config(args.config)
    except (OSError, ValueError) as e:
        raise SystemExit(f"error: loading config: {e}")
    if args.data_dir:
        cfg.data_dir = args.data_dir
    if args.bind:
        cfg.bind = args.bind
    if args.cluster_hosts:
        cfg.cluster.hosts = args.cluster_hosts.split(",")
        cfg.cluster.disabled = False
    if args.cluster_replicas is not None:
        cfg.cluster.replicas = args.cluster_replicas
    if args.anti_entropy_interval is not None:
        cfg.anti_entropy.interval = args.anti_entropy_interval
    if getattr(args, "wal_fsync", None):
        cfg.storage.wal_fsync = args.wal_fsync
    if getattr(args, "mesh_devices", None):
        cfg.mesh.devices = args.mesh_devices
    if getattr(args, "log_format", None):
        cfg.log_format = args.log_format

    import os
    from pilosa_tpu.parallel.mesh import mesh_from_config
    from pilosa_tpu.server import Server
    data_dir = os.path.expanduser(cfg.data_dir)
    # build the device mesh BEFORE anything else touches the backend —
    # platform forcing / virtual-device flags only apply at backend init
    # (SURVEY §2.9 strategy 2: shard slabs partition over local chips)
    try:
        mesh = mesh_from_config(devices=cfg.mesh.devices,
                                platform=cfg.mesh.platform,
                                host_devices=cfg.mesh.host_devices,
                                replicas=cfg.mesh.replicas)
    except ValueError as e:
        raise SystemExit(f"error: building device mesh: {e}")
    server = Server(
        data_dir, host=cfg.host, port=cfg.port, mesh=mesh,
        cluster_hosts=cfg.cluster.hosts if not cfg.cluster.disabled else None,
        replica_n=cfg.cluster.replicas,
        liveness_threshold=cfg.cluster.liveness_threshold,
        probe_timeout=cfg.cluster.probe_timeout,
        membership_interval=cfg.cluster.membership_interval,
        anti_entropy_interval=cfg.anti_entropy.interval,
        anti_entropy_jitter=cfg.anti_entropy.jitter,
        anti_entropy_pace=cfg.anti_entropy.pace,
        anti_entropy_max_blocks=cfg.anti_entropy.max_blocks,
        wal_fsync=cfg.storage.wal_fsync,
        eviction=cfg.storage.eviction,
        ingest_batch_window=cfg.ingest.batch_window,
        ingest_max_batch=cfg.ingest.max_batch,
        join=getattr(args, "join", False),
        long_query_time=cfg.cluster.long_query_time,
        query_timeout=cfg.cluster.query_timeout,
        fanout_pool_size=cfg.cluster.fanout_pool_size,
        fanout_coalesce_window=cfg.cluster.fanout_coalesce_window,
        fanout_coalesce_max_batch=cfg.cluster.fanout_coalesce_max_batch,
        hedge_delay=cfg.cluster.hedge_delay,
        ici_serving=cfg.cluster.ici_serving,
        profile_mode=cfg.cluster.profile,
        query_history_size=cfg.cluster.query_history_size,
        hint_max_bytes=cfg.cluster.hint_max_bytes,
        hint_max_age=cfg.cluster.hint_max_age,
        drain_timeout=cfg.cluster.drain_timeout,
        plan=cfg.query.plan,
        plan_cache_bytes=cfg.query.plan_cache_bytes,
        sparse_threshold=cfg.query.sparse_threshold,
        run_threshold=cfg.query.run_threshold,
        max_writes_per_request=cfg.max_writes_per_request,
        metric_service=cfg.metric.service,
        metric_host=cfg.metric.host,
        metric_poll_interval=cfg.metric.poll_interval,
        telemetry_interval=cfg.metric.telemetry_interval,
        telemetry_ring=cfg.metric.telemetry_ring,
        usage_max_principals=cfg.metric.usage_max_principals,
        usage_ring=cfg.metric.usage_ring,
        trace_export=cfg.metric.trace_export,
        trace_export_path=cfg.metric.trace_export_path,
        trace_export_endpoint=cfg.metric.trace_export_endpoint,
        trace_export_format=cfg.metric.trace_export_format,
        trace_export_sample=cfg.metric.trace_export_sample,
        events_ring=cfg.metric.events_ring,
        events_spool=cfg.metric.events_spool,
        slo_read_latency_ms=cfg.slo.read_latency_ms,
        slo_count_latency_ms=cfg.slo.count_latency_ms,
        slo_topn_latency_ms=cfg.slo.topn_latency_ms,
        slo_groupby_latency_ms=cfg.slo.groupby_latency_ms,
        slo_latency_target=cfg.slo.latency_target,
        slo_availability_target=cfg.slo.availability_target,
        slo_burn_yellow=cfg.slo.burn_yellow,
        slo_burn_red=cfg.slo.burn_red,
        slo_window_short=cfg.slo.window_short,
        slo_window_long=cfg.slo.window_long,
        qos_mode=cfg.qos.mode,
        qos_default_priority=cfg.qos.default_priority,
        qos_default_deadline=cfg.qos.default_deadline,
        qos_queries_per_s=cfg.qos.queries_per_s,
        qos_device_ms_per_s=cfg.qos.device_ms_per_s,
        qos_bytes_per_s=cfg.qos.bytes_per_s,
        qos_burst=cfg.qos.burst,
        qos_max_principals=cfg.qos.max_principals,
        qos_principals=cfg.qos.principals,
        gossip_secret=cfg.gossip.secret,
        log_format=cfg.log_format,
        diagnostics_url=cfg.diagnostics.url,
        diagnostics_interval=cfg.diagnostics.interval,
        tls_certificate=cfg.tls.certificate,
        tls_key=cfg.tls.key,
        tls_skip_verify=cfg.tls.skip_verify,
        gossip_port=cfg.gossip.port if cfg.gossip.port >= 0 else None,
        gossip_seeds=cfg.gossip.seeds,
        gossip_config=_gossip_config(cfg),
        tracing_sampler_type=cfg.tracing.sampler_type,
        tracing_sampler_param=cfg.tracing.sampler_param,
        tracing_endpoint=cfg.tracing.agent_host_port,
    ).open()
    # name the device actually serving: a server that came up on the CPU
    # because of an exported JAX_PLATFORMS must say so on its first line
    import jax
    from pilosa_tpu import native
    devs = list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
    print(f"pilosa-tpu {__version__} serving at {server.uri} "
          f"(data: {data_dir}, node: {server.node_id}, "
          f"platform: {devs[0].platform}, device_kind: "
          f"{devs[0].device_kind!r}, devices: {len(devs)}, native storage: "
          f"{'loaded' if native.available() else 'NOT loaded (numpy path)'})",
          flush=True)

    stop = threading.Event()
    # SIGTERM = graceful drain (the deploy/rolling-restart path): shed new
    # queries, let in-flight work finish, flush queues, land a final
    # snapshot — then exit. A SECOND signal skips the remaining drain and
    # stops immediately (the kill -9 escape hatch that still closes
    # cleanly). SIGINT (^C) behaves the same for interactive parity.
    signals_seen = []

    def _sig(_s, _f):
        signals_seen.append(_s)
        if len(signals_seen) > 1:
            server._drain_abort.set()  # cut the drain short, exit now
        stop.set()

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    try:
        stop.wait()
        if not server._drain_abort.is_set():
            print("draining (send another signal to skip)...", flush=True)
            server.drain()
    finally:
        if mesh is not None:
            # the last word on the rule for collectives (docs/operations.md
            # "One node, several chips"): /debug/vars `mesh` as it stood
            print("mesh: " + json.dumps(server.runner.mesh_snapshot()),
                  flush=True)
        server.close()
    return 0


def _post(host: str, path: str, payload=None, raw=None) -> dict:
    body = raw if raw is not None else json.dumps(payload or {}).encode()
    req = urllib.request.Request(host + path, data=body, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            out = resp.read()
            return json.loads(out) if out else {}
    except urllib.error.HTTPError as e:
        detail = e.read().decode(errors="replace")
        raise SystemExit(f"error: {path}: {e.code}: {detail}")


def cmd_import(args) -> int:
    if args.create:
        _post_tolerant(args.host, f"/index/{args.index}")
        opts = {"options": {"type": args.field_type}}
        if args.field_type == "int":
            opts["options"].update(min=args.min, max=args.max)
        _post_tolerant(args.host, f"/index/{args.index}/field/{args.field}", opts)

    total = 0
    batch_a, batch_b = [], []

    def flush():
        nonlocal total
        if not batch_a:
            return
        if args.field_type == "int":
            payload = {"columnIDs": batch_a, "values": batch_b}
        else:
            payload = {"rowIDs": batch_a, "columnIDs": batch_b}
            if args.clear:
                payload["clear"] = True
        _post(args.host, f"/index/{args.index}/field/{args.field}/import", payload)
        total += len(batch_a)
        batch_a.clear()
        batch_b.clear()

    for fname in args.files:
        fh = sys.stdin if fname == "-" else open(fname)
        with fh:
            for rowno, row in enumerate(csv.reader(fh), 1):
                if not row:
                    continue
                if len(row) < 2:
                    raise SystemExit(f"error: {fname}:{rowno}: expected 2+ columns")
                batch_a.append(int(row[0]))
                batch_b.append(int(row[1]))
                if len(batch_a) >= args.batch_size:
                    flush()
    flush()
    print(f"imported {total} records into {args.index}/{args.field}")
    return 0


def _post_tolerant(host: str, path: str, payload=None) -> None:
    """POST ignoring 409 conflict (create-if-not-exists)."""
    req = urllib.request.Request(host + path,
                                 data=json.dumps(payload or {}).encode(),
                                 method="POST")
    try:
        urllib.request.urlopen(req, timeout=60).read()
    except urllib.error.HTTPError as e:
        if e.code != 409:
            raise SystemExit(f"error: {path}: {e.code}: {e.read().decode(errors='replace')}")


def cmd_export(args) -> int:
    # discover shards, then stream each via /export
    with urllib.request.urlopen(args.host + "/internal/shards/max", timeout=60) as resp:
        max_shards = json.loads(resp.read())["standard"]
    out = open(args.output, "w") if args.output else sys.stdout
    try:
        for shard in range(max_shards.get(args.index, 0) + 1):
            url = (f"{args.host}/export?index={args.index}"
                   f"&field={args.field}&shard={shard}")
            with urllib.request.urlopen(url, timeout=60) as resp:
                out.write(resp.read().decode())
    finally:
        if args.output:
            out.close()
    return 0


def cmd_inspect(args) -> int:
    from pilosa_tpu.storage.roaring import Bitmap
    with open(args.path, "rb") as f:
        data = f.read()
    b = Bitmap.from_bytes(data)
    kinds = {}
    for c in b.containers.values():
        kinds[c.kind] = kinds.get(c.kind, 0) + 1
    print(json.dumps({
        "path": args.path,
        "bytes": len(data),
        "bits": b.count(),
        "containers": len(b.containers),
        "containerKinds": kinds,
        "opN": b.op_n,
        "min": b.min(),
        "max": b.max(),
    }, indent=2))
    return 0


def cmd_check(args) -> int:
    from pilosa_tpu.storage.hints import HINT_MAGIC, verify_hint_log
    from pilosa_tpu.storage.roaring import Bitmap
    failed = 0
    for path in args.paths:
        try:
            # hint logs (".hints" files / 0xFB lead byte) get framing
            # validation; everything else is a fragment/roaring file
            with open(path, "rb") as f:
                lead = f.read(1)
            if path.endswith(".hints") or (
                    lead and lead[0] == HINT_MAGIC):
                rep = verify_hint_log(path)
                if rep["error"]:
                    failed += 1
                    print(f"{path}: FAILED: hint log damaged at byte "
                          f"{rep['validBytes']}/{rep['bytes']} "
                          f"({rep['error']}); {rep['records']} valid "
                          f"record(s) precede the damage")
                else:
                    print(f"{path}: OK ({rep['records']} hint record(s), "
                          f"{rep['droppedMarkers']} drop marker(s))")
                continue
            with open(path, "rb") as f:
                b = Bitmap.from_bytes(f.read())
            b.check()
            print(f"{path}: OK ({b.count()} bits)")
        except (ValueError, OSError) as e:
            failed += 1
            print(f"{path}: FAILED: {e}")
    return 1 if failed else 0


def cmd_config(args) -> int:
    cfg = load_config(getattr(args, "config", None))
    print(cfg.to_toml(), end="")
    return 0


def cmd_generate_config(_args) -> int:
    print(Config().to_toml(), end="")
    return 0


def cmd_advise(args) -> int:
    """`pilosa-tpu advise`: the node's fragment heat map run through the
    placement advisor (GET /debug/heat?advice=true) — the same dry-run
    recommendations /debug/heat serves, rendered for a terminal."""
    from pilosa_tpu.analysis.advisor import render_advice
    url = args.host + "/debug/heat?advice=true&top=0"
    try:
        with urllib.request.urlopen(url, timeout=30) as resp:
            doc = json.loads(resp.read())
    except (OSError, ValueError) as e:
        raise SystemExit(f"error: fetching {url}: {e}")
    if not doc.get("enabled", False) and not doc.get("trackedFragments"):
        print("heat tracking is disabled or has no data yet "
              "(PILOSA_TPU_HEAT=0, or no traffic)")
        return 1
    advice = doc.get("advice") or {}
    if args.as_json:
        print(json.dumps(advice, indent=2, sort_keys=True))
    else:
        print(render_advice(advice))
    return 0


def render_timeline(doc: dict, node: "str | None" = None,
                    etype: "str | None" = None) -> str:
    """Render a /cluster/events document as a terminal incident
    timeline: one line per event in merged HLC order — local time from
    the stamp's physical half, a short node id, the type, and the
    event's own fields. health.transition lines are called out with a
    marker and an explicit from→to annotation so "when did B go yellow"
    is answerable by eye."""
    import datetime

    lines = []
    nodes = {n["id"]: n for n in doc.get("nodes", [])}
    legacy = sorted(i for i, n in nodes.items()
                    if n.get("status") == "legacy")
    events = doc.get("events", [])
    if node:
        events = [e for e in events if e.get("node") == node]
    if etype:
        events = [e for e in events if e.get("type") == etype]
    skip = {"hlc", "ts", "type", "node", "seq"}
    for e in events:
        hlc = e.get("hlc") or [0, 0]
        try:
            when = datetime.datetime.fromtimestamp(
                hlc[0] / 1000.0).strftime("%H:%M:%S.%f")[:-3]
        except (OSError, OverflowError, ValueError):
            when = "??:??:??"
        stamp = f"{when}+{hlc[1]}" if hlc[1] else when
        nid = str(e.get("node", "?"))[:8]
        fields = " ".join(f"{k}={e[k]}" for k in sorted(e)
                          if k not in skip)
        if e.get("type") == "health.transition":
            arrow = (f"{e.get('fromScore', '?')} -> "
                     f"{e.get('toScore', '?')}")
            reasons = "; ".join(e.get("reasons") or [])
            lines.append(f"{stamp}  {nid}  ** HEALTH {arrow}"
                         + (f" ({reasons})" if reasons else ""))
        else:
            lines.append(f"{stamp}  {nid}  {e.get('type')}"
                         + (f"  {fields}" if fields else ""))
    head = [f"cluster timeline: {len(events)} event(s) across "
            f"{len(nodes)} node(s), HLC-merged (causal order; "
            f"+N = logical tiebreak)"]
    if legacy:
        head.append(f"note: legacy peer(s) without /debug/events "
                    f"(no events contributed): {', '.join(legacy)}")
    return "\n".join(head + [""] + lines)


def cmd_timeline(args) -> int:
    """`pilosa-tpu timeline`: the merged cluster incident timeline
    (GET /cluster/events — every node's flight-recorder feed, HLC-sorted
    into one causal stream), rendered for a terminal."""
    url = args.host + "/cluster/events"
    if args.limit:
        url += f"?limit={args.limit}"
    try:
        with urllib.request.urlopen(url, timeout=30) as resp:
            doc = json.loads(resp.read())
    except (OSError, ValueError) as e:
        raise SystemExit(f"error: fetching {url}: {e}")
    if args.as_json:
        print(json.dumps(doc, indent=2, sort_keys=True))
    else:
        print(render_timeline(doc, node=args.node, etype=args.etype))
    return 0


def cmd_profile_capture(args) -> int:
    """`pilosa-tpu profile-capture`: wrap ?seconds= of the node's live
    traffic in jax.profiler.trace (POST /debug/device-profile) and print
    where the capture spooled. "disabled" (PILOSA_TPU_DEVICE_PROFILE=0)
    and "busy" (a capture is already running) are reported, not
    errored — the node never blocks serving for a profile."""
    url = f"{args.host}/debug/device-profile?seconds={args.seconds:g}"
    try:
        req = urllib.request.Request(url, data=b"", method="POST")
        with urllib.request.urlopen(req, timeout=args.seconds + 30) as resp:
            doc = json.loads(resp.read())
    except (OSError, ValueError) as e:
        raise SystemExit(f"error: capturing via {url}: {e}")
    if args.as_json:
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 0 if doc.get("status") == "ok" else 1
    status = doc.get("status", "?")
    if status == "ok":
        print(f"captured {doc.get('seconds')}s device profile "
              f"({doc.get('bytes', 0)} bytes) -> {doc.get('dir')}")
        print("open with: tensorboard --logdir "
              + str(doc.get("spoolDir", doc.get("dir"))))
        return 0
    print(f"capture not taken: {status}"
          + (f" ({doc.get('error')})" if doc.get("error") else ""))
    return 1


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    handler = {
        "server": cmd_server,
        "import": cmd_import,
        "export": cmd_export,
        "inspect": cmd_inspect,
        "check": cmd_check,
        "config": cmd_config,
        "generate-config": cmd_generate_config,
        "advise": cmd_advise,
        "timeline": cmd_timeline,
        "profile-capture": cmd_profile_capture,
    }[args.command]
    return handler(args)


if __name__ == "__main__":
    sys.exit(main())
