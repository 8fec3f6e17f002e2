#!/usr/bin/env python3
"""The benchmark's one command: run one cell of BENCHMARK.json once, in a
fresh process, and print the result as the last line of standard output.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name BENCHMARK.json
gives it (see benchmarks/README.md); nothing cell-specific lives here.

A run: this parent never touches JAX. It starts `python -m pilosa_tpu.cli
server` as a child on a generated TOML (default [mesh], default residency
budget and thresholds, wal-fsync as shipped) and makes the cell's data
from --seed with numpy meanwhile; refuses any platform but `tpu`
(--rehearse is the only way to a CPU run, which prints no device metric);
loads the data over POST /index/{i}/field/{f}/import-roaring/{shard};
checks one Set -> Count read-back on a field the window never queries;
sends the mix's warm-up requests; then drives POST /index/{i}/query
from closed-loop keep-alive clients for --seconds. set-up ends, and
`setup_s` is read, at the window's first request. After the window it
reads the device's peak memory, stops the server, and only then compares a
sample of the window's answers with the plain reference (lib/reference.py)
and, with --trace 1, reduces the device trace in a child of its own.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python gives it

import argparse  # noqa: E402
import base64  # noqa: E402
import collections  # noqa: E402
import concurrent.futures  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from lib import byfile, control, datagen, loadgen, peaks, proc  # noqa: E402
from lib import query, reference, roaring_wire, stats, work  # noqa: E402

READBACK_FIELD = "readback"   # a field of its own: no query of a mix names it
RUN_LIMIT_S = 1150.0          # the contract's 1200 s for a run that compiles
START_LIMIT_S = 300.0
TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    sys.stderr.write(f"[{time.perf_counter() - T_START:7.1f}s] {msg}\n")
    sys.stderr.flush()


def load_cell(name: str) -> tuple:
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(REPO, cfg_entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(HERE, "traffic", f"{cell['traffic']}.json")) as fh:
        mix = json.load(fh)
    return bench, cell, config, mix


def metrics_of(bench: dict, cell: dict, group: str) -> list:
    """The cell's metrics of a group: those that list it and those that
    list no cell; of the latter a per-layer metric only where the cell
    reports the end-to-end metric it moves."""
    mine = [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]
    if group == "per_layer":
        moved = {m["name"] for m in metrics_of(bench, cell, "end_to_end")}
        mine = [m for m in mine if m["moves"] in moved]
    return mine


def server_argv(cfg_path: str) -> list:
    return [sys.executable, "-m", "pilosa_tpu.cli", "server",
            "--config", cfg_path]


def load_data(http_port: int, index: str, data) -> dict:
    """DDL, then every (field, shard) as one roaring payload."""
    ctl = proc.Http("127.0.0.1", http_port, timeout=600.0)
    ctl.ok("POST", f"/index/{index}", {})
    for name in data.fields:
        ctl.ok("POST", f"/index/{index}/field/{name}",
               {"options": data.options[name]})
    ctl.ok("POST", f"/index/{index}/field/{READBACK_FIELD}",
           {"options": {"type": "set"}})
    ctl.close()
    def payload(job: tuple) -> tuple:
        field, shard = job
        rows = data.fields[field]
        return field, shard, roaring_wire.fragment_payload(
            [(r, rows[r].shard_piece(shard)) for r in sorted(rows)])

    # Payloads are made ahead on a few threads and sent one at a time: the
    # server drops a shard now and then when imports of one field arrive
    # together (PERF.md section 7, first row), and set-up is not the place
    # to meet that.
    sent = {"bytes": 0, "requests": 0}
    jobs = [(f, s) for s in range(data.n_shards) for f in data.fields]
    http = proc.Http("127.0.0.1", http_port, timeout=600.0)
    with concurrent.futures.ThreadPoolExecutor(max_workers=3) as pool:
        for field, shard, body in pool.map(payload, jobs):
            http.ok(
                "POST",
                f"/index/{index}/field/{field}/import-roaring/{shard}",
                {"views": {"standard": base64.b64encode(body).decode()}})
            sent["bytes"] += len(body)
            sent["requests"] += 1
    http.close()
    return sent


def readback(http: proc.Http, index: str, n_shards: int, seed: int) -> int:
    """Set one bit, then count it at once: read-your-writes on a field no
    mix queries. Returns |count - 1| (limit 0)."""
    col = int(np.random.default_rng([seed, 0x5E7]).integers(
        0, n_shards * datagen.SHARD_WIDTH))
    status, got = http.query(index, f"Set({col}, {READBACK_FIELD}=1)")
    if status != 200 or got != [True]:
        raise proc.BenchFailure(f"Set was not acknowledged: {status} {got}")
    status, got = http.query(index, f"Count(Row({READBACK_FIELD}=1))")
    if status != 200:
        raise proc.BenchFailure(f"read-back failed: {status} {got}")
    return abs(int(got[0]) - 1)


def capture_trace(port: int, seconds: float, out: dict) -> None:
    http = proc.Http("127.0.0.1", port, timeout=seconds + 240.0)
    try:
        out["doc"] = http.ok(
            "POST", f"/debug/device-profile?seconds={seconds}")
    except proc.BenchFailure as e:
        out["doc"] = {"status": "error", "error": str(e)}
    finally:
        http.close()


def reduce_trace(capture: dict) -> dict | None:
    """The trace reduction, in a child held to the CPU: the parent stays
    off JAX."""
    if not capture or capture.get("status") != "ok":
        log(f"no device trace: {capture}")
        return None
    files = glob.glob(os.path.join(capture["dir"], "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        log(f"no .xplane.pb under {capture['dir']}")
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "lib", "trace.py"), files[0]],
        env=env, capture_output=True, text=True, timeout=200)
    if res.returncode != 0:
        log(f"trace reduction failed: {res.stderr[-1500:]}")
        return None
    return json.loads(res.stdout.strip().splitlines()[-1])


def read_layer_metric(name: str, ctx: dict):
    return byfile.load("layer_metrics", name).read(ctx)


def compare(sample: list, ref: reference.Reference) -> list:
    """Indices of the sampled requests whose answer is not the
    reference's, each call by its own rule (lib/calls/). The same text is
    evaluated once; numpy's passes over a row's words run outside the
    interpreter's lock, so a few threads share the work."""
    asts = {s.req["pql"]: s.req["ast"] for s in sample}
    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
        want = dict(zip(asts, pool.map(
            lambda ast: query.answer(ref, ast), asts.values())))
    return [i for i, s in enumerate(sample)
            if not query.same(s.req["ast"], s.got, want[s.req["pql"]])]


def by_label(sent: list) -> dict:
    """Latencies by the label the generator gave each request."""
    groups: dict = {}
    for s in sent:
        groups.setdefault(s.req["label"], []).append(s.ms)

    def natural(label: str) -> list:
        return [int(t) if t.isdigit() else t
                for t in re.split(r"(\d+)", label)]
    return {label: {"n": len(v), "p50_ms": stats.percentile(v, 50),
                    "p95_ms": stats.percentile(v, 95)}
            for label, v in sorted(groups.items(),
                                   key=lambda kv: natural(kv[0]))}


def run_cell(args, make_server_argv=server_argv) -> int:
    bench, cell, config, mix = load_cell(args.workload)
    if not os.path.isdir(os.path.join(REPO, "pilosa_tpu")):
        sys.stderr.write("run.py: no pilosa_tpu/ beside benchmarks/: "
                         "nothing to measure\n")
        return 2
    want_platform = "cpu" if args.rehearse else "tpu"
    tmp = tempfile.mkdtemp(prefix="pilosa-bench-")
    kids = proc.Children(os.path.join(tmp, "logs"), cwd=REPO)
    watchdog = threading.Timer(
        RUN_LIMIT_S, lambda: (log("run limit passed; killing children"),
                              kids.kill_all(), os._exit(4)))
    watchdog.daemon = True
    watchdog.start()
    try:
        return _run(args, bench, cell, config, mix, want_platform, tmp,
                    kids, make_server_argv)
    except proc.BenchFailure as e:
        log(f"FAILED: {e}")
        return 2
    finally:
        kids.kill_all()
        watchdog.cancel()
        shutil.rmtree(tmp, ignore_errors=True)


def _run(args, bench, cell, config, mix, want_platform, tmp, kids,
         make_server_argv) -> int:
    index = config["index"]
    port = proc.free_port()
    cfg_path = os.path.join(tmp, "server.toml")
    with open(cfg_path, "w") as fh:
        fh.write(f'data-dir = "{os.path.join(tmp, "data")}"\n'
                 f'bind = "127.0.0.1:{port}"\n')
        if args.rehearse:  # the explicit rehearsal, never a default
            fh.write('[mesh]\nplatform = "cpu"\n')
    # The profile spool lands under the child's TMPDIR: keep it in ours.
    # JAX's persistent compile cache: a fixed directory inside the checkout
    # (the program's own default place), whatever the machine's
    # environment names, and with no size cap: a capped cache takes a file
    # lock on every read, and 32 request threads then time out on it.
    env = dict(os.environ, TMPDIR=tmp,
               JAX_COMPILATION_CACHE_DIR=os.path.join(REPO, ".jax_cache"))
    for name in ("BENCH_RUN", "JAX_COMPILATION_CACHE_MAX_SIZE"):
        env.pop(name, None)
    server = kids.start("server", make_server_argv(cfg_path), env)

    shards = args.shards or None
    data = datagen.make(config, args.seed, shards=shards)
    log(f"data: {data.n_shards} shards, "
        + ", ".join(f"{f} {len(rows)} rows" for f, rows in data.fields.items()))
    http = proc.Http("127.0.0.1", port)
    proc.wait_ready(http, server, kids, START_LIMIT_S)
    device = proc.device_of(http)
    log(f"server up on {device}")
    if device["platform"] != want_platform:
        raise proc.BenchFailure(
            f"server is on platform {device['platform']!r}, not "
            f"{want_platform!r}: no accelerator, no result")
    if not args.rehearse and device["count"] < cell["chips"]:
        raise proc.BenchFailure(
            f"cell needs {cell['chips']} chips, JAX found {device['count']}")

    t0 = time.perf_counter()
    loaded = load_data(port, index, data)
    load_s = time.perf_counter() - t0
    log(f"loaded {loaded['requests']} payloads, {loaded['bytes']} bytes "
        f"in {load_s:.1f}s")
    readback_gap = readback(http, index, data.n_shards, args.seed)

    gen = byfile.load("lib/generators", mix.get("generator")).Traffic(
        mix, data, args.seed)
    t0 = time.perf_counter()
    it = iter(gen.warmup())
    lock = threading.Lock()

    def next_warm():
        with lock:
            return next(it, None)

    warmed = loadgen.drive("127.0.0.1", port, index, mix["clients"],
                           next_warm, None, grace_s=RUN_LIMIT_S)[0]
    bad = [s for s in warmed if s.status != 200]
    if bad:
        raise proc.BenchFailure(
            f"{len(bad)} of {len(warmed)} warm-up requests failed, first: "
            f"{bad[0].req['pql']} -> {bad[0].status} {str(bad[0].got)[:300]}")
    warm_s = time.perf_counter() - t0
    log(f"warmed {len(warmed)} requests in {warm_s:.1f}s; slowest: "
        + "; ".join(f"{s.ms / 1e3:.1f}s {s.req['pql'][:60]}" for s in
                    sorted(warmed, key=lambda s: -s.ms)[:4]))

    vars_before = http.ok("GET", "/debug/vars")
    compiles_before = proc.jit_compiles(http)
    log(f"programs traced in set-up: {sum(compiles_before.values())}")
    capture: dict = {}
    tracer = None
    if args.trace:
        tracer = threading.Thread(target=capture_trace, args=(
            port, min(TRACE_SECONDS, args.seconds / 2), capture))
    setup_s = time.perf_counter() - T_START
    if tracer:
        threading.Timer(min(1.0, args.seconds / 4), tracer.start).start()
    sent, window_s = loadgen.drive("127.0.0.1", port, index, mix["clients"],
                                   gen.take, args.seconds)
    if tracer:
        tracer.join()
    log(f"window: {len(sent)} requests in {window_s:.1f}s")
    vars_after = http.ok("GET", "/debug/vars")
    compiled = {k: v - compiles_before.get(k, 0)
                for k, v in proc.jit_compiles(http).items()
                if v > compiles_before.get(k, 0)}
    window_compiles = sum(compiled.values())
    log(f"programs first traced inside the window: {window_compiles} "
        f"{compiled}")
    memory_peak = proc.memory_peak_bytes(http)
    http.close()
    code = kids.stop(server, grace=30.0)
    log(f"server stopped with code {code}")
    if args.logs:
        shutil.copytree(kids.log_dir, args.logs, dirs_exist_ok=True)

    # -- correct: a sample of the window's answers against the reference ---
    t_open = sent[0].t_send if sent else 0.0
    failed_http = [s for s in sent if s.status != 200]
    answered = [s for s in sent if s.status == 200]
    rng = np.random.default_rng([args.seed, 0x5A3F])
    n_check = min(len(answered), mix["check_sample"])
    picks = set(rng.choice(len(answered), size=n_check,
                           replace=False).tolist()) if n_check else set()
    if answered:  # the slowest request is always looked at
        picks.add(max(range(len(answered)), key=lambda i: answered[i].ms))
    sample = [answered[i] for i in sorted(picks)]
    t0 = time.perf_counter()
    count_fn = (control.sampled_count(data.n_shards) if args.control
                else reference.exact_count)
    wrong = compare(sample, reference.Reference(data, count_fn))
    check_s = time.perf_counter() - t0
    compared = {
        "wrong_answers": {"value": len(wrong), "limit": 0},
        "http_failures": {"value": len(failed_http), "limit": 0},
        "readback_count_gap": {"value": readback_gap, "limit": 0},
        "answers_checked": {"value": len(sample),
                            "at_least": min(mix["check_sample"],
                                            mix["check_min"])},
    }
    correct = (not wrong and not failed_http and readback_gap == 0
               and len(sample) >= compared["answers_checked"]["at_least"])
    log(f"compared {len(sample)} answers with the reference in "
        f"{check_s:.1f}s ({'control' if args.control else 'reference'})")
    for i in wrong[:5]:
        s = sample[i]
        log(f"  WRONG {s.req['pql']}: got {str(s.got)[:200]}")
    for s in failed_http[:5]:
        log(f"  FAILED {s.req['pql']}: {s.status} {str(s.got)[:200]}")

    # -- metrics ----------------------------------------------------------
    done_in_window = [s for s in answered
                      if s.t_send + s.ms / 1e3 <= t_open + window_s]
    per_second = [0] * (int(window_s) + 1)
    for s in done_in_window:
        per_second[int(s.t_send + s.ms / 1e3 - t_open)] += 1
    values: dict = {}
    extra: dict = {"answered_per_second": per_second[:int(window_s)],
                   "setup": {"load_s": load_s, "warmup_s": warm_s,
                             "payload_bytes": loaded["bytes"]},
                   "check_s": check_s, "window_requests": len(sent),
                   "checked_by_label": dict(collections.Counter(
                       s.req["label"] for s in sample)),
                   "wrong_by_label": dict(collections.Counter(
                       sample[i].req["label"] for i in wrong)),
                   "window_compiles": window_compiles,
                   "window_compiles_by_family": compiled}
    if not args.trace:
        ms = [s.ms for s in sent]
        if ms:
            values["query_p50_ms"] = stats.percentile(ms, 50)
            values["query_p95_ms"] = stats.percentile(ms, 95)
        values["queries_per_s"] = (len(done_in_window) - len(wrong)) / window_s
        values["setup_s"] = setup_s
        extra[gen.label_key] = by_label(sent)
        wanted = metrics_of(bench, cell, "end_to_end")
    else:
        trace = reduce_trace(capture.get("doc"))
        ctx = {"vars_before": vars_before, "vars_after": vars_after,
               "requests": len(sent), "latencies_ms": [s.ms for s in sent],
               "trace": trace,
               "window_compiles": window_compiles,
               "traced_bytes_needed": 0, "peaks": None}
        if device["platform"] != "cpu":
            ctx["peaks"] = peaks.peaks_of(device["kind"])
        if trace and trace.get("profile_start_ns"):
            lo = trace["profile_start_ns"] / 1e9
            hi = trace["profile_stop_ns"] / 1e9
            need = work.Work(data)
            ctx["traced_bytes_needed"] = sum(
                query.bytes_needed(need, s.req["ast"]) for s in answered
                if lo <= s.wall_send + s.ms / 1e3 <= hi)
        wanted = metrics_of(bench, cell, "per_layer")
        for m in wanted:
            if m["source"] == "device_trace" and device["platform"] == "cpu":
                continue  # a CPU run never prints a device metric
            got = read_layer_metric(m["name"], ctx)
            if got is not None:
                values[m["name"]] = got
        if trace:
            extra["trace"] = {k: trace[k] for k in
                              ("window_s", "busy_s", "op_sum_s", "devices",
                               "lines")}
    units = {m["name"]: m["unit"] for m in wanted}
    result = {
        "correct": bool(correct),
        "attempted": len(sent),
        "failed": len(failed_http) + len(wrong),
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items() if k in units},
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"],
                   "memory_peak_bytes": memory_peak},
    }
    if args.trace and device["platform"] != "cpu" and trace:
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if args.rehearse:
        # a CPU run's numbers never stand under a metric's name
        result["rehearsal_metrics"] = result["metrics"]
        result["metrics"] = {}
    result["workload"] = cell["name"]
    result["shards"] = data.n_shards
    result["seed"] = args.seed
    result["extra"] = extra
    result["compared"] = compared
    for k, v in compared.items():
        log(f"compared {k}: {json.dumps(v)}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU backend, to debug the harness: "
                         "the result says platform=cpu and holds no "
                         "device metric")
    ap.add_argument("--shards", type=int, default=0,
                    help="another scale than the configuration's: for the "
                         "rehearsal, the tests and sizing a new cell; "
                         "BENCHMARK.json's command never passes it")
    ap.add_argument("--logs", default="",
                    help="copy the server's output into this directory")
    ap.add_argument("--control", action="store_true",
                    help="compare the control's answers (lib/control.py) "
                         "in the server's place: has to read not correct")
    args = ap.parse_args()
    return run_cell(args)


if __name__ == "__main__":
    sys.exit(main())
