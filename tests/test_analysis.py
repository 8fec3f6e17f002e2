"""pilosa-lint + runtime lock-order witness (tier-1).

Three layers:

* rule units — each lint rule against synthetic sources, positive and
  negative;
* the tree gate — `run_all(repo root)` must return ZERO findings (the
  committed baseline is empty and stays empty), plus the
  `python -m pilosa_tpu.analysis --check` CLI contract (exit 0 on the
  clean tree, exit 1 on an injected violation);
* the witness — an induced A→B / B→A inversion and a lock held across a
  fake RPC must both be detected with the offending stacks; reentrant
  RLocks, Condition/Event integration and consistent orders must stay
  silent; and the live suite (witnessed via conftest) must stay clean
  through a real server query.

Plus the thread-boundary contextvar regression tests: a profiled query's
trace/principal/deadline/priority must survive every background hop now
that all spawn sites route through utils.threads (enforced by the
`ctx-thread` rule over the tree).
"""

import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from pilosa_tpu.analysis import (config_knob_findings, env_gate_findings,
                                 lockwitness, run_all)
from pilosa_tpu.analysis.lint import lint_source
from pilosa_tpu.utils import accounting, qctx, threads, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------- lint units


def test_lint_flags_raw_thread_and_timer():
    src = ("import threading\n"
           "t = threading.Thread(target=print)\n"
           "threading.Timer(1.0, print).start()\n")
    fs = lint_source("pilosa_tpu/x.py", src)
    assert [f.rule for f in fs] == ["ctx-thread", "ctx-thread"]
    assert fs[0].line == 2 and fs[1].line == 3


def test_lint_flags_from_import_thread_alias():
    src = ("from threading import Thread as T\n"
           "T(target=print).start()\n")
    assert rules(lint_source("pilosa_tpu/x.py", src)) == ["ctx-thread"]


def test_lint_allows_threads_wrapper_module():
    src = "import threading\nt = threading.Thread(target=print)\n"
    assert lint_source("pilosa_tpu/utils/threads.py", src) == []


def test_lint_submit_rule():
    bad = "fut = self._fanout_pool.submit(fn, 1)\n"
    good = ("import contextvars\n"
            "fut = pool.submit(contextvars.copy_context().run, fn, 1)\n")
    not_a_pool = "out = self.submit(key, payload)\n"  # batcher protocol
    assert rules(lint_source("pilosa_tpu/x.py", bad)) == ["ctx-submit"]
    assert lint_source("pilosa_tpu/x.py", good) == []
    assert lint_source("pilosa_tpu/x.py", not_a_pool) == []


def test_lint_swallowed_future():
    bad = "pool.submit(contextvars.copy_context().run, fn)\n"
    good = "fut = pool.submit(contextvars.copy_context().run, fn)\n"
    assert rules(lint_source("pilosa_tpu/x.py",
                             "import contextvars\n" + bad)) \
        == ["swallowed-future"]
    assert lint_source("pilosa_tpu/x.py",
                       "import contextvars\n" + good) == []


def test_lint_wall_clock_rule():
    bad = "import time\ndeadline = time.time() + 5\n"
    same_line = "import time\nts = time.time()  # wall-clock: serialized\n"
    prev_line = ("import time\n"
                 "# wall-clock: export timestamps\n"
                 "ts = time.time()\n")
    monotonic = "import time\nd = time.monotonic() + 5\n"
    assert rules(lint_source("pilosa_tpu/x.py", bad)) == ["wall-clock"]
    assert lint_source("pilosa_tpu/x.py", same_line) == []
    assert lint_source("pilosa_tpu/x.py", prev_line) == []
    assert lint_source("pilosa_tpu/x.py", monotonic) == []


def test_lint_bare_except():
    bad = "try:\n    pass\nexcept:\n    pass\n"
    good = "try:\n    pass\nexcept Exception:\n    pass\n"
    assert rules(lint_source("pilosa_tpu/x.py", bad)) == ["bare-except"]
    assert lint_source("pilosa_tpu/x.py", good) == []


def test_lint_lock_blocking():
    bad = ("import os\n"
           "with self._lock:\n"
           "    os.fsync(fd)\n")
    rpc = ("with self.mu:\n"
           "    client.query_proto(uri, i, q)\n")
    deferred = ("with self._lock:\n"
                "    def later():\n"
                "        os.fsync(fd)\n")
    not_a_lock = "with open(p) as f:\n    os.fsync(f.fileno())\n"
    assert rules(lint_source("pilosa_tpu/x.py", bad)) == ["lock-blocking"]
    assert rules(lint_source("pilosa_tpu/x.py", rpc)) == ["lock-blocking"]
    assert lint_source("pilosa_tpu/x.py", deferred) == []
    assert lint_source("pilosa_tpu/x.py", not_a_lock) == []


def test_lint_stats_registry():
    bad = "s = StatsClient()\n"
    assert rules(lint_source("pilosa_tpu/x.py", bad)) == ["stats-registry"]
    assert lint_source("pilosa_tpu/utils/stats.py", bad) == []
    assert lint_source("pilosa_tpu/server.py", bad) == []


def test_lint_raw_jit():
    bare = "import jax\n@jax.jit\ndef f(a):\n    return a\n"
    configured = ("import jax\n@jax.jit(static_argnames=('k',))\n"
                  "def f(a, k):\n    return a\n")
    call_form = "import jax\ng = jax.jit(lambda a: a)\n"
    aliased = "from jax import jit as J\n@J\ndef f(a):\n    return a\n"
    counted = ("from pilosa_tpu.utils.telemetry import counted_jit\n"
               "@counted_jit('bsi')\ndef f(a):\n    return a\n")
    for bad in (bare, configured, call_form, aliased):
        assert rules(lint_source("pilosa_tpu/ops/x.py", bad)) == ["raw-jit"]
    # counted_jit is the sanctioned wrapper
    assert lint_source("pilosa_tpu/ops/x.py", counted) == []
    # scope is pilosa_tpu/ops/ only — jit elsewhere is someone else's call
    assert lint_source("pilosa_tpu/executor.py", bare) == []


# ------------------------------------------------------------- the tree gate


def test_tree_is_lint_clean():
    """THE gate: zero findings over the real tree — AST rules AND the
    env-gate / config-knob inventory diffs. The committed baseline plays
    no part here; a baselined finding still fails."""
    findings = run_all(ROOT)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_committed_baseline_is_empty():
    path = os.path.join(ROOT, "pilosa_tpu", "analysis", "baseline.txt")
    with open(path, encoding="utf-8") as f:
        entries = [ln for ln in (l.strip() for l in f)
                   if ln and not ln.startswith("#")]
    assert entries == [], "the baseline must stay empty; fix, don't suppress"


def test_cli_check_passes_on_clean_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "pilosa_tpu.analysis", "--check",
         "--root", ROOT],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 findings" in proc.stdout


def test_cli_check_fails_on_injected_finding(tmp_path):
    """A mini-tree with one raw-thread violation (docs copied from the
    real tree so the inventory rules stay quiet) must exit 1 and name
    the file:line."""
    pkg = tmp_path / "pilosa_tpu"
    pkg.mkdir()
    bad = pkg / "bad.py"
    bad.write_text("import threading\n"
                   "threading.Thread(target=print).start()\n")
    docs = tmp_path / "docs"
    docs.mkdir()
    with open(os.path.join(ROOT, "docs", "operations.md"),
              encoding="utf-8") as f:
        (docs / "operations.md").write_text(f.read())
    proc = subprocess.run(
        [sys.executable, "-m", "pilosa_tpu.analysis", "--check",
         "--root", str(tmp_path)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "bad.py:2: ctx-thread" in proc.stdout


def test_cli_baseline_suppresses_but_check_reports(tmp_path):
    """The incident-branch escape hatch: a baselined finding passes
    --check but still prints (marked), so it cannot vanish silently."""
    pkg = tmp_path / "pilosa_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text("import threading\n"
                                "threading.Thread(target=print).start()\n")
    docs = tmp_path / "docs"
    docs.mkdir()
    with open(os.path.join(ROOT, "docs", "operations.md"),
              encoding="utf-8") as f:
        # less the env table's rows: this package reads none of them
        (docs / "operations.md").write_text("".join(
            line for line in f if not line.startswith("| `PILOSA_TPU_")))
    baseline = tmp_path / "baseline.txt"
    baseline.write_text("# incident hotfix\npilosa_tpu/bad.py:ctx-thread\n")
    proc = subprocess.run(
        [sys.executable, "-m", "pilosa_tpu.analysis", "--check",
         "--root", str(tmp_path), "--baseline", str(baseline)],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "(baselined)" in proc.stdout


def test_env_gate_inventory_sees_known_gates():
    from pilosa_tpu.analysis.inventories import env_gate_inventory
    inv = env_gate_inventory(ROOT)
    assert "PILOSA_TPU_LOCKCHECK" in inv
    assert "PILOSA_TPU_QOS" in inv
    assert "PILOSA_TPU_WAL_FSYNC" in inv
    assert env_gate_findings(ROOT) == []


def test_env_gate_listed_but_unread_is_a_finding(tmp_path):
    """The other direction: a switch deleted from the code and left in
    the docs' env table is a finding at the table's row; a name the prose
    merely mentions is not."""
    pkg = tmp_path / "pilosa_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text(
        'import os\nON = os.environ.get("PILOSA_TPU_KEPT", "1")\n')
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "operations.md").write_text(
        "| Env | Default | Meaning |\n|---|---|---|\n"
        "| `PILOSA_TPU_KEPT` | 1 | read |\n"
        "| `PILOSA_TPU_GONE` | 0 | nothing reads this |\n"
        "\n`PILOSA_TPU_CLUSTER_REPLICAS` is the pattern of a config field.\n")
    findings = env_gate_findings(str(tmp_path))
    assert [(f.path, f.line, f.rule) for f in findings] == [
        ("docs/operations.md", 4, "env-gate-docs")]
    assert "PILOSA_TPU_GONE" in findings[0].msg


def test_config_knob_inventory_complete():
    from pilosa_tpu.analysis.inventories import config_knob_inventory
    knobs = dict.fromkeys(f"{s}.{k}" if s else k
                          for s, k in config_knob_inventory())
    # spot checks incl. the knobs this PR wired into to_toml
    for expect in ("cluster.query-timeout", "cluster.liveness-threshold",
                   "cluster.membership-interval", "log-path",
                   "qos.mode", "slo.burn-red"):
        assert expect in knobs
    assert config_knob_findings(ROOT) == []


# ------------------------------------------------------------- lock witness


def make_locks(witness, *sites):
    # build on the RAW factories: under the suite-wide witness,
    # threading.Lock() here would return an already-wrapped lock whose
    # inner recordings pollute the GLOBAL witness with these tests'
    # intentional inversions (and trip the conftest guard)
    return [lockwitness.WitnessLock(lockwitness._real_lock(), s, witness)
            for s in sites]


def test_witness_detects_ab_ba_inversion_with_stacks():
    w = lockwitness.Witness()
    A, B = make_locks(w, "mod_a.py:10", "mod_b.py:20")
    with A:
        with B:
            pass
    with B:
        with A:  # closes the cycle
            pass
    rep = w.report()
    assert len(rep["cycles"]) == 1
    cyc = rep["cycles"][0]
    assert set(cyc["cycle"]) == {"mod_a.py:10", "mod_b.py:20"}
    # both the closing edge's stack and the prior edge's stack point here
    assert "test_witness_detects_ab_ba_inversion" in cyc["newEdgeStack"]
    prior = list(cyc["priorStacks"].values())
    assert prior and all(
        "test_witness_detects_ab_ba_inversion" in s for s in prior if s)
    assert "LOCK-ORDER CYCLE" in w.format_violations()


def test_witness_transitive_cycle():
    """A→B, B→C, then C→A: the cycle spans three sites."""
    w = lockwitness.Witness()
    A, B, C = make_locks(w, "a.py:1", "b.py:2", "c.py:3")
    with A:
        with B:
            pass
    with B:
        with C:
            pass
    with C:
        with A:
            pass
    rep = w.report()
    assert len(rep["cycles"]) == 1
    assert set(rep["cycles"][0]["cycle"]) == {"a.py:1", "b.py:2", "c.py:3"}


def test_witness_consistent_order_is_silent():
    w = lockwitness.Witness()
    A, B = make_locks(w, "a.py:1", "b.py:2")
    for _ in range(3):
        with A:
            with B:
                pass
    assert w.report()["cycles"] == []
    assert w.violation_count() == 0


def test_witness_held_across_fake_rpc():
    w = lockwitness.Witness()
    L = lockwitness.WitnessRLock(lockwitness._real_rlock(), "srv.py:42", w)
    with L:
        w.note_blocking("rpc", "POST /internal/query-batch")
    rep = w.report()
    assert len(rep["heldAcrossBlocking"]) == 1
    v = rep["heldAcrossBlocking"][0]
    assert v["kind"] == "rpc" and v["held"] == ["srv.py:42"]
    assert "test_witness_held_across_fake_rpc" in v["stack"]
    # identical (kind, held sites) dedup: a hot path reports once
    with L:
        w.note_blocking("rpc", "POST /internal/query-batch")
    assert len(w.report()["heldAcrossBlocking"]) == 1
    # no lock held -> clean
    w2 = lockwitness.Witness()
    w2.note_blocking("rpc", "GET /status")
    assert w2.report()["heldAcrossBlocking"] == []


def test_witness_reentrant_rlock_no_self_noise():
    w = lockwitness.Witness()
    L = lockwitness.WitnessRLock(lockwitness._real_rlock(), "re.py:1", w)
    with L:
        with L:  # reentrant: no edge, no self-edge
            pass
    rep = w.report()
    assert rep["cycles"] == [] and rep["selfEdges"] == []
    # but two DIFFERENT instances from one site nesting -> selfEdges info
    L2 = lockwitness.WitnessRLock(lockwitness._real_rlock(), "re.py:1", w)
    with L:
        with L2:
            pass
    rep = w.report()
    assert rep["selfEdges"] == ["re.py:1"]
    assert rep["cycles"] == []  # info, not a violation


def test_witness_condition_and_event_integration():
    """Condition.wait/notify over a witnessed RLock and Event round trips
    must keep bookkeeping balanced (no phantom held locks)."""
    w = lockwitness.Witness()
    inner = lockwitness.WitnessRLock(lockwitness._real_rlock(), "cv.py:1", w)
    cond = threading.Condition(inner)
    ready = []

    def waiter():
        with cond:
            while not ready:
                cond.wait(timeout=5)

    t = threads.spawn(waiter)
    time.sleep(0.05)
    with cond:
        ready.append(1)
        cond.notify_all()
    t.join(5)
    assert not t.is_alive()
    # the waiter thread released during wait: nothing held afterwards
    w.note_blocking("rpc", "after")
    assert w.report()["heldAcrossBlocking"] == []
    assert w.report()["cycles"] == []


def test_witness_env_gate_and_passthrough():
    """Without install(), threading.Lock() stays native and
    note_blocking is a no-op even under a held native lock."""
    if lockwitness.ACTIVE:
        lockwitness.uninstall()
        try:
            lk = threading.Lock()
            assert not isinstance(lk, lockwitness.WitnessLock)
        finally:
            lockwitness.install()
    else:
        lk = threading.Lock()
        assert not isinstance(lk, lockwitness.WitnessLock)


def test_suite_runs_witnessed_and_clean():
    """The conftest arms the witness for the whole tier-1 run (the env
    gate opts out); a real server query under it must record no
    violations — the clean-run acceptance in miniature. (The autouse
    guard enforces the same per test; this pins the wiring itself.)"""
    if os.environ.get(lockwitness.ENV_GATE) == "0":
        pytest.skip("witness explicitly disabled")
    assert lockwitness.ACTIVE
    from pilosa_tpu.server import Server
    import tempfile
    before = lockwitness.violation_count()
    with tempfile.TemporaryDirectory() as tmp:
        s = Server(os.path.join(tmp, "n0"), port=0).open()
        try:
            # at least one witnessed lock exists (the server is full of
            # them) and real traffic crossed the choke points
            req = urllib.request.Request(
                s.uri + "/index/w", data=b"{}", method="POST")
            urllib.request.urlopen(req, timeout=30).read()
            req = urllib.request.Request(
                s.uri + "/index/w/query", data=b"Set(1, f=1)",
                method="POST")
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(req, timeout=30)  # no field: 400
        finally:
            s.close()
    assert lockwitness.violation_count() == before
    assert lockwitness.report()["edges"] > 0


# ---------------------------------------- thread-boundary ctx propagation


def test_spawn_propagates_all_query_contextvars():
    from pilosa_tpu import qos
    seen = {}
    tok_t = tracing.current_trace_id.set("trace-spawn-1")
    acct = accounting.Account(accounting.UsageLedger(), "key:ctx-test")
    tok_a = accounting.current_account.set(acct)
    tok_d = qctx.deadline.set(time.monotonic() + 30)
    tok_p = qos.current_priority.set("batch")
    try:
        t = threads.spawn(lambda: seen.update(
            trace=tracing.current_trace_id.get(),
            acct=accounting.current_account.get(),
            deadline=qctx.deadline.get(),
            prio=qos.current_priority.get()))
        t.join(5)
    finally:
        tracing.current_trace_id.reset(tok_t)
        accounting.current_account.reset(tok_a)
        qctx.deadline.reset(tok_d)
        qos.current_priority.reset(tok_p)
    assert seen["trace"] == "trace-spawn-1"
    assert seen["acct"] is acct
    assert seen["deadline"] is not None and seen["prio"] == "batch"


def test_ctx_thread_and_timer_propagate_trace():
    seen = {}
    tok = tracing.current_trace_id.set("trace-timer-1")
    try:
        t = threads.ctx_thread(
            lambda: seen.__setitem__("t", tracing.current_trace_id.get()))
        t.start()
        t.join(5)
        tm = threads.ctx_timer(0.01, lambda: seen.__setitem__(
            "timer", tracing.current_trace_id.get()))
        tm.start()
        tm.join(5)
    finally:
        tracing.current_trace_id.reset(tok)
    assert seen == {"t": "trace-timer-1", "timer": "trace-timer-1"}


def test_submit_ctx_propagates_through_pool():
    from concurrent.futures import ThreadPoolExecutor
    tok = tracing.current_trace_id.set("trace-pool-1")
    try:
        with ThreadPoolExecutor(max_workers=1) as pool:
            fut = threads.submit_ctx(
                pool, lambda: tracing.current_trace_id.get())
            assert fut.result(5) == "trace-pool-1"
    finally:
        tracing.current_trace_id.reset(tok)


def test_telemetry_sampler_tick_keeps_trace():
    """The sampler's background tick chain (one of the paths the lint
    migration covered) runs in the context active at start()."""
    from pilosa_tpu.utils.telemetry import TelemetrySampler
    seen = []

    def source():
        seen.append(tracing.current_trace_id.get())
        return {"g": 1.0}

    tok = tracing.current_trace_id.set("trace-sampler-1")
    try:
        sampler = TelemetrySampler(interval=0.01, ring_size=8,
                                   source=source)
        sampler.start()
        deadline = time.monotonic() + 5
        while not seen and time.monotonic() < deadline:
            time.sleep(0.01)
        sampler.close()
    finally:
        tracing.current_trace_id.reset(tok)
    assert seen and seen[0] == "trace-sampler-1"


def test_hint_replay_from_spawned_heal_keeps_trace(tmp_path):
    """The server's return-heal replays hints on a spawned thread; the
    trace active when the heal was triggered must reach every applied
    hint (the profiled-query-keeps-its-trace regression)."""
    from pilosa_tpu.storage.hints import HintStore
    store = HintStore(str(tmp_path / "hints"))
    store.append("peer-1", "i", "Set(1, f=1)")
    store.append("peer-1", "i", "Set(2, f=1)")
    seen = []

    def apply(doc):
        seen.append((doc["pql"], tracing.current_trace_id.get()))

    tok = tracing.current_trace_id.set("trace-heal-1")
    try:
        t = threads.spawn(lambda: store.replay("peer-1", apply))
        t.join(10)
    finally:
        tracing.current_trace_id.reset(tok)
    assert [p for p, _ in seen] == ["Set(1, f=1)", "Set(2, f=1)"]
    assert all(tid == "trace-heal-1" for _, tid in seen)
    assert store.pending("peer-1") == 0  # replayed prefix retired


def test_hint_replay_concurrent_append_survives(tmp_path):
    """The witness-driven fix (apply outside the per-target lock) must
    not lose hints appended mid-replay: the un-replayed suffix stays for
    the next pass, in order."""
    from pilosa_tpu.storage.hints import HintStore
    store = HintStore(str(tmp_path / "hints"))
    store.append("peer-1", "i", "Set(1, f=1)")
    applied = []

    def apply(doc):
        if not applied:
            # mid-replay, after the snapshot was taken: a new hint lands
            store.append("peer-1", "i", "Set(99, f=1)")
        applied.append(doc["pql"])

    replayed, dropped, complete = store.replay("peer-1", apply)
    assert (replayed, dropped, complete) == (1, 0, True)
    assert applied == ["Set(1, f=1)"]
    assert store.pending("peer-1") > 0  # the mid-replay hint survived
    replayed2, _, _ = store.replay("peer-1", apply)
    assert replayed2 == 1 and applied[-1] == "Set(99, f=1)"
    assert store.pending("peer-1") == 0
