#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that pilosa-tpu still starts and answers
PQL correctly on a real TPU.

    python3 chip_smoke.py                 # on a machine with a TPU
    python3 chip_smoke.py --rehearse-cpu  # tiny sizes on the CPU, to debug

One process per chip: this parent never initialises JAX (stdlib, numpy and
the package's jax-free client/storage modules only). Every phase is a child
process, run one after the other, each gone before the next starts:

  A  server   `python -m pilosa_tpu.cli server` on a generated TOML (fresh
              data dir, default [mesh]). The parent reads /debug/vars
              `deviceMemory` first and exits non-zero at once unless the
              platform is `tpu`; then loads a deployment-sized index over
              HTTP (import-roaring + import), runs a few queries of every
              family against a plain numpy reference written in this file,
              a read-your-writes Set, and 32 concurrent clients.
  B  dryrun   (4-device hosts only) PILOSA_DRYRUN_PLATFORM=native
              python __graft_entry__.py 4.

Exit status is the result: 0 only if every phase ran and every answer
matched. The last stdout line is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`,
the device as JAX reports it. The line before it, `summary {...}`, carries
sizes, set-up times, compile-cache entry counts, resident bytes and
per-phase pass/fail — set-up facts, no rates. A non-TPU platform prints
neither.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# jax-free by construction (asserted in tests/test_chip_smoke.py): in a
# directory that holds this file and nothing else of the repo these
# imports fail, and so does the script
from pilosa_tpu.net.client import ClientError, InternalClient  # noqa: E402
from pilosa_tpu.storage.roaring import Bitmap  # noqa: E402

SHARD_WIDTH = 1 << 20
INDEX = "smoke"
OVERALL_LIMIT_S = 1140.0  # the contract's 1200 s, less a margin to report
PHASE_LIMIT_S = {"server": 900.0, "dryrun": 300.0}
# the concurrent phase's own limit, per request: concurrent multi-device
# dispatch is where a mesh would hang, and a hang must fail fast
CONCURRENT_REQUEST_LIMIT_S = 120.0


class SmokeFailure(Exception):
    """Any mismatch, timeout, dead child or wrong device."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Data scale. The defaults are the deployment size; per-shard
    cardinalities (which pick the device representation) are the same at
    every scale — only shard and row counts shrink for a rehearsal."""
    shards: int = 128              # 2^27 columns at the full 2^20 width
    dense_rows: int = 48
    dense_bits: int = 10_500       # per shard (~1%) > sparse-threshold 4096
    sparse_rows: int = 4
    sparse_bits: int = 300         # per shard
    run_rows: int = 4
    run_len: int = 3000            # two contiguous ranges of this per shard
    g_rows: int = 8                # GroupBy axis 1, ~1% rows
    h_rows: int = 6                # GroupBy axis 2; row 0 covers half
    tag_rows: int = 10_000         # ranked field for TopN
    tag_head: int = 100_000        # bits in the largest tag row (Zipf 1/k)
    bsi_shards: int = 32           # a value on EVERY column of these
    bsi_max: int = (1 << 21) - 1   # bit depth 21
    topn: int = 1000
    # sized when the filtered walk uploaded one [S, W] leaf (16 MiB at 128
    # shards) a recounted row; since PR 29 rows this thin are recounted
    # from their sorted columns in one launch
    topn_filtered: int = 100
    clients: int = 32
    per_client: int = 10

    @property
    def columns(self) -> int:
        return self.shards * SHARD_WIDTH


FULL = Sizes()
REHEARSAL = Sizes(shards=2, dense_rows=4, sparse_rows=2, run_rows=2,
                  g_rows=3, h_rows=2, tag_rows=300, tag_head=20_000,
                  bsi_shards=1, bsi_max=(1 << 10) - 1, topn=50,
                  topn_filtered=20, clients=8, per_client=4)


# ---------------------------------------------------------------------------
# The plain reference: numpy set algebra on sorted unique column arrays.
# Shares no code with pilosa_tpu.
# ---------------------------------------------------------------------------


def _member(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Which elements of a are in the sorted unique set b."""
    if b.size == 0:
        return np.zeros(a.size, dtype=bool)
    idx = np.searchsorted(b, a)
    idx[idx == b.size] = b.size - 1
    return b[idx] == a


def ref_intersect(a, b):
    return a[_member(a, b)]


def ref_difference(a, b):
    return a[~_member(a, b)]


def ref_union(a, b):
    return np.union1d(a, b)


def ref_xor(a, b):
    return np.setxor1d(a, b, assume_unique=True)


def ref_topn(row_ids: np.ndarray, counts: np.ndarray, n: int) -> list:
    """Pairs order: count descending, row id ascending; zero rows dropped."""
    keep = counts > 0
    ids, cs = row_ids[keep], counts[keep]
    order = np.lexsort((ids, -cs))[:n]
    return [{"id": int(ids[i]), "count": int(cs[i])} for i in order]


def ref_row_counts(rows: np.ndarray, cols: np.ndarray, n_rows: int,
                   within: np.ndarray | None = None) -> np.ndarray:
    """Bits per row of a (row, col) pair list, optionally only the columns
    inside the sorted unique set `within`."""
    if within is not None:
        rows = rows[_member(cols, within)]
    return np.bincount(rows, minlength=n_rows).astype(np.int64)


def ref_valcount(vals: np.ndarray, op: str) -> dict:
    """Sum / Min / Max over the selected values, Pilosa's ValCount: Sum
    counts the values summed, Min/Max count the columns attaining it."""
    if vals.size == 0:
        return {"value": 0, "count": 0}
    if op == "sum":
        return {"value": int(vals.astype(np.int64).sum()),
                "count": int(vals.size)}
    v = int(vals.min() if op == "min" else vals.max())
    return {"value": v, "count": int((vals == v).sum())}


def ref_groupby(axis_a: dict, axis_b: dict, fa: str, fb: str) -> list:
    """Two-axis GroupBy: every (a, b) with a non-empty intersection, in
    (a, b) ascending order."""
    out = []
    for ra in sorted(axis_a):
        for rb in sorted(axis_b):
            n = int(_member(axis_a[ra], axis_b[rb]).sum())
            if n:
                out.append({"group": [{"field": fa, "rowID": ra},
                                      {"field": fb, "rowID": rb}],
                            "count": n})
    return out


# ---------------------------------------------------------------------------
# Data, made from the seed in bulk.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Data:
    sizes: Sizes
    f: dict           # row id -> sorted unique uint32 global columns
    g: dict
    h: dict
    dense_ids: list
    sparse_ids: list
    run_ids: list
    tag_rows: np.ndarray   # (row, col) pairs of field t, sorted by row, col
    tag_cols: np.ndarray
    values: np.ndarray     # int32 per column of the first bsi_shards shards
    exists: np.ndarray     # columns the existence field must hold


def _scatter_row(rng, shards: int, per_shard: int) -> np.ndarray:
    """~per_shard uniform random columns in every shard (duplicates drop)."""
    local = rng.integers(0, SHARD_WIDTH, size=(shards, per_shard),
                         dtype=np.uint32)
    local += (np.arange(shards, dtype=np.uint32) * SHARD_WIDTH)[:, None]
    return np.unique(local.ravel())


def make_data(z: Sizes, seed: int) -> Data:
    rng = np.random.default_rng(seed)
    f: dict = {}
    dense_ids = list(range(z.dense_rows))
    sparse_ids = list(range(100, 100 + z.sparse_rows))
    run_ids = list(range(200, 200 + z.run_rows))
    for r in dense_ids:
        f[r] = _scatter_row(rng, z.shards, z.dense_bits)
    for r in sparse_ids:
        f[r] = _scatter_row(rng, z.shards, z.sparse_bits)
    # runny rows: two contiguous ranges per shard, one in each half; row j
    # is row 0 shifted by j*1000 so run rows overlap each other
    half = SHARD_WIDTH // 2
    room = half - z.run_len - 1000 * z.run_rows
    base = rng.integers(0, room, size=(z.shards, 2), dtype=np.int64)
    base[:, 1] += half
    base += (np.arange(z.shards, dtype=np.int64) * SHARD_WIDTH)[:, None]
    span = np.arange(z.run_len, dtype=np.int64)
    for j, r in enumerate(run_ids):
        cols = (base + 1000 * j)[:, :, None] + span[None, None, :]
        f[r] = np.sort(cols.ravel()).astype(np.uint32)
    g = {r: _scatter_row(rng, z.shards, z.dense_bits)
         for r in range(z.g_rows)}
    h = {r: _scatter_row(rng, z.shards, 2 * z.dense_bits)
         for r in range(1, z.h_rows)}
    h[0] = np.flatnonzero(np.unpackbits(rng.integers(
        0, 256, size=z.columns // 8, dtype=np.uint8))).astype(np.uint32)
    # tags: Zipf row sizes, columns uniform over the whole index
    sizes = (z.tag_head // np.arange(1, z.tag_rows + 1)) + 3
    rows = np.repeat(np.arange(z.tag_rows, dtype=np.int64), sizes)
    cols = rng.integers(0, z.columns, size=rows.size, dtype=np.int64)
    pairs = np.unique((rows << 32) | cols)
    tag_rows = (pairs >> 32).astype(np.int64)
    tag_cols = (pairs & 0xFFFFFFFF).astype(np.uint32)
    n_val = z.bsi_shards * SHARD_WIDTH
    values = rng.integers(0, z.bsi_max + 1, size=n_val, dtype=np.int32)
    # existence is what went through /import (bits and values);
    # import-roaring does not track it, as upstream
    exists = np.unique(np.concatenate(
        [np.arange(n_val, dtype=np.uint32), tag_cols]
        + [f[r] for r in sparse_ids]))
    return Data(z, f, g, h, dense_ids, sparse_ids, run_ids, tag_rows,
                tag_cols, values, exists)


# ---------------------------------------------------------------------------
# Children: started in their own session, killed on every exit path, with
# hard wall-clock limits per phase and overall.
# ---------------------------------------------------------------------------


class Children:
    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._live: list = []
        self._lock = threading.Lock()
        self.phase = ""
        self.phase_deadline = float("inf")
        self.overall_deadline = time.monotonic() + OVERALL_LIMIT_S
        threading.Thread(target=self._watch, daemon=True).start()

    def start(self, name: str, argv: list, env: dict) -> subprocess.Popen:
        out = open(os.path.join(self.log_dir, f"{name}.out"), "wb")
        err = open(os.path.join(self.log_dir, f"{name}.err"), "wb")
        proc = subprocess.Popen(argv, stdout=out, stderr=err, cwd=REPO,
                                env=env, start_new_session=True)
        out.close()
        err.close()
        with self._lock:
            self._live.append(proc)
        return proc

    def begin_phase(self, name: str) -> None:
        self.phase = name
        self.phase_deadline = time.monotonic() + PHASE_LIMIT_S[name]

    def remaining(self) -> float:
        return max(1.0, min(self.phase_deadline, self.overall_deadline)
                   - time.monotonic())

    def stop(self, proc: subprocess.Popen, grace: float = 0.0) -> int:
        """Stop one child (SIGTERM first when given a grace period) and
        its whole session; returns its exit code."""
        if proc.poll() is None and grace > 0:
            proc.terminate()
            try:
                proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        with self._lock:
            if proc in self._live:
                self._live.remove(proc)
        return proc.returncode if proc.returncode is not None else -9

    def kill_all(self) -> None:
        with self._lock:
            live = list(self._live)
        for proc in live:
            self.stop(proc)

    def tail(self, name: str, stream: str = "err", n: int = 3000) -> str:
        try:
            with open(os.path.join(self.log_dir, f"{name}.{stream}"),
                      "rb") as fh:
                return fh.read()[-n:].decode(errors="replace")
        except OSError:
            return ""

    def _watch(self) -> None:
        while True:
            time.sleep(1.0)
            now = time.monotonic()
            if now > self.phase_deadline or now > self.overall_deadline:
                which = ("overall" if now > self.overall_deadline
                         else f"phase {self.phase!r}")
                sys.stderr.write(
                    f"chip_smoke: {which} wall-clock limit exceeded\n"
                    + self.tail(self.phase or "server"))
                sys.stderr.flush()
                self.kill_all()
                os._exit(4)


# ---------------------------------------------------------------------------
# HTTP: DDL, PQL and /debug reads on the stdlib (a connection per request —
# a few hundred requests in all); imports go through pilosa_tpu.net.client.
# ---------------------------------------------------------------------------


class Http:
    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.uri = f"http://{host}:{port}"

    def request(self, method: str, path: str, body: bytes | None = None,
                ctype: str = "application/json", timeout: float = 300.0):
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=timeout)
        try:
            conn.request(method, path, body=body,
                         headers={"Content-Type": ctype} if body else {})
            resp = conn.getresponse()
            data = resp.read()
        except (OSError, http.client.HTTPException) as e:
            raise SmokeFailure(f"{method} {path}: {type(e).__name__}: {e}")
        finally:
            conn.close()
        if resp.status >= 400:
            raise SmokeFailure(f"{method} {path}: HTTP {resp.status}: "
                               f"{data[:600].decode(errors='replace')}")
        return json.loads(data) if data else {}

    def get(self, path: str, **kw):
        return self.request("GET", path, **kw)

    def post(self, path: str, payload: dict, **kw):
        return self.request("POST", path, json.dumps(payload).encode(), **kw)

    def query(self, pql: str, **kw) -> list:
        return self.request("POST", f"/index/{INDEX}/query", pql.encode(),
                            ctype="text/plain", **kw)["results"]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cache_dir() -> str:
    """Where the children keep JAX's persistent compile cache: the
    environment's choice, else the fixed in-checkout path
    (pilosa_tpu.parallel.mesh.COMPILE_CACHE_DIR, restated here because the
    parent must not import jax)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(REPO, ".jax_cache"))


def cache_entries() -> set:
    try:
        return set(os.listdir(cache_dir()))
    except OSError:
        return set()


# ---------------------------------------------------------------------------
# Phase A: the server.
# ---------------------------------------------------------------------------


def wait_ready(http: Http, proc, kids: Children) -> None:
    while True:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"server exited with code {proc.returncode} before "
                f"serving\n{kids.tail('server')}")
        try:
            http.get("/status", timeout=5.0)
            return
        except SmokeFailure:
            time.sleep(0.5)


def check_platform(http: Http, want: str) -> dict:
    """The device the server actually holds, before any data is loaded."""
    devs = http.get("/debug/vars")["deviceMemory"]
    platforms = sorted({d["platform"] for d in devs})
    if platforms != [want]:
        raise SmokeFailure(
            f"server is on platform {platforms}, not {want!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} in this "
            f"environment); refusing to load data")
    return {"platform": want, "kind": devs[0]["device_kind"],
            "count": len(devs)}


def _roaring(rows: dict, shard: int) -> bytes:
    """One shard of a field's rows as a roaring payload (row r's local
    column c at position r * 2^20 + c, the fragment layout)."""
    lo = shard * SHARD_WIDTH
    # bounds in the columns' own dtype: a Python-int needle would make
    # numpy cast the whole (up to 67M-element) row per call
    bounds = np.array([lo, lo + SHARD_WIDTH - 1], dtype=np.uint32)
    parts = []
    for r, cols in rows.items():
        a = np.searchsorted(cols, bounds[0], side="left")
        b = np.searchsorted(cols, bounds[1], side="right")
        parts.append(cols[a:b].astype(np.uint64) - np.uint64(lo)
                     + np.uint64(r * SHARD_WIDTH))
    return Bitmap(np.concatenate(parts)).to_bytes()


def _parallel(jobs: list, workers: int) -> None:
    """Run thunks on a few threads; the first failure is raised."""
    errors: list = []
    it = iter(jobs)
    lock = threading.Lock()

    def work():
        while not errors:
            with lock:
                job = next(it, None)
            if job is None:
                return
            try:
                job()
            except Exception as e:  # noqa: BLE001 — reported by the caller
                errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        e = errors[0]
        raise e if isinstance(e, SmokeFailure) else SmokeFailure(
            f"import failed: {type(e).__name__}: {e}")


def load(http: Http, d: Data) -> dict:
    """DDL and imports over the normal routes. Dense, runny and GroupBy rows
    go as roaring payloads per shard; sparse rows, tags and BSI values go
    through /import (which also maintains the existence field)."""
    z = d.sizes
    client = InternalClient(timeout=600.0)
    http.post(f"/index/{INDEX}", {})
    ranked = {"options": {"type": "set", "cacheType": "ranked",
                          "cacheSize": 50_000}}
    for name in ("f", "g", "h", "t"):
        http.post(f"/index/{INDEX}/field/{name}", ranked)
    http.post(f"/index/{INDEX}/field/v",
              {"options": {"type": "int", "min": 0, "max": z.bsi_max}})

    roaring_rows = {
        "f": {r: d.f[r] for r in d.dense_ids + d.run_ids},
        "g": d.g, "h": d.h}
    jobs = []
    for field, rows in roaring_rows.items():
        for s in range(z.shards):
            jobs.append(lambda field=field, rows=rows, s=s:
                        client.import_roaring(
                            http.uri, INDEX, field, s,
                            {"standard": _roaring(rows, s)}))
    sparse_r = np.concatenate([np.full(d.f[r].size, r, dtype=np.int64)
                               for r in d.sparse_ids])
    sparse_c = np.concatenate([d.f[r] for r in d.sparse_ids])
    jobs.append(lambda: client.import_bits(
        http.uri, INDEX, "f", {"rowIDs": sparse_r.tolist(),
                               "columnIDs": sparse_c.tolist()}))
    # tags go shard-major, a group of shards per request: each fragment
    # then takes ONE bulk import (and one snapshot), not one per request
    order = np.argsort(d.tag_cols, kind="stable")
    t_rows, t_cols = d.tag_rows[order], d.tag_cols[order]
    group = max(1, z.shards // 8)
    edges = np.searchsorted(t_cols >> 20, np.arange(
        0, z.shards + group, group, dtype=np.uint32))
    for a, b in zip(edges[:-1], edges[1:]):
        jobs.append(lambda a=a, b=b: client.import_bits(
            http.uri, INDEX, "t", {"rowIDs": t_rows[a:b].tolist(),
                                   "columnIDs": t_cols[a:b].tolist()}))
    for s in range(z.bsi_shards):
        lo = s * SHARD_WIDTH
        jobs.append(lambda lo=lo: client.import_bits(
            http.uri, INDEX, "v",
            {"columnIDs": list(range(lo, lo + SHARD_WIDTH)),
             "values": d.values[lo:lo + SHARD_WIDTH].tolist()}))
    try:
        _parallel(jobs, workers=6)
    except ClientError as e:
        raise SmokeFailure(f"import failed: {e}")
    bits = {
        "f": int(sum(c.size for c in d.f.values())),
        "g": int(sum(c.size for c in d.g.values())),
        "h": int(sum(c.size for c in d.h.values())),
        "t": int(d.tag_cols.size), "v_values": int(d.values.size)}
    return {"shards": z.shards, "columns": z.columns, "bits": bits,
            "rows": {"dense": z.dense_rows, "sparse": z.sparse_rows,
                     "run": z.run_rows, "g": z.g_rows, "h": z.h_rows,
                     "t": z.tag_rows},
            "bsi_shards": z.bsi_shards,
            "bsi_depth": int(z.bsi_max).bit_length()}


class Checker:
    """Runs queries, compares with the reference, keeps every verdict."""

    def __init__(self, http: Http):
        self.http = http
        self.families: dict = {}
        self.failures: list = []
        self.first_correct: float | None = None

    def record(self, family: str, name: str, got, want,
               seconds: float | None = None) -> bool:
        ok = got == want
        self.families[family] = self.families.get(family, True) and ok
        if ok:
            if self.first_correct is None:
                self.first_correct = time.monotonic()
        else:
            self.failures.append(
                f"{family}/{name}: got {_short(got)} want {_short(want)}")
        took = "" if seconds is None else f" ({seconds:.2f}s)"
        print(f"  [{'ok' if ok else 'MISMATCH'}] {family}: {name}{took}",
              flush=True)
        return ok

    def check(self, family: str, pql: str, want, pick=lambda r: r) -> bool:
        t0 = time.monotonic()  # one host-clock reading, first call: a
        # set-up fact (compile and upload included), not a latency metric
        got = pick(self.http.query(pql)[0])
        return self.record(family, pql, got, want, time.monotonic() - t0)


def _short(x, n: int = 300) -> str:
    s = json.dumps(x) if not isinstance(x, str) else x
    return s if len(s) <= n else s[:n] + f"...({len(s)} chars)"


def rep_dispatches(http: Http) -> dict:
    out = {"dense": 0, "sparse": 0, "run": 0}
    for c in http.get("/debug/vars")["kernels"]["calls"]:
        out[c["rep"]] = out.get(c["rep"], 0) + c["dispatches"]
    return out


def run_queries(http: Http, d: Data, ck: Checker) -> None:
    z = d.sizes
    f = d.f
    d0, d1, d2 = d.dense_ids[:3]
    s0, s1 = d.sparse_ids[:2]
    r0, r1 = d.run_ids[:2]

    def row(r):
        return f"Row(f={r})"

    ck.check("count_intersect_dense",
             f"Count(Intersect({row(d0)}, {row(d1)}))",
             int(ref_intersect(f[d0], f[d1]).size))
    ck.check("count_intersect_dense",
             f"Count(Intersect({row(d1)}, {row(d2)}))",
             int(ref_intersect(f[d1], f[d2]).size))

    mixed = [
        (f"Count(Union({row(d0)}, {row(s0)}))",
         ref_union(f[d0], f[s0])),
        (f"Count(Union({row(r0)}, {row(s0)}))",
         ref_union(f[r0], f[s0])),
        (f"Count(Difference({row(d1)}, {row(r0)}))",
         ref_difference(f[d1], f[r0])),
        (f"Count(Difference({row(s0)}, {row(d0)}))",
         ref_difference(f[s0], f[d0])),
        (f"Count(Xor({row(r0)}, {row(d2)}))", ref_xor(f[r0], f[d2])),
        (f"Count(Xor({row(s0)}, {row(s1)}))", ref_xor(f[s0], f[s1])),
        (f"Count(Intersect({row(r0)}, {row(r1)}))",
         ref_intersect(f[r0], f[r1])),
        (f"Count(Intersect({row(s0)}, {row(r0)}))",
         ref_intersect(f[s0], f[r0])),
        (f"Count(Intersect({row(s0)}, {row(d0)}))",
         ref_intersect(f[s0], f[d0])),
        (f"Count(Intersect({row(r0)}, {row(d0)}))",
         ref_intersect(f[r0], f[d0])),
    ]
    for pql, want in mixed:
        ck.check("mixed_algebra", pql, int(want.size))

    ck.check("chain3",
             f"Count(Intersect({row(d0)}, {row(d1)}, {row(d2)}))",
             int(ref_intersect(ref_intersect(f[d0], f[d1]), f[d2]).size))
    ck.check("chain3",
             f"Count(Difference(Union({row(d0)}, {row(r0)}), {row(s0)}))",
             int(ref_difference(ref_union(f[d0], f[r0]), f[s0]).size))
    ck.check("chain3",
             f"Count(Intersect(Union({row(s0)}, {row(r0)}), {row(d1)}, "
             f"{row(r1)}))",
             int(ref_intersect(ref_intersect(ref_union(f[s0], f[r0]),
                                             f[d1]), f[r1]).size))

    ck.check("row_sparse_columns", row(s0), f[s0].tolist(),
             pick=lambda r: r["columns"])

    ck.check("not_existence", f"Count(Not({row(d0)}))",
             int(ref_difference(d.exists, f[d0]).size))
    ck.check("not_existence", f"Count(Not({row(s0)}))",
             int(ref_difference(d.exists, f[s0]).size))

    tag_ids = np.arange(z.tag_rows)
    ck.check("topn", f"TopN(t, n={z.topn})",
             ref_topn(tag_ids, ref_row_counts(d.tag_rows, d.tag_cols,
                                              z.tag_rows), z.topn))
    ck.check("topn", f"TopN(t, Row(h=0), n={z.topn_filtered})",
             ref_topn(tag_ids, ref_row_counts(d.tag_rows, d.tag_cols,
                                              z.tag_rows, within=d.h[0]),
                      z.topn_filtered))

    v = d.values
    x = int(np.sort(v[:4096])[2048])  # a threshold that occurs in the data
    lo, hi = sorted(int(t) for t in v[[7, 11]])
    if lo == hi:
        hi = int(v.max())
    for op in ("sum", "min", "max"):
        ck.check("bsi", f"{op.capitalize()}(Range(v > {x}), field=v)",
                 ref_valcount(v[v > x], op))
        ck.check("bsi",
                 f"{op.capitalize()}(Range(v >< [{lo}, {hi}]), field=v)",
                 ref_valcount(v[(v >= lo) & (v <= hi)], op))

    ck.check("groupby", "GroupBy(Rows(field=g), Rows(field=h))",
             ref_groupby(d.g, d.h, "g", "h"))


def run_write(http: Http, d: Data, ck: Checker) -> None:
    """Set, then at once a Count that must see it: read-your-writes through
    the in-place patch of the resident leaves the queries above uploaded."""
    f = d.f
    d0, d1 = d.dense_ids[:2]
    col = int(ref_difference(f[d1], f[d0])[0])  # in d1, not yet in d0
    before = int(ref_intersect(f[d0], f[d1]).size)
    got = http.query(f"Set({col}, f={d0})")[0]
    ck.record("set_then_count", f"Set({col}, f={d0})", got, True)
    f[d0] = ref_union(f[d0], np.array([col], dtype=np.uint32))
    ck.check("set_then_count", f"Count(Row(f={d0}))", int(f[d0].size))
    ck.check("set_then_count",
             f"Count(Intersect(Row(f={d0}), Row(f={d1})))", before + 1)


def run_concurrent(http: Http, d: Data, ck: Checker) -> dict:
    """32 clients x 10 Counts of a dense pair (the continuous batcher must
    be on the path), and among them, from the same clients, three Counts
    the batcher does not take: a 3-way all-dense Intersect, a dense
    Union of three and a tree with a sparse leaf. On a mesh the first two
    are programs that sum across chips and the third runs chip-local
    kernels, launched while the batcher's own are in flight: where a mesh
    would hang, under the phase's limit a request."""
    z = d.sizes
    ids = d.dense_ids[:8]
    s0 = d.sparse_ids[0]
    pairs = [(ids[i % len(ids)], ids[(i * 3 + 1) % len(ids)])
             for i in range(z.clients * z.per_client)]
    want = {}
    for a, b in set(pairs):
        want[f"Count(Intersect(Row(f={a}), Row(f={b})))"] = int(
            ref_intersect(d.f[a], d.f[b]).size)
    trees = {}  # a client's three, by its place among the dense rows
    for k in range(len(ids)):
        a, b, c = (ids[(k + j) % len(ids)] for j in (0, 1, 3))
        fa, fb, fc = d.f[a], d.f[b], d.f[c]
        trees[k] = [
            (f"Count(Intersect(Row(f={a}), Row(f={b}), Row(f={c})))",
             ref_intersect(ref_intersect(fa, fb), fc)),
            (f"Count(Union(Row(f={a}), Row(f={b}), Row(f={c})))",
             ref_union(ref_union(fa, fb), fc)),
            (f"Count(Difference(Union(Row(f={a}), Row(f={s0})), "
             f"Row(f={b})))",
             ref_difference(ref_union(fa, d.f[s0]), fb))]
        for pql, cols in trees[k]:
            want[pql] = int(cols.size)
    before = http.get("/debug/vars")["countBatcher"]["batched_queries"]
    wrong: list = []
    errors: list = []

    def client(tid: int) -> None:
        mine = [f"Count(Intersect(Row(f={a}), Row(f={b})))" for a, b in
                pairs[tid * z.per_client:(tid + 1) * z.per_client]]
        for j, (pql, _) in enumerate(trees[tid % len(ids)]):
            mine.insert(min(len(mine), 3 * j + 2), pql)
        try:
            for pql in mine:
                got = http.query(pql, timeout=CONCURRENT_REQUEST_LIMIT_S)[0]
                if got != want[pql]:
                    wrong.append((pql, got, want[pql]))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(z.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    after = http.get("/debug/vars")["countBatcher"]
    batched = after["batched_queries"] - before
    ck.record("concurrent_counts",
              f"{z.clients} clients x ({z.per_client} Count(Intersect) of "
              f"a pair + 3 trees)",
              {"wrong": wrong[:3], "errors": errors[:3]},
              {"wrong": [], "errors": []})
    ck.record("concurrent_counts", "batched_queries > 0", batched > 0, True)
    return {"queries": z.clients * (z.per_client + 3),
            "batched_queries": batched,
            "max_batch_seen": after["max_batch_seen"]}


def check_memory(http: Http, device: dict, z: Sizes, ck: Checker) -> dict:
    hbm = http.get("/debug/hbm")
    dvars = http.get("/debug/vars")
    stats = [d.get("memoryStats") or {} for d in dvars["deviceMemory"]]
    in_use = [int(s.get("bytes_in_use", 0)) for s in stats]
    out = {"residentBytes": hbm["residentBytes"],
           "budgetBytes": hbm["budgetBytes"],
           "device_bytes_limit": [int(s.get("bytes_limit", 0))
                                  for s in stats],
           "device_bytes_in_use": in_use,
           "byKind": {k: v["bytes"] for k, v in
                      dvars["deviceResidency"]["by_kind"].items()}}
    # what the queries above must have left on the device: every BSI plane
    # (Sum, Range) and both GroupBy axes, a plane of 128 KiB a shard each
    # (1 GiB was the floor while a filtered TopN uploaded a plane a
    # recounted row; since PR 29 it recounts from sorted columns)
    planes = int(z.bsi_max).bit_length() + z.g_rows + z.h_rows
    floor = planes * z.shards * (SHARD_WIDTH // 8)
    ck.record("residency", f"residentBytes >= {floor} ({planes} planes)",
              hbm["residentBytes"] >= floor, True)
    if device["platform"] == "tpu" and device["count"] > 1:
        # nothing piled on the first chip (the CPU backend reports no stats)
        ck.record("mesh_balance",
                  "bytes_in_use non-zero and within 2x on every device",
                  min(in_use) > 0 and max(in_use) <= 2 * min(in_use), True)
    return out


def phase_server(kids: Children, d: Data, want_platform: str, tmp: str,
                 summary: dict) -> None:
    kids.begin_phase("server")
    port = free_port()
    cfg = os.path.join(tmp, "smoke.toml")
    with open(cfg, "w") as fh:
        fh.write(f'data-dir = "{os.path.join(tmp, "data")}"\n'
                 f'bind = "127.0.0.1:{port}"\n')
        if want_platform == "cpu":  # the explicit rehearsal, never a default
            fh.write('[mesh]\nplatform = "cpu"\n')
    t_start = time.monotonic()
    proc = kids.start("server", [sys.executable, "-m", "pilosa_tpu.cli",
                                 "server", "--config", cfg],
                      dict(os.environ))
    http = Http("127.0.0.1", port)
    ck = Checker(http)
    try:
        wait_ready(http, proc, kids)
        device = summary["device"] = check_platform(http, want_platform)
        print(f"server up in {time.monotonic() - t_start:.1f}s on "
              f"platform={device['platform']} kind={device['kind']!r} "
              f"count={device['count']}", flush=True)
        t0 = time.monotonic()
        summary["sizes"] = load(http, d)
        summary["load_seconds"] = round(time.monotonic() - t0, 1)
        print(f"loaded {json.dumps(summary['sizes'])} in "
              f"{summary['load_seconds']}s", flush=True)
        disp0 = rep_dispatches(http)
        run_queries(http, d, ck)
        run_write(http, d, ck)
        summary["concurrent"] = run_concurrent(http, d, ck)
        disp1 = rep_dispatches(http)
        moved = summary["dispatches_moved"] = {
            k: disp1[k] - disp0[k] for k in disp0}
        ck.record("dispatch_counters",
                  f"dense, sparse and run dispatches moved: {moved}",
                  all(moved[k] > 0 for k in ("dense", "sparse", "run")),
                  True)
        summary["memory"] = check_memory(http, device, d.sizes, ck)
        print(f"residency {json.dumps(summary['memory'])}", flush=True)
    except SmokeFailure as e:
        died = (f"\nserver exited with code {proc.returncode}"
                if proc.poll() is not None else "")
        raise SmokeFailure(f"{e}{died}\n--- server stderr tail ---\n"
                           f"{kids.tail('server')}")
    finally:
        code = kids.stop(proc, grace=60.0)
        summary["families"] = ck.families
        if ck.first_correct is not None:
            summary["start_to_first_answer_seconds"] = round(
                ck.first_correct - t_start, 1)
    if ck.failures:
        raise SmokeFailure("answers differ from the reference:\n  "
                           + "\n  ".join(ck.failures))
    if code != 0:
        raise SmokeFailure(f"server exited with code {code} on SIGTERM\n"
                           f"{kids.tail('server')}")


# ---------------------------------------------------------------------------
# Phase B: a child that holds the chips itself.
# ---------------------------------------------------------------------------


def phase_dryrun(kids: Children, n: int) -> dict:
    kids.begin_phase("dryrun")
    proc = kids.start(
        "dryrun",
        [sys.executable, os.path.join(REPO, "__graft_entry__.py"), str(n)],
        dict(os.environ, PILOSA_DRYRUN_PLATFORM="native"))
    try:
        proc.wait(timeout=kids.remaining())
    except subprocess.TimeoutExpired:
        pass
    timed_out = proc.poll() is None
    code = kids.stop(proc)
    if timed_out or code != 0:
        raise SmokeFailure(
            "phase 'dryrun' "
            + ("timed out" if timed_out else f"exited with code {code}")
            + f"\n{kids.tail('dryrun', 'out', 1500)}\n{kids.tail('dryrun')}")
    out = kids.tail("dryrun", "out", 20000)
    return {"ok": True, "tail": out.strip().splitlines()[-2:]}


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU backend, to debug the "
                         "script; the output says platform=cpu")
    ap.add_argument("--seed", type=int, default=20260926)
    args = ap.parse_args()

    want = "cpu" if args.rehearse_cpu else "tpu"
    sizes = REHEARSAL if args.rehearse_cpu else FULL
    log_dir = os.path.join(REPO, "chiprun_out", "chip_smoke")
    os.makedirs(log_dir, exist_ok=True)
    kids = Children(log_dir)
    tmp = tempfile.mkdtemp(prefix="chip-smoke-")
    cache_before = cache_entries()
    phases: dict = {}
    summary: dict = {"ok": False}
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        t0 = time.monotonic()
        data = make_data(sizes, args.seed)
        print(f"data generated from seed {args.seed} in "
              f"{time.monotonic() - t0:.1f}s", flush=True)
        phase_server(kids, data, want, tmp, summary)
        phases["server"] = True
        del data
        if summary["device"]["count"] == 4:
            summary["dryrun"] = phase_dryrun(kids, 4)
            phases["dryrun"] = True
        summary["ok"] = True
    except SmokeFailure as e:
        phases.setdefault(kids.phase or "setup", False)
        sys.stderr.write(f"chip_smoke FAILED in phase "
                         f"{kids.phase or 'setup'!r}: {e}\n")
        if summary.get("device") is None:
            return 2  # no accelerator (or no server at all): no result line
        summary["error"] = str(e).splitlines()[0][:300]
    finally:
        kids.phase_deadline = float("inf")
        kids.kill_all()
        shutil.rmtree(tmp, ignore_errors=True)
    after = cache_entries()
    summary.update({
        "rehearsal": args.rehearse_cpu, "seed": args.seed,
        "phases": phases,
        "compile_cache": {
            "dir": cache_dir(), "entries_before": len(cache_before),
            "entries_after": len(after),
            "new_by_program": _by_program(after - cache_before)},
        "seconds_total": round(
            OVERALL_LIMIT_S - (kids.overall_deadline - time.monotonic()), 1),
    })
    device = summary.pop("device")
    print("summary " + json.dumps(summary), flush=True)
    print(result_line(summary["ok"], device), flush=True)
    return 0 if summary["ok"] else 1


def result_line(ok: bool, device: dict) -> str:
    """The last stdout line, to the driver's contract: exactly `ok` and
    `device`, the device exactly platform / kind / count as JAX reports
    them. Everything else this script learned goes on the `summary` line
    before it."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def _by_program(entries: set) -> dict:
    """New cache entries counted by the jitted program's name."""
    out: dict = {}
    for e in entries:
        name = e.rsplit("-", 2)[0] if e.count("-") >= 2 else e
        out[name] = out.get(name, 0) + 1
    return dict(sorted(out.items()))


if __name__ == "__main__":
    sys.exit(main())
