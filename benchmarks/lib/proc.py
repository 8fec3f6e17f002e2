"""Process control and HTTP for the harness: the server runs as a child in
its own session and is killed on every exit path; the parent never imports
JAX. Copied from chip_smoke.py (Children, Http, free_port, wait_ready,
check_platform) and cut to what a benchmark run needs — copied,
not imported, so that a later PR may change the program and its smoke
script and not the yardstick.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import time


class BenchFailure(Exception):
    """The run cannot give a result: no server, wrong device, dead child."""


class Children:
    """Children started in their own session, each with its output in a
    file of the log directory; `kill_all` ends every one and its session."""

    def __init__(self, log_dir: str, cwd: str):
        self.log_dir = log_dir
        self.cwd = cwd
        self._live: list = []
        os.makedirs(log_dir, exist_ok=True)

    def start(self, name: str, argv: list, env: dict) -> subprocess.Popen:
        with open(os.path.join(self.log_dir, f"{name}.out"), "wb") as out, \
                open(os.path.join(self.log_dir, f"{name}.err"), "wb") as err:
            proc = subprocess.Popen(argv, stdout=out, stderr=err,
                                    cwd=self.cwd, env=env,
                                    start_new_session=True)
        self._live.append(proc)
        return proc

    def stop(self, proc: subprocess.Popen, grace: float = 0.0) -> int:
        """SIGTERM first when given a grace period, then SIGKILL to the
        whole session; waits until the child has ended."""
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
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass
        if proc in self._live:
            self._live.remove(proc)
        return proc.returncode if proc.returncode is not None else -9

    def kill_all(self) -> None:
        for proc in list(self._live):
            self.stop(proc)

    def tail(self, name: str, stream: str = "err", n: int = 3000) -> str:
        try:
            with open(os.path.join(self.log_dir, f"{name}.{stream}"),
                      "rb") as fh:
                return fh.read()[-n:].decode(errors="replace")
        except OSError:
            return ""


class Http:
    """One keep-alive connection (one per client thread). `request` gives
    (status, parsed JSON or None); a transport error reconnects once for
    the next call and is raised as BenchFailure."""

    def __init__(self, host: str, port: int, timeout: float = 300.0):
        self.host, self.port, self.timeout = host, port, timeout
        self._conn = None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def raw(self, method: str, path: str, body: bytes | None = None,
            ctype: str = "application/json"):
        """(status, body bytes)."""
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                self.host, self.port, timeout=self.timeout)
        try:
            self._conn.request(method, path, body=body, headers={
                "Content-Type": ctype} if body is not None else {})
            resp = self._conn.getresponse()
            data = resp.read()
        except (OSError, http.client.HTTPException) as e:
            self.close()
            raise BenchFailure(f"{method} {path}: {type(e).__name__}: {e}")
        return resp.status, data

    def request(self, method: str, path: str, body: bytes | None = None,
                ctype: str = "application/json"):
        status, data = self.raw(method, path, body, ctype)
        try:
            doc = json.loads(data) if data else None
        except ValueError:
            doc = None
        return status, doc

    def ok(self, method: str, path: str, payload=None):
        """A set-up call: JSON in, JSON out, any HTTP error is fatal."""
        body = None if payload is None else json.dumps(payload).encode()
        status, doc = self.request(method, path, body)
        if status >= 400:
            raise BenchFailure(f"{method} {path}: HTTP {status}: "
                               f"{json.dumps(doc)[:600]}")
        return doc

    def query(self, index: str, pql: str):
        """(status, results or error document) of one PQL request."""
        status, doc = self.request("POST", f"/index/{index}/query",
                                   pql.encode(), ctype="text/plain")
        if status == 200 and isinstance(doc, dict) and "results" in doc:
            return status, doc["results"]
        return status, doc


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_ready(http: Http, proc, kids: Children, limit_s: float) -> None:
    deadline = time.monotonic() + limit_s
    while True:
        if proc.poll() is not None:
            raise BenchFailure(
                f"server exited with code {proc.returncode} before "
                f"serving\n{kids.tail('server')}")
        if time.monotonic() > deadline:
            raise BenchFailure(f"server not serving after {limit_s:.0f}s\n"
                               f"{kids.tail('server')}")
        try:
            if http.request("GET", "/status")[0] == 200:
                return
        except BenchFailure:
            pass
        time.sleep(0.25)


def device_of(http: Http) -> dict:
    """The device the server holds, as JAX reported it to /debug/vars."""
    devs = http.ok("GET", "/debug/vars")["deviceMemory"]
    platforms = sorted({d["platform"] for d in devs})
    if len(platforms) != 1:
        raise BenchFailure(f"server holds mixed platforms {platforms}")
    return {"platform": platforms[0], "kind": devs[0]["device_kind"],
            "count": len(devs)}


def memory_peak_bytes(http: Http) -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    reports no allocator statistics, as the CPU's does not)."""
    peak = 0
    for d in http.ok("GET", "/debug/vars")["deviceMemory"]:
        stats = d.get("memoryStats") or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def jit_compiles(http: Http) -> dict:
    """Programs the server's jitted kernels have traced so far (compiled,
    or fetched from the persistent cache), by kernel family: the
    `pilosa_xlaCompiles_total{family=...}` rows of /metrics, the one place
    the program publishes them."""
    status, text = http.raw("GET", "/metrics")
    if status != 200:
        raise BenchFailure(f"GET /metrics: HTTP {status}")
    out = {}
    for line in text.decode(errors="replace").splitlines():
        if line.startswith("pilosa_xlaCompiles_total"):
            name, value = line.rsplit(" ", 1)
            out[name.partition("{")[2].rstrip("}") or name] = \
                int(float(value))
    return out
