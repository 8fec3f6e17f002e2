"""Standalone repro driver for the flaky gossip clusterproc failure.

Runs the same 3-process SIGSTOP scenario as
tests/test_clusterproc.py::test_gossip_cluster_sigstop_liveness in a loop;
on the first DEGRADED-wait timeout it SIGUSR1s every node (faulthandler
stack dump to the node log), copies the logs to /tmp/gossip_fail/, and
exits 1. Diagnostic tool only — not part of the suite.
"""

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def http(method, port, path, body=None, timeout=10.0):
    data = None if body is None else (
        body if isinstance(body, bytes) else json.dumps(body).encode())
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read() or b"{}")


def wait_until(fn, timeout, interval=0.25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            if fn():
                return True
        except Exception:
            pass
        time.sleep(interval)
    return False


def state(port):
    _, st = http("GET", port, "/status", timeout=3.0)
    return st["state"]


def ready(port):
    _, st = http("GET", port, "/status", timeout=3.0)
    return st["state"] == "NORMAL" and len(st["nodes"]) == 3


def one_round(i):
    tmp = tempfile.mkdtemp(prefix=f"gossip_round{i}_")
    ports = free_ports(3)
    gports = free_ports(3)
    hosts = ", ".join(f'"http://127.0.0.1:{p}"' for p in ports)
    procs = []
    ok = False
    try:
        for n, port in enumerate(ports):
            cfg = os.path.join(tmp, f"g{n}.toml")
            with open(cfg, "w") as f:
                f.write(
                    f'data-dir = "{os.path.join(tmp, f"g{n}")}"\n'
                    f'bind = "127.0.0.1:{port}"\n'
                    "[cluster]\ndisabled = false\nreplicas = 2\n"
                    f"hosts = [{hosts}]\n"
                    "membership-interval = 0.5\n"
                    "[gossip]\n"
                    f"port = {gports[n]}\n"
                    f'seeds = ["127.0.0.1:{gports[0]}"]\n'
                    "period = 0.15\nprobe-timeout = 0.3\n"
                    "push-pull-interval = 0.5\n"
                    '[mesh]\ndevices = "none"\nplatform = "cpu"\n')
            env = dict(os.environ)
            env["PYTHONPATH"] = REPO
            env["JAX_PLATFORMS"] = "cpu"
            p = subprocess.Popen(
                [sys.executable, "-m", "pilosa_tpu.cli", "server",
                 "--config", cfg],
                stdout=open(os.path.join(tmp, f"g{n}.log"), "wb"),
                stderr=subprocess.STDOUT, cwd=REPO, env=env)
            procs.append(p)
        if not wait_until(lambda: all(ready(p) for p in ports), 90.0):
            print(f"round {i}: never reached NORMAL/3")
            return False, tmp, procs
        http("POST", ports[0], "/index/gi", {"options": {}})
        http("POST", ports[0], "/index/gi/field/f",
             {"options": {"type": "set"}})
        http("POST", ports[0], "/index/gi/query", b"Set(1, f=5)")
        os.kill(procs[2].pid, signal.SIGSTOP)
        t0 = time.monotonic()
        ok = wait_until(lambda: state(ports[0]) == "DEGRADED"
                        and state(ports[1]) == "DEGRADED", 45.0)
        print(f"round {i}: degraded={ok} after "
              f"{time.monotonic() - t0:.1f}s")
        return ok, tmp, procs
    except Exception as e:  # noqa: BLE001
        print(f"round {i}: exception {e}")
        return False, tmp, procs


def teardown(procs, dump=False):
    for p in procs:
        if dump:
            try:
                os.kill(p.pid, signal.SIGCONT)
                time.sleep(0.1)
                os.kill(p.pid, signal.SIGUSR1)
            except OSError:
                pass
    time.sleep(1.0 if dump else 0)
    for p in procs:
        try:
            os.kill(p.pid, signal.SIGCONT)
        except OSError:
            pass
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 6
    for i in range(rounds):
        ok, tmp, procs = one_round(i)
        if not ok:
            # SIGUSR1 while n2 is still stopped is useless (it can't run
            # the handler); dump survivors first, then everything
            for p in procs[:2]:
                try:
                    os.kill(p.pid, signal.SIGUSR1)
                except OSError:
                    pass
            time.sleep(1.0)
            teardown(procs, dump=True)
            dst = "/tmp/gossip_fail"
            shutil.rmtree(dst, ignore_errors=True)
            shutil.copytree(tmp, dst)
            print(f"FAILURE captured -> {dst}")
            return 1
        teardown(procs)
        shutil.rmtree(tmp, ignore_errors=True)
    print("no failure reproduced")
    return 0


if __name__ == "__main__":
    sys.exit(main())
