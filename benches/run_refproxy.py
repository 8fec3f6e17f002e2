"""Build + run the C++ reference-baseline proxy and record the results.

Produces benches/refproxy.json: {bench_name: {"ns_per_op": float, "ops": int,
"qps": float}} plus host metadata: the record BASELINE.md's table is
rendered from. See refproxy.cc for why a scalar C++ proxy stands in for the
absent Go toolchain.
"""

import json
import os
import platform
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "refproxy.cc")
BIN = os.path.join(HERE, "refproxy")
OUT = os.path.join(HERE, "refproxy.json")


def build() -> None:
    if (os.path.exists(BIN)
            and os.path.getmtime(BIN) >= os.path.getmtime(SRC)):
        return
    subprocess.run(["g++", "-O2", "-std=c++17", "-o", BIN, SRC], check=True)


def main() -> None:
    build()
    filters = sys.argv[1:]  # zero names = full run; N names = N filtered runs
    stdout = ""
    for args in ([[]] if not filters else [[f] for f in filters]):
        proc = subprocess.run([BIN] + args, capture_output=True,
                              text=True, check=True, timeout=600)
        stdout += proc.stdout
    results = {}
    prev_meta = {}
    if filters:  # filtered rerun: merge over the existing file
        try:
            with open(OUT) as f:
                prev_meta = json.load(f)
                results = prev_meta.get("results", {})
        except (OSError, ValueError):
            prev_meta = {}
    try:
        cpu = [l.split(":", 1)[1].strip()
               for l in open("/proc/cpuinfo")
               if l.startswith("model name")][0]
    except (OSError, IndexError):
        cpu = platform.processor()
    for line in stdout.splitlines():
        parts = line.split()
        if len(parts) != 3:
            continue
        name, ns, ops = parts[0], float(parts[1]), int(parts[2])
        results[name] = {"ns_per_op": ns, "ops": ops,
                         "qps": round(1e9 / ns, 2) if ns else 0.0}
        if filters and prev_meta.get("host_cpu") not in ("", None, cpu):
            # merged entry measured on a different host than the original
            # full run: record its provenance per-entry
            results[name]["host_cpu"] = cpu
    if filters and prev_meta:
        # keep the original full-run host metadata on merges
        cpu = prev_meta.get("host_cpu", cpu)
    out = {
        "proxy": "scalar C++ -O2 reimplementation of the reference's "
                 "roaring kernels + bench workloads (no Go toolchain in "
                 "image; see refproxy.cc header and BASELINE.md)",
        "host_cpu": cpu,
        "host_cores": (prev_meta.get("host_cores") if filters and prev_meta
                       else None) or os.cpu_count(),
        "results": results,
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["results"], indent=1))


if __name__ == "__main__":
    main()
