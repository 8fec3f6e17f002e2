"""Reduce a JAX profiler trace (.xplane.pb) to the numbers the benchmark
reports. Run as a child of the harness, after the server has exited, with
JAX_PLATFORMS=cpu: reading a trace needs jax's ProfileData and nothing of
the chip.

    python benchmarks/lib/trace.py <file.xplane.pb>   -> one JSON line

  window_s    the traced window: profile_stop_time - profile_start_time of
              the trace's "Task Environment" plane (else the span of all
              events)
  busy_s      per device plane, the union of the intervals of its
              operation events; the mean over the device planes
  device_ops  the ten names with most summed time, seconds summed over
              devices: the compiled programs of the "XLA Modules" line
              (the jitted functions' names, without the fingerprint the
              profiler puts after each in brackets, so that all the
              shapes of one function count as one) where the trace has
              that line, else the operations
  idle_gaps   the ten longest gaps between operations on the first device,
              each named "unattributed": the program writes no host spans
              into the trace yet
  op_sum_s    summed (not united) operation time, mean over devices

A device plane is one whose name starts with "/device:" and is not a
"/device:CUSTOM" or host plane; its operation events are those of the line
named "XLA Ops" (every line, where no line has that name).
"""

from __future__ import annotations

import json
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def _union(intervals: list) -> tuple:
    """(united length, gaps between the united intervals) of sorted
    (start, end) pairs."""
    total, gaps = 0.0, []
    cur_s, cur_e = None, None
    for s, e in intervals:
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s - cur_e))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def reduce_trace(path: str) -> dict:
    from jax.profiler import ProfileData

    space = ProfileData.from_file(path)
    start = stop = None
    lo, hi = float("inf"), float("-inf")
    devices = []
    for plane in space.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            start = stats.get("profile_start_time")
            stop = stats.get("profile_stop_time")
        lines = list(plane.lines)
        is_device = (plane.name.startswith("/device:")
                     and not plane.name.startswith("/device:CUSTOM"))
        named = [ln.name for ln in lines if ln.name == OPS_LINE]
        ops, modules = [], []
        for ln in lines:
            for ev in ln.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                lo, hi = min(lo, s), max(hi, s + d)
                if is_device and (ln.name in named or not named):
                    ops.append((s, s + d, ev.name))
                if is_device and ln.name == MODULES_LINE:
                    modules.append((s, s + d, ev.name))
        if is_device and ops:
            devices.append((plane.name, sorted(ops), modules))
    if start is not None and stop is not None and stop > start:
        window_ns = float(stop - start)
    elif hi > lo:
        window_ns = hi - lo
    else:
        window_ns = 0.0
    by_name: dict = {}
    busy, op_sum, first_gaps = [], [], []
    for i, (_, ops, modules) in enumerate(devices):
        united, gaps = _union([(s, e) for s, e, _ in ops])
        busy.append(united)
        op_sum.append(sum(e - s for s, e, _ in ops))
        if i == 0:
            first_gaps = gaps
        for s, e, name in modules or ops:
            name = _FINGERPRINT.sub("", name)[:120]
            by_name[name] = by_name.get(name, 0.0) + (e - s)
    n = max(1, len(devices))
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(first_gaps, key=lambda g: -g[1])[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "op_sum_s": sum(op_sum) / n / 1e9,
        "devices": [name for name, _, _ in devices],
        "lines": {pl.name: {ln.name: len(list(ln.events))
                            for ln in pl.lines}
                  for pl in space.planes if pl.name.startswith("/device:")},
        "profile_start_ns": start,
        "profile_stop_ns": stop,
        "device_ops": [[name, ns / 1e9] for name, ns in top],
        "idle_gaps": [["unattributed", ns / 1e9] for _, ns in gaps],
    }


if __name__ == "__main__":
    print(json.dumps(reduce_trace(sys.argv[1])))
