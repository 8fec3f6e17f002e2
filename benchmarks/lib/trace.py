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
              each named by what the host was doing: the program's own
              `pilosa.<span>` event (utils/tracing.py writes every span
              into the trace, on its clock) on the thread that launched
              the program the gap ends at, and of the spans nested there
              the one in whose own time most of the gap lies;
              "unattributed" only where that thread is not known or no
              span of it reaches into the gap
  op_sum_s    summed (not united) operation time, mean over devices

A device plane is one whose name starts with "/device:" and is not a
"/device:CUSTOM" or host plane; its operation events are those of the line
named "XLA Ops" (every line, where no line has that name). The host's
threads are the lines of the plane "/host:CPU". The thread that launched
the program a gap ends at is found by the profiler's own link where the
trace has it: the program's event on the "XLA Modules" line and the
runtime's "...Execute..." events on the launching thread carry the same
`run_id`. Where it has not, a launch is a host event "PjitFunction(<fn>)",
which the runtime writes on the thread that calls a jitted function, and
the launch looked for is the last one that began before the gap's end, of
that program's own function ("jit_<fn>") where the device plane names it.
"""

from __future__ import annotations

import bisect
import json
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "pilosa."
UNATTRIBUTED = "unattributed"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_LAUNCH = re.compile(r"^PjitFunction\((.*)\)$")


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


def _launch_thread(launches: list, gap_end: float, fn: str | None):
    """The thread of the last launch that began before `gap_end`: of the
    function `fn` where one is, else of any."""
    best = {}
    for start, thread, name in launches:
        if start > gap_end:
            break
        best[None] = thread
        best[name] = thread
    return best.get(fn, best.get(None))


def _span_of_gap(spans: list, lo: float, hi: float) -> str:
    """Of one thread's (start, end, name) spans, properly nested, the name
    in whose own time (its time less its children's) most of [lo, hi]
    lies."""
    inside = [(max(s, lo), min(e, hi), e - s, name)
              for s, e, name in spans if s < hi and e > lo]
    if not inside:
        return UNATTRIBUTED
    cuts = sorted({lo, hi} | {t for s, e, _, _ in inside for t in (s, e)})
    own: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [(length, name) for s, e, length, name in inside
                if s <= a and e >= b]
        if over:   # the shortest span over a stretch is the innermost
            name = min(over)[1]
            own[name] = own.get(name, 0.0) + (b - a)
    return max(own.items(), key=lambda kv: kv[1])[0]


def _stat(ev, key: str):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def name_gaps(gaps: list, starts_at: dict, launches: list,
              spans: dict, thread_of_run: dict | None = None) -> list:
    """[name] for (gap start, gap length) pairs. `starts_at` maps the
    device time at which a program starts to (its function's name, its
    run_id or None), `thread_of_run` a run_id to the host thread that
    launched it, `launches` is sorted (start, thread, fn), `spans` maps a
    thread to its pilosa.* events."""
    out = []
    program_starts = sorted(starts_at)
    for start, length in gaps:
        end = start + length
        k = bisect.bisect_left(program_starts, end - 1.0)   # to the ns
        fn, run = (starts_at[program_starts[k]]
                   if k < len(program_starts)
                   and program_starts[k] <= end + 1.0 else (None, None))
        thread = (thread_of_run or {}).get(run)
        if thread is None:
            thread = _launch_thread(launches, end, fn)
        out.append(_span_of_gap(spans.get(thread, []), start, end)
                   if thread is not None else UNATTRIBUTED)
    return out


def reduce_trace(path: str) -> dict:
    from jax.profiler import ProfileData

    space = ProfileData.from_file(path)
    start = stop = None
    lo, hi = float("inf"), float("-inf")
    devices = []
    launches, spans, thread_of_run = [], {}, {}
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
        for thread, ln in enumerate(lines):
            for ev in ln.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                lo, hi = min(lo, s), max(hi, s + d)
                if plane.name == HOST_PLANE:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.setdefault(thread, []).append(
                            (s, s + d, ev.name))
                    elif launch := _LAUNCH.match(ev.name):
                        launches.append((s, thread, launch.group(1)))
                    elif "Execute" in ev.name:
                        run = _stat(ev, "run_id")
                        if run is not None:
                            thread_of_run.setdefault(run, thread)
                if is_device and (ln.name in named or not named):
                    ops.append((s, s + d, ev.name))
                if is_device and ln.name == MODULES_LINE:
                    modules.append((s, s + d, ev.name, _stat(ev, "run_id")))
        if is_device and ops:
            devices.append((plane.name, sorted(ops), modules))
    if start is not None and stop is not None and stop > start:
        window_ns = float(stop - start)
    elif hi > lo:
        window_ns = hi - lo
    else:
        window_ns = 0.0
    by_name: dict = {}
    busy, op_sum, first_gaps, starts_at = [], [], [], {}
    for i, (_, ops, modules) in enumerate(devices):
        united, gaps = _union([(s, e) for s, e, _ in ops])
        busy.append(united)
        op_sum.append(sum(e - s for s, e, _ in ops))
        if i == 0:
            first_gaps = gaps
            # a program's first operation starts with the program
            for s, e, name, run in sorted(modules, key=lambda m: m[0]):
                fn = _FINGERPRINT.sub("", name)
                fn = fn[4:] if fn.startswith("jit_") else fn
                k = bisect.bisect_left(ops, (s,))
                if k < len(ops) and ops[k][0] < e:
                    starts_at[ops[k][0]] = (fn, run)
        for s, e, name, *_ in modules or ops:
            name = _FINGERPRINT.sub("", name)[:120]
            by_name[name] = by_name.get(name, 0.0) + (e - s)
    n = max(1, len(devices))
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(first_gaps, key=lambda g: -g[1])[:10]
    names = name_gaps(gaps, starts_at, sorted(launches), spans,
                      thread_of_run)
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
        "idle_gaps": [[name, ns / 1e9]
                      for name, (_, ns) in zip(names, gaps)],
    }


if __name__ == "__main__":
    print(json.dumps(reduce_trace(sys.argv[1])))
