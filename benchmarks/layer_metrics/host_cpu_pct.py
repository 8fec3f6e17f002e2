"""Share of one core the request threads used over the window: the thread
CPU time of the `http.request` spans over the window's length on the
server's own clock, in percent. Python runs one thread at a time, so near
100 the interpreter is the ceiling whatever the device does."""

from lib import spans


def read(ctx):
    d = spans.delta(ctx)
    if d is None or d["nowMs"] <= 0 or d.get(spans.ROOT, {}).get("n", 0) <= 0:
        return None
    return d[spans.ROOT]["cpuMs"] / d["nowMs"] * 100.0
