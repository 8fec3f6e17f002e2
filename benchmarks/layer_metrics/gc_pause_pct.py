"""Share of the window the server's interpreter spent in collections, in
percent: the delta of /debug/vars `runtime.gc` (`pauseMs` summed over the
three generations; one `gc.callbacks` hook times each collection from its
start to its stop, a stretch in which no other thread runs Python) over the
window's length on the server's clock (`spans.nowMs`). 0.0 is a reading: no
collection finished in the window. None where either end has no
`runtime.gc` block (a program older than the hook) or no span table."""


def read(ctx):
    before = ctx.get("vars_before") or {}
    after = ctx.get("vars_after") or {}
    a = (before.get("runtime") or {}).get("gc")
    b = (after.get("runtime") or {}).get("gc")
    if a is None or b is None or not before.get("spans") \
            or not after.get("spans"):
        return None
    window_ms = after["spans"].get("nowMs", 0.0) \
        - before["spans"].get("nowMs", 0.0)
    if window_ms <= 0:
        return None
    paused = sum(e.get("pauseMs", 0.0) - a.get(gen, {}).get("pauseMs", 0.0)
                 for gen, e in b.items())
    return paused / window_ms * 100.0
