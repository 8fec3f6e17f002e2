"""Milliseconds a request of the window spent blocked at the program's
named locks: the delta of /debug/vars `locks` (`waitMs`, summed over every
name: each `TimedLock` of pilosa_tpu/utils/tracing.py and the residency's
single-flight wait `residency.build`) over the window's requests. A thread
that queues on a lock is still inside some span, which books the wait as
its own `selfMs`; the server counts it at the lock. None where either end
has no `locks` block (a program older than the counters) or the window
sent nothing."""


def read(ctx):
    a = (ctx.get("vars_before") or {}).get("locks")
    b = (ctx.get("vars_after") or {}).get("locks")
    if a is None or b is None or not ctx.get("requests"):
        return None
    waited = sum(e.get("waitMs", 0.0) - a.get(name, {}).get("waitMs", 0.0)
                 for name, e in b.items())
    return waited / ctx["requests"]
