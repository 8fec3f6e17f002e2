"""How late, on average, a sleeping thread of the server got the
interpreter lock back during the window's capture, in milliseconds: the
delta of /debug/vars `runtime.interpHandoff` `sumMs` over the delta of its
`n`. The server runs the probe (one thread that sleeps 5 ms again and
again and records by how much it woke late) only while a device profile
capture runs, so only a traced run reads it. None where either end has no
such block (a program older than the probe) or no wake-up was recorded in
the window."""


def read(ctx):
    a = ((ctx.get("vars_before") or {}).get("runtime") or {}).get(
        "interpHandoff")
    b = ((ctx.get("vars_after") or {}).get("runtime") or {}).get(
        "interpHandoff")
    if a is None or b is None:
        return None
    n = b.get("n", 0) - a.get("n", 0)
    if n <= 0:
        return None
    return (b.get("sumMs", 0.0) - a.get("sumMs", 0.0)) / n
