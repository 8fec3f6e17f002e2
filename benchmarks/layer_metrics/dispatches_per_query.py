"""Device dispatches per request of the window: the delta of /debug/vars
`kernels.dispatches` (every counted jitted call, whatever its family) over
the window's requests."""


def read(ctx):
    a, b = ctx["vars_before"]["kernels"], ctx["vars_after"]["kernels"]
    if not ctx["requests"]:
        return None
    return (b["dispatches"] - a["dispatches"]) / ctx["requests"]
