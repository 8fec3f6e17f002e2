"""Share of the traced window in which no operation ran on the device:
1 - busy_s / window_s of the trace reduction (lib/trace.py)."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
