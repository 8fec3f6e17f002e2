"""How close the device's busy time came to the least the chip could take
for the traced window's requests: bytes needed (lib/work.py: from the
requests and the data, not from what the program uploaded or dispatched)
over the chip's peak memory bandwidth (lib/peaks.json), over the seconds an
operation ran on the device in the trace. Memory-bound: bitmap algebra
does next to no arithmetic per byte read. The requests counted are those
answered inside the traced window, so at its edges a request's device work
may fall outside it; the window holds some thousand requests."""


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] <= 0 or not ctx["traced_bytes_needed"]:
        return None
    least_s = ctx["traced_bytes_needed"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
