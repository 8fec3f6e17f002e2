"""kernels_roofline against the whole node's memory bandwidth: the bytes
the traced window's requests need (lib/work.py: from the requests and the
data, whatever implements them) over the chips' summed peak (the device
planes of the trace times one chip's row of lib/peaks.json), over the
seconds an operation ran on a device (lib/trace.py: the mean over the
device planes). On n chips it reads a n-th of kernels_roofline, which
holds the same bytes against one chip's peak. None where the trace holds
fewer than two device planes (a one-chip cell has kernels_roofline), no
operation ran or no traced request was answered; never 0."""


def read(ctx):
    trace = ctx.get("trace")
    need = ctx.get("traced_bytes_needed")
    if not trace or not ctx.get("peaks") or not need:
        return None
    chips = len(trace.get("devices") or [])
    if chips < 2 or trace["busy_s"] <= 0:
        return None
    least_s = need / (chips * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / trace["busy_s"]
