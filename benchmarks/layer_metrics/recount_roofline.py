"""How close the recount from sorted columns came to the least time the
chip could take for it: the bytes the traced window's recounts had to
read over the chip's peak memory bandwidth (lib/peaks.json), over the
seconds the recount's program ran in the trace.

The bytes are the server's own count, /debug/vars `topnPairsBytes`: for
every launch 4 bytes a stored bit of the rows it recounted plus 128 KiB a
shard for the filter plane - from the data and the request, not from what
was uploaded or how the kernel reads it. The counter covers the whole
window, the trace a few seconds of it, so it is scaled by the traced
window's share of the window (the window's length on the server's clock:
the span table's `nowMs`). The program is the jitted function
`pairs_count` (its mesh form `pairs_count_mesh`), found by name among the
trace's device programs. None where the server has no such counter, the
window launched no recount, or the trace does not name the program; never
0."""

from lib import spans

PROGRAM = "jit_pairs_count"


def read(ctx):
    trace = ctx.get("trace")
    a = (ctx.get("vars_before") or {}).get("topnPairsBytes")
    b = (ctx.get("vars_after") or {}).get("topnPairsBytes")
    d = spans.delta(ctx)
    if not trace or a is None or b is None or d is None or not ctx["peaks"]:
        return None
    ran_s = sum(s for name, s in trace.get("device_ops", [])
                if name.startswith(PROGRAM))
    window_ms = d.get("nowMs", 0.0)
    if ran_s <= 0 or window_ms <= 0 or b <= a:
        return None
    traced_bytes = (b - a) * (trace["window_s"] * 1e3 / window_ms)
    return 100.0 * traced_bytes / ctx["peaks"]["hbm_bytes_per_s"] / ran_s
