"""The 95th percentile (nearest rank) of the client-seen times of all the
window's requests, in a cell where that tail is no end-to-end metric: in
`segmentation-mesh4.adhoc` it follows which state of the interpreter lock
the server spent the window in (271, 312-316 and 448-455 ms on one
program, PERF.md section 6), so it is recorded here and judged nowhere.
The benchmark's own clock, as `query_p95_ms` is."""

from lib import stats


def read(ctx):
    ms = ctx.get("latencies_ms")
    return stats.percentile(ms, 95) if ms else None
