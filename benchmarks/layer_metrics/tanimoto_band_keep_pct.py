"""Share of a TopN's candidates that the Tanimoto band keeps: the delta of
/debug/vars `topnBandKept` over that of `topnBandIn` across the window, in
%. A candidate leaves the band where its exact row count lies outside
(|src| T/100, |src| 100/T) and is never recounted. None on a program
without the counters, or where no TopN under `tanimotoThreshold` ran."""


def read(ctx):
    before = ctx.get("vars_before") or {}
    after = ctx.get("vars_after") or {}
    if any(k not in d for d in (before, after)
           for k in ("topnBandIn", "topnBandKept")):
        return None
    seen = after["topnBandIn"] - before["topnBandIn"]
    if seen <= 0:
        return None
    return 100.0 * (after["topnBandKept"] - before["topnBandKept"]) / seen
