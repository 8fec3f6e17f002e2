"""Share of the window's result-cache lookups that hit: /debug/vars
`planCache` hits / (hits + misses), as deltas over the window. The cache
(`PlanCache`, parallel/residency.py) holds evaluated sub-trees and Count
scalars, keyed by the data's generation."""


def read(ctx):
    a, b = ctx["vars_before"]["planCache"], ctx["vars_after"]["planCache"]
    hits = b["hits"] - a["hits"]
    lookups = hits + b["misses"] - a["misses"]
    if lookups <= 0:
        return None
    return 100.0 * hits / lookups
