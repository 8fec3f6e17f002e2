"""Share of the window's pairs recounts that ran over an entry laid as a
window slab (the rows' own words over the one window of words their
columns touch; no gather, no one-hot product): the delta of /debug/vars
`topnPairsRecountsBySlab` over that of `topnPairsRecounts`, in %. It reads
whether the layout engages where the rows' columns lie in a narrow window
(100 in `chem-similarity.similar`) and stays out where they run across the
shard (0 in `taxi.flight`). None on a program without the counter, or
where the window launched no recount."""


def read(ctx):
    before = ctx.get("vars_before") or {}
    after = ctx.get("vars_after") or {}
    if any(k not in d for d in (before, after)
           for k in ("topnPairsRecounts", "topnPairsRecountsBySlab")):
        return None
    seen = after["topnPairsRecounts"] - before["topnPairsRecounts"]
    if seen <= 0:
        return None
    return 100.0 * (after["topnPairsRecountsBySlab"]
                    - before["topnPairsRecountsBySlab"]) / seen
