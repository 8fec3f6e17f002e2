"""Milliseconds a filtered TopN spends recounting its candidates under
the filter: the wall time of the `topn.recount` spans (the walk over
stacked planes for rows above the sparse threshold, the one launch from
the field's sorted columns for the rows below it, their fetches and the
merge), over the `topn.recount` spans themselves, since only a filtered
TopN has one. None on a program without the span."""

from lib import spans


def read(ctx):
    d = spans.delta(ctx)
    if d is None or d.get("topn.recount", {}).get("n", 0) <= 0:
        return None
    return d["topn.recount"]["wallMs"] / d["topn.recount"]["n"]
