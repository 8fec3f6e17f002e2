"""Dense planes walked a TopN: the delta of /debug/vars
`topnRecountRows` (rows that went through the walk over [S, W] planes,
one plane a candidate row whatever the row holds) over the
window's `executor.TopN` spans. A program that recounts a field's small
rows from their sorted columns reads about the count of its candidate
rows above the sparse threshold here; the walk alone reads up to every
row of the field. None where the server has no such counter or no TopN
finished in the window."""

from lib import spans


def read(ctx):
    a = (ctx.get("vars_before") or {}).get("topnRecountRows")
    b = (ctx.get("vars_after") or {}).get("topnRecountRows")
    d = spans.delta(ctx)
    if a is None or b is None or d is None:
        return None
    calls = d.get("executor.TopN", {}).get("n", 0)
    if calls <= 0:
        return None
    return (b - a) / calls
