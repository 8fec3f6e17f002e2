"""Host milliseconds a request spends in the planner and the result cache:
the self time of the `plan` spans (plan_call, the cache's key, get and
put), over the window's requests."""

from lib import spans


def read(ctx):
    return spans.per_query(ctx, "selfMs", ("plan",))
