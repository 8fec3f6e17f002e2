"""Host milliseconds a request spends after the fetch and around the
stages: the self time of the `reduce` spans (the host sum, translation of
the result) and of every `executor.<Call>` span (a call's own time outside
its stages), over the window's requests."""

from lib import spans


def read(ctx):
    return spans.per_query(ctx, "selfMs", ("reduce", "executor.*"))
