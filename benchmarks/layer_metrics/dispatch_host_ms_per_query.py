"""Host milliseconds a request spends enqueueing device programs: the self
time of the `dispatch` spans (the launches return before the device has
run them), over the window's requests."""

from lib import spans


def read(ctx):
    return spans.per_query(ctx, "selfMs", ("dispatch",))
