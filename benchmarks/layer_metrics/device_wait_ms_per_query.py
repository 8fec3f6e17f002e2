"""Milliseconds a request is blocked on the device: the wall time of the
`device.wait` spans (the one fetch that waits for what `dispatch`
enqueued, behind every other thread's programs), over the window's
requests."""

from lib import spans


def read(ctx):
    return spans.per_query(ctx, "wallMs", ("device.wait",))
