"""Milliseconds a request spends resolving its leaves: the wall time of
the `leaves` spans (residency lookups, and on a first touch the leaf's
build and upload), over the window's requests."""

from lib import spans


def read(ctx):
    return spans.per_query(ctx, "wallMs", ("leaves",))
