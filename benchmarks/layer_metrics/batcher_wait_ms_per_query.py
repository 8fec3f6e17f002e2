"""Milliseconds a Count that went through the continuous batcher waited
there, submit to delivery: the wall time of the `batcher.wait` spans over
their own number (not every request reaches the batcher). A window that
never reached it reads nothing."""

from lib import spans


def read(ctx):
    d = spans.delta(ctx)
    if d is None or d.get("batcher.wait", {}).get("n", 0) <= 0:
        return None
    return d["batcher.wait"]["wallMs"] / d["batcher.wait"]["n"]
