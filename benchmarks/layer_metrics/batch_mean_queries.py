"""Mean Count queries per batch of the continuous batcher over the window:
/debug/vars `countBatcher` batched_queries / batches, as deltas. A window
that never reached the batcher reads nothing."""


def read(ctx):
    a, b = ctx["vars_before"]["countBatcher"], ctx["vars_after"]["countBatcher"]
    batches = b["batches"] - a["batches"]
    if batches <= 0:
        return None
    return (b["batched_queries"] - a["batched_queries"]) / batches
