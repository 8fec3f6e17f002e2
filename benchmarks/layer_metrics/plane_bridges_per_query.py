"""Launches that bridged a sparse list to a plane, per request of the
window: the delta of /debug/vars `hybrid.planeBridges` over the window's
requests. The bridge is a product on the matrix unit (ops/bitvector.py
`_bridge`), launched alone (`sparse_to_dense`) or fused with the AND or
AND-NOT of a list meeting a plane (`sparse_intersect_dense`,
`sparse_difference_dense`); the server counts each launch where it is made.
None on a program without the counter, or where the window answered no
request."""


def read(ctx):
    a = (ctx.get("vars_before") or {}).get("hybrid") or {}
    b = (ctx.get("vars_after") or {}).get("hybrid") or {}
    if "planeBridges" not in a or "planeBridges" not in b \
            or not ctx.get("requests"):
        return None
    return (b["planeBridges"] - a["planeBridges"]) / ctx["requests"]
