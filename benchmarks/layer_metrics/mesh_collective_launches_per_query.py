"""Launches of a program that holds a collective (a psum or a GSPMD
reduce across the mesh's shard axis) per request of the window: the delta
of /debug/vars `mesh.collectiveLaunches` over the window's requests. The
server counts a launch where it is made, as collective or local, and only
where it went to more than one device. None where the server has no
`mesh` block (a program older than the counter) or holds one device: the
counter cannot move there."""


def read(ctx):
    a = (ctx.get("vars_before") or {}).get("mesh")
    b = (ctx.get("vars_after") or {}).get("mesh")
    if not a or not b or b.get("devices", 1) < 2 or not ctx.get("requests"):
        return None
    return ((b["collectiveLaunches"] - a["collectiveLaunches"])
            / ctx["requests"])
