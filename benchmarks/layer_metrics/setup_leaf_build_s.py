"""Host CPU seconds spent building leaves before the window opened: the
thread CPU time of the `leaf.build` spans at the window's first end (the
first touch of every row, which the warm-up's requests wait for)."""


def read(ctx):
    table = (ctx.get("vars_before") or {}).get("spans")
    build = (table or {}).get("byName", {}).get("leaf.build")
    if not build or build.get("n", 0) <= 0:
        return None
    return build["cpuMs"] / 1e3
