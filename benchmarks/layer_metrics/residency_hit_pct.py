"""Share of the window's device-residency lookups that found their leaf on
the device: /debug/vars `deviceResidency` hits / (hits + misses), as deltas
over the window."""


def read(ctx):
    a = ctx["vars_before"]["deviceResidency"]
    b = ctx["vars_after"]["deviceResidency"]
    hits = b["hits"] - a["hits"]
    lookups = hits + b["misses"] - a["misses"]
    if lookups <= 0:
        return None
    return 100.0 * hits / lookups
