"""Programs the server traced for the first time inside the window: the
delta of /metrics `pilosa_xlaCompiles_total` over all kernel families.
Each is a shape the warm-up did not meet — a compile, or a fetch from the
persistent cache — and a request that waited for it. The eager hybrid path
keys its programs on every operand's padded size, so ad-hoc trees keep
meeting new ones; 0 is a real reading here, not a missing one."""


def read(ctx):
    return float(ctx["window_compiles"])
