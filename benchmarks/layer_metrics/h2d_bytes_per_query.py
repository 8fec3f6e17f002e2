"""Bytes of row leaves uploaded to the device per request of the window:
the delta of /debug/vars `hybrid` dense + sparse + run BytesUploaded over
the window's requests."""

KEYS = ("denseBytesUploaded", "sparseBytesUploaded", "runBytesUploaded")


def read(ctx):
    a, b = ctx["vars_before"]["hybrid"], ctx["vars_after"]["hybrid"]
    if not ctx["requests"]:
        return None
    return sum(b[k] - a[k] for k in KEYS) / ctx["requests"]
