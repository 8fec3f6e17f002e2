"""The table of device peaks, keyed by the device kind JAX reports. A
device that is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os


def peaks_of(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add it to benchmarks/lib/peaks.json with its source")
    return table[device_kind]
