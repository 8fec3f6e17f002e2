"""Milliseconds a TopN under `tanimotoThreshold` spends on its Tanimoto
band's own host work: the `selfMs` of the `topn.band` spans (every
candidate's exact row count from container metadata, the band's mask),
over the `topn.band` spans themselves, since only such a TopN has one.
The span's `dispatch` and `device.wait` children, the filter's count
fetched from the device behind the queue of other requests' recounts,
are left out: `device_wait_ms_per_query` holds that wait. None on a
program without the span."""

from lib import spans


def read(ctx):
    d = spans.delta(ctx)
    if d is None or d.get("topn.band", {}).get("n", 0) <= 0:
        return None
    return d["topn.band"]["selfMs"] / d["topn.band"]["n"]
