"""`recount_slab_pct` over hand-made ends of a window: the share of the
recounts laid as a window slab where the program has the counter; None,
and no exception, where it has not (a program older than the slab) or
where the window launched no recount."""

import pytest

from test_span_metrics import reader


def ends(recounts: int, by_slab) -> dict:
    before = {"topnPairsRecounts": 40}
    after = {"topnPairsRecounts": 40 + recounts}
    if by_slab is not None:
        before["topnPairsRecountsBySlab"] = 40
        after["topnPairsRecountsBySlab"] = 40 + by_slab
    return {"vars_before": before, "vars_after": after, "requests": 120,
            "trace": None, "peaks": None}


@pytest.mark.parametrize("recounts,by_slab,want", [
    (120, 120, 100.0), (2709, 0, 0.0), (10, 4, 40.0)])
def test_the_share_of_slab_recounts(recounts, by_slab, want):
    assert reader("recount_slab_pct")(ends(recounts, by_slab)) == \
        pytest.approx(want)


def test_none_without_the_counter_or_a_recount():
    read = reader("recount_slab_pct")
    assert read(ends(120, None)) is None           # a program without it
    assert read(ends(0, 0)) is None                 # no recount
    assert read({"vars_before": {}, "vars_after": {}, "requests": 0,
                 "trace": None, "peaks": None}) is None
