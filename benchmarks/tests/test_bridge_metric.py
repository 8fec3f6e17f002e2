"""`plane_bridges_per_query` over hand-made ends of a window: the launches
that bridged a list to a plane a request where the program has the
counter; None, and no exception, where it has not (a program older than
the bridge counter) or where the window answered no request."""

import pytest

from test_span_metrics import reader


def ends(requests: int, bridges) -> dict:
    before = {"hybrid": {"materialized": 3}}
    after = {"hybrid": {"materialized": 3}}
    if bridges is not None:
        before["hybrid"]["planeBridges"] = 900
        after["hybrid"]["planeBridges"] = 900 + bridges
    return {"vars_before": before, "vars_after": after, "requests": requests,
            "trace": None, "peaks": None}


@pytest.mark.parametrize("requests,bridges,want", [
    (410, 1148, 2.8), (4300, 4300, 1.0), (120, 0, 0.0)])
def test_bridges_a_request(requests, bridges, want):
    assert reader("plane_bridges_per_query")(ends(requests, bridges)) == \
        pytest.approx(want)


def test_none_without_the_counter_or_a_request():
    read = reader("plane_bridges_per_query")
    assert read(ends(410, None)) is None           # a program without it
    assert read(ends(0, 0)) is None                 # no request
    assert read({"vars_before": {}, "vars_after": {}, "requests": 0,
                 "trace": None, "peaks": None}) is None
