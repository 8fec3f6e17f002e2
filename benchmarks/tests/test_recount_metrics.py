"""The three readers `taxi.flight` brings, over hand-made ends of a
window: a value where the program has the span, the counter and the
program's name in the trace; None, and no exception, where it has not (the
parent of the PR that brought them, a CPU run, an empty window)."""

import pytest

from test_span_metrics import entry, reader


def ends(with_recount: bool) -> dict:
    before = {"topnRecountRows": 1000, "spans": {"nowMs": 0.0, "byName": {
        "http.request": entry(66, 1.0, 1.0, 1.0),
        "executor.TopN": entry(40, 1.0, 1.0, 1.0)}}}
    after = {"topnRecountRows": 1000 + 70 * 48, "spans": {
        "nowMs": 50_000.0, "byName": {
            "http.request": entry(66 + 110, 2.0, 2.0, 2.0),
            "executor.TopN": entry(40 + 70, 2.0, 2.0, 2.0)}}}
    if with_recount:
        before["topnPairsBytes"] = 10 ** 9
        after["topnPairsBytes"] = 10 ** 9 + 30 * 100_000_000
        after["spans"]["byName"]["topn.recount"] = entry(
            60, 60 * 900.0, 10.0, 10.0)
    return {"vars_before": before, "vars_after": after, "requests": 110,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"window_s": 4.0, "busy_s": 3.0, "device_ops": [
                ["jit_pairs_count", 1.2], ["jit_intersect_count", 0.5]]}}


def test_readers_read_the_change():
    ctx = ends(True)
    assert reader("topn_recount_ms_per_query")(ctx) == pytest.approx(900.0)
    assert reader("recount_dense_rows_per_query")(ctx) == pytest.approx(48.0)
    # 3e9 bytes a 50 s window, 4 s of it traced: 240 MB over 819 GB/s is
    # 0.293 ms of the 1.2 s the program ran
    assert reader("recount_roofline")(ctx) == pytest.approx(
        100 * (3e9 * 4 / 50) / 819e9 / 1.2)
    assert reader("recount_roofline")(ctx) < 100


def test_readers_on_the_parent_and_on_nothing():
    parent = ends(False)
    assert reader("topn_recount_ms_per_query")(parent) is None
    assert reader("recount_roofline")(parent) is None
    # the walk's own counter is older than this PR: the parent reads too
    assert reader("recount_dense_rows_per_query")(parent) == \
        pytest.approx(48.0)
    for ctx in (
            {**ends(True), "trace": None},
            {**ends(True), "trace": {"window_s": 4.0, "busy_s": 3.0,
                                     "device_ops": [["jit_other", 1.0]]}},
            {**ends(True), "vars_before": {}, "vars_after": {}},
            {**ends(True), "peaks": None}):
        assert reader("recount_roofline")(ctx) is None
    empty = {"vars_before": {}, "vars_after": {}, "requests": 0,
             "trace": None, "peaks": None}
    for name in ("topn_recount_ms_per_query", "recount_dense_rows_per_query",
                 "recount_roofline"):
        assert reader(name)(empty) is None
