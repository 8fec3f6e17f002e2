"""The two readers `segmentation-mesh4.adhoc` brings, over hand-made ends
of a window: a value where the server has a `mesh` block of several
devices and the trace several device planes; None, and no exception, on
the parent of the PR that brought them (no `mesh` block), on one chip and
on nothing. And the tail the cell records as a per-layer reading, since
`query_p95_ms` is no end-to-end metric of it (what each cell reports:
test_cell_metrics.py)."""

import pytest

from test_span_metrics import reader

PLANES = ["/device:TPU:0", "/device:TPU:1", "/device:TPU:2", "/device:TPU:3"]


def ends(devices: int = 4) -> dict:
    def mesh(launches):
        return {"devices": devices, "shardSlots": devices,
                "collectiveLaunches": launches, "localLaunches": 9 * launches,
                "collectiveThreads": 1 if launches else 0}
    return {"vars_before": {"mesh": mesh(100)},
            "vars_after": {"mesh": mesh(100 + 700)},
            "requests": 4000, "traced_bytes_needed": 2 * 819e9 // 100,
            "peaks": {"hbm_bytes_per_s": 819e9},
            "trace": {"window_s": 4.0, "busy_s": 0.5,
                      "devices": PLANES[:devices]}}


def test_readers_read_a_mesh():
    ctx = ends()
    assert reader("mesh_collective_launches_per_query")(ctx) == \
        pytest.approx(700 / 4000)
    # 1 % of a second of one chip's bandwidth, over four chips busy half a
    # second each: a quarter of what kernels_roofline reads
    share = reader("mesh_kernels_roofline")(ctx)
    assert share == pytest.approx(100 * 0.02 / 4 / 0.5)
    assert share == pytest.approx(reader("kernels_roofline")(ctx) / 4)
    assert 0 < share < 100


def test_readers_on_the_parent_on_one_chip_and_on_nothing():
    parent = {**ends(), "vars_before": {}, "vars_after": {}}
    assert reader("mesh_collective_launches_per_query")(parent) is None
    one = ends(devices=1)
    assert reader("mesh_collective_launches_per_query")(one) is None
    assert reader("mesh_kernels_roofline")(one) is None
    for ctx in ({**ends(), "trace": None}, {**ends(), "peaks": None},
                {**ends(), "traced_bytes_needed": 0},
                {**ends(), "trace": {"window_s": 4.0, "busy_s": 0.0,
                                     "devices": PLANES}}):
        assert reader("mesh_kernels_roofline")(ctx) is None
    empty = {"vars_before": {}, "vars_after": {}, "requests": 0,
             "trace": None, "peaks": None}
    for name in ("mesh_collective_launches_per_query",
                 "mesh_kernels_roofline"):
        assert reader(name)(empty) is None


def test_the_tail_where_it_is_no_end_to_end_metric():
    ms = [float(k) for k in range(1, 201)]          # nearest rank: 190
    assert reader("tail_p95_ms")({"latencies_ms": ms[::-1]}) == 190.0
    assert reader("tail_p95_ms")({"latencies_ms": []}) is None
    assert reader("tail_p95_ms")({}) is None
