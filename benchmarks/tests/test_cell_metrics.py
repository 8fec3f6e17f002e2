"""What each cell of BENCHMARK.json reports, as the harness selects it
(`run.metrics_of`): its end-to-end metrics under `--trace 0` and its
per-layer metrics under `--trace 1`, at least the sets below. An unscoped
per-layer metric reads only in a cell that reports the end-to-end metric
it moves, so a tail taken out of a cell's judged metrics would take with
it every reading set against that tail; each reading below stays, under
one name in every cell (no alias reader named after a cell). Later PRs
add cells and metrics without editing this file, so a set here is what a
cell keeps, not all it may read."""

import json
import os

import pytest

from conftest import BENCH, REPO
import run as harness
from test_span_metrics import reader

# read in every cell: set against query_p50_ms, queries_per_s or setup_s
EVERY_CELL = {
    "plan_cache_hit_pct", "dispatches_per_query", "window_compiles",
    "kernels_roofline", "device_idle_pct", "http_parse_ms_per_query",
    "plan_ms_per_query", "leaf_resolve_ms_per_query",
    "dispatch_host_ms_per_query", "device_wait_ms_per_query",
    "host_reduce_ms_per_query", "host_cpu_pct", "setup_leaf_build_s",
    "lock_wait_ms_per_query", "gc_pause_pct", "interp_handoff_ms",
}
NO_TAIL = {"query_p50_ms", "queries_per_s", "setup_s"}

CELLS = {
    "segmentation.adhoc": (NO_TAIL, EVERY_CELL | {
        "batch_mean_queries", "batcher_wait_ms_per_query",
        "plane_bridges_per_query", "tail_p95_ms"}),
    "segmentation-mesh4.adhoc": (NO_TAIL, EVERY_CELL | {
        "mesh_collective_launches_per_query", "mesh_kernels_roofline",
        "plane_bridges_per_query", "tail_p95_ms"}),
    "taxi.flight": (NO_TAIL | {"query_p95_ms"}, EVERY_CELL | {
        "residency_hit_pct", "h2d_bytes_per_query",
        "topn_recount_ms_per_query", "recount_dense_rows_per_query",
        "recount_roofline", "recount_slab_pct"}),
    "chem-similarity.similar": (NO_TAIL, EVERY_CELL | {
        "tanimoto_band_keep_pct", "topn_band_ms_per_query",
        "recount_roofline", "recount_slab_pct"}),
}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_a_cell_reads_no_metric_set_against_one_it_does_not_report(bench):
    assert set(CELLS) <= {w["name"] for w in bench["workloads"]}
    for cell in bench["workloads"]:
        end = {m["name"]
               for m in harness.metrics_of(bench, cell, "end_to_end")}
        layer = harness.metrics_of(bench, cell, "per_layer")
        assert "setup_s" in end and len(end) >= 2 and layer
        assert all(m["moves"] in end for m in layer)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_what_each_cell_reads(bench, name):
    cell = {w["name"]: w for w in bench["workloads"]}[name]
    end = {m["name"] for m in harness.metrics_of(bench, cell, "end_to_end")}
    names = {m["name"]
             for m in harness.metrics_of(bench, cell, "per_layer")}
    want_end, want_layer = CELLS[name]
    assert want_end <= end and want_layer <= names
    # the tail is judged where it repeats and recorded where it does not
    assert ("query_p95_ms" in end) == ("query_p95_ms" in want_end)
    assert ("tail_p95_ms" in names) == ("tail_p95_ms" in want_layer)
    assert not {n for n in names if n.endswith((".mesh4", ".similar"))}


def test_no_reader_is_an_alias():
    files = os.listdir(os.path.join(BENCH, "layer_metrics"))
    assert not [f for f in files if f.endswith((".mesh4.py", ".similar.py"))]


def test_the_tail_on_one_chip():
    # a one-chip traced window: one device plane, the tail of every
    # request whatever the trace holds
    ms = [5.0] * 90 + [300.0] * 8 + [900.0] * 2
    ctx = {"latencies_ms": ms, "requests": len(ms), "window_compiles": 0,
           "trace": {"window_s": 4.0, "busy_s": 1.2,
                     "devices": ["/device:TPU:0"]}}
    assert reader("tail_p95_ms")(ctx) == 300.0
    assert reader("tail_p95_ms")({**ctx, "trace": None}) == 300.0
    assert reader("tail_p95_ms")({**ctx, "latencies_ms": []}) is None
