"""Each span metric's reader over a hand-made pair of /debug/vars ends,
and over ends that give it nothing to read."""

import importlib.util
import os

import pytest

from conftest import BENCH
from lib import spans


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def entry(n, wall, self_ms, cpu):
    return {"n": n, "wallMs": wall, "selfMs": self_ms, "cpuMs": cpu,
            "buckets": {}}


# set-up left 50 requests behind; the window adds 10, over 2,000 ms of the
# server's clock
BEFORE = {"spans": {"nowMs": 10_000.0, "byName": {
    "http.request": entry(50, 5000.0, 50.0, 900.0),
    "http.read": entry(50, 5.0, 5.0, 5.0),
    "plan": entry(100, 100.0, 100.0, 100.0),
    "leaf.build": entry(400, 81_000.0, 81_000.0, 64_500.0),
    "leaves": entry(50, 90_000.0, 1000.0, 1000.0),
}}}
AFTER = {"spans": {"nowMs": 12_000.0, "byName": {
    "http.request": entry(60, 25_000.0, 60.0, 1700.0),     # +10, self +10
    "http.other": entry(3, 9000.0, 9000.0, 9000.0),        # never counted
    "http.read": entry(60, 6.0, 6.0, 6.0),                 # self +1
    "http.admit": entry(10, 2.0, 2.0, 2.0),                # self +2
    "pql.parse": entry(10, 3.0, 3.0, 3.0),                 # self +3
    "http.encode": entry(10, 4.0, 4.0, 4.0),               # self +4
    "http.write": entry(10, 5.0, 5.0, 5.0),                # self +5
    "plan": entry(130, 160.0, 160.0, 160.0),               # self +60
    "batcher.wait": entry(4, 120.0, 20.0, 8.0),            # 4 of the 10
    "leaf.build": entry(401, 81_003.0, 81_003.0, 64_502.0),
    "leaves": entry(60, 90_040.0, 1030.0, 1030.0),         # wall +40
    "dispatch": entry(10, 700.0, 700.0, 650.0),            # self +700
    "device.wait": entry(10, 18_000.0, 18_000.0, 30.0),    # wall +18,000
    "reduce": entry(20, 10.0, 10.0, 10.0),                 # self +10
    "executor.Count": entry(10, 19_900.0, 80.0, 900.0),    # self +80
    "executor.TopN": entry(1, 500.0, 10.0, 10.0),          # self +10
}}}

WANT = {
    "http_parse_ms_per_query": (10 + 1 + 2 + 3 + 4 + 5) / 10,
    "plan_ms_per_query": 60 / 10,
    "batcher_wait_ms_per_query": 120 / 4,
    "leaf_resolve_ms_per_query": 40 / 10,
    "dispatch_host_ms_per_query": 700 / 10,
    "device_wait_ms_per_query": 18_000 / 10,
    "host_reduce_ms_per_query": (10 + 80 + 10) / 10,
    "host_cpu_pct": (1700 - 900) / 2000 * 100,
    "setup_leaf_build_s": 64.5,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_over_a_hand_made_window(name):
    got = reader(name)({"vars_before": BEFORE, "vars_after": AFTER,
                        "requests": 10})
    assert got == pytest.approx(WANT[name])


EMPTY_ENDS = [
    # the same table at both ends: nothing finished in the window
    ("no-delta", BEFORE, BEFORE),
    # a program without the table (the parent of the PR that brought it)
    ("no-table", {"countBatcher": {}}, {"countBatcher": {}}),
    ("empty-table", {"spans": {"nowMs": 1.0, "byName": {}}},
     {"spans": {"nowMs": 2.0, "byName": {}}}),
]


@pytest.mark.parametrize("name", sorted(WANT))
@pytest.mark.parametrize("case,before,after", EMPTY_ENDS,
                         ids=[c[0] for c in EMPTY_ENDS])
def test_reader_returns_none_on_an_empty_delta(name, case, before, after):
    got = reader(name)({"vars_before": before, "vars_after": after,
                        "requests": 0})
    if name == "setup_leaf_build_s" and case == "no-delta":
        # a reading of set-up, not of the window: the first end alone
        assert got == pytest.approx(64.5)
    else:
        assert got is None


def test_delta_and_prefix_names():
    d = spans.delta({"vars_before": BEFORE, "vars_after": AFTER})
    assert d["nowMs"] == 2000.0
    assert d["http.request"]["n"] == 10
    assert d["device.wait"]["wallMs"] == 18_000.0      # absent before: from 0
    assert sorted(spans.names_of(d, ("reduce", "executor.*"))) == [
        "executor.Count", "executor.TopN", "reduce"]
    assert spans.delta({"vars_before": {}, "vars_after": AFTER}) is None


def test_every_span_metric_is_declared_with_its_file():
    import json

    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    declared = {m["name"]: m for m in bench["per_layer"]
                if m["source"] == "program_span"}
    assert set(declared) == set(WANT)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in declared.values():
        assert m["moves"] in e2e and m["better"] == "lower"
