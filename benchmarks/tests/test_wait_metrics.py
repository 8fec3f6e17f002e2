"""The three readers of where the server's threads wait (`locks`,
`runtime.gc`, `runtime.interpHandoff` of /debug/vars) over hand-made ends
of a window, and over the ends a program without the counters gives."""

import importlib.util
import json
import os

import pytest

from conftest import BENCH

NAMES = ("lock_wait_ms_per_query", "gc_pause_pct", "interp_handoff_ms")


def reader(name):
    path = os.path.join(BENCH, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"layer_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def lock(waited, wait_ms, most):
    return {"waited": waited, "waitMs": wait_ms, "maxWaitMs": most}


def gen(n, pause_ms, most):
    return {"n": n, "pauseMs": pause_ms, "maxPauseMs": most}


def handoff(n, sum_ms, most):
    return {"n": n, "sumMs": sum_ms, "maxMs": most, "buckets": {}}


# set-up left waits and collections behind; the window (2,000 ms of the
# server's clock, 10 requests) adds 30 ms at `heat`, 20 at a lock first
# met in it, two collections of 14 ms in all, and a capture's 400 wake-ups
# that came 600 ms late in all
BEFORE = {
    "spans": {"nowMs": 10_000.0, "byName": {}},
    "locks": {"heat": lock(40, 500.0, 90.0),
              "residency.lru": lock(3, 2.0, 1.0)},
    "runtime": {"gc": {"0": gen(900, 90.0, 1.0), "1": gen(80, 40.0, 2.0),
                       "2": gen(3, 600.0, 280.0)},
                "interpHandoff": handoff(0, 0.0, 0.0)},
}
AFTER = {
    "spans": {"nowMs": 12_000.0, "byName": {}},
    "locks": {"heat": lock(52, 530.0, 90.0),
              "residency.lru": lock(3, 2.0, 1.0),
              "residency.build": lock(4, 20.0, 9.0)},
    "runtime": {"gc": {"0": gen(901, 90.5, 1.0), "1": gen(80, 40.0, 2.0),
                       "2": gen(4, 613.5, 280.0)},
                "interpHandoff": handoff(400, 600.0, 20.0)},
}

WANT = {
    "lock_wait_ms_per_query": (30.0 + 20.0) / 10,
    "gc_pause_pct": (0.5 + 13.5) / 2000.0 * 100,
    "interp_handoff_ms": 600.0 / 400,
}


def read(name, before, after, requests=10):
    return reader(name)({"vars_before": before, "vars_after": after,
                         "requests": requests})


@pytest.mark.parametrize("name", NAMES)
def test_reader_over_a_hand_made_window(name):
    assert read(name, BEFORE, AFTER) == pytest.approx(WANT[name])


# a program older than the counters: /debug/vars has spans, no `locks`,
# no `runtime`
PARENT = {"spans": {"nowMs": 10_000.0, "byName": {}},
          "countBatcher": {}}
PARENT_AFTER = {"spans": {"nowMs": 12_000.0, "byName": {}},
                "countBatcher": {}}


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("before,after", [
    (PARENT, PARENT_AFTER), (PARENT, AFTER), (BEFORE, PARENT_AFTER),
    ({}, {}), (None, None)], ids=["parent", "parent-before",
                                  "parent-after", "empty", "missing"])
def test_reader_gives_none_on_a_program_without_the_counters(
        name, before, after):
    assert read(name, before, after) is None


def test_a_window_without_a_wait_reads_zero_and_without_a_capture_none():
    assert read("lock_wait_ms_per_query", BEFORE, {
        **BEFORE, "spans": AFTER["spans"]}) == 0.0
    assert read("gc_pause_pct", BEFORE, {
        **BEFORE, "spans": AFTER["spans"]}) == 0.0
    # untraced: no capture, the probe never ran in the window
    assert read("interp_handoff_ms", BEFORE, {
        **BEFORE, "spans": AFTER["spans"]}) is None
    # no request: nothing to share the waits over
    assert read("lock_wait_ms_per_query", BEFORE, AFTER, requests=0) is None
    # no clock: nothing to share the pauses over
    assert read("gc_pause_pct", BEFORE, {**AFTER, "spans": BEFORE["spans"]}
                ) is None


def test_the_three_are_declared_as_counters_of_the_host_threads():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    declared = {m["name"]: m for m in bench["per_layer"]
                if m["name"] in NAMES}
    assert set(declared) == set(NAMES)
    for m in declared.values():
        assert m["source"] == "program_counter"
        assert m["layer"] == "host threads"
        assert m["better"] == "lower"
        assert "workloads" not in m
    assert declared["interp_handoff_ms"]["moves"] == "query_p50_ms"
    assert declared["lock_wait_ms_per_query"]["moves"] == "queries_per_s"
    assert declared["gc_pause_pct"]["moves"] == "queries_per_s"
