"""The trace reduction on a small recorded trace with known busy time,
idle gaps and per-operation sums, and with host threads whose spans name
one gap and leave another unattributed."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH
import xplane_fixture

MS = 1_000_000  # ns


@pytest.fixture()
def recorded(tmp_path):
    path = str(tmp_path / "host.xplane.pb")
    dev0 = {
        "XLA Ops": [("fusion.1", 10 * MS, 20 * MS),     # 10..30
                    ("fusion.2", 25 * MS, 15 * MS),     # 25..40 overlaps
                    ("copy.3", 100 * MS, 5 * MS),       # 100..105
                    ("fusion.1", 400 * MS, 10 * MS),    # 400..410
                    ("fusion.9", 600 * MS, 10 * MS)],   # 600..610
        # not operations; two shapes of one jitted function
        "XLA Modules": [("jit_band(123456789)", 0, 300 * MS),
                        ("jit_band(987654321)", 400 * MS, 100 * MS),
                        # linked to its launch by run_id, not by name
                        ("jit_bor(5)", 600 * MS, 10 * MS, {"run_id": 77})],
    }
    dev1 = {"XLA Ops": [("fusion.1", 0, 60 * MS)]}
    # thread a launches the program the longest gap (105..400) ends at,
    # from inside pilosa.dispatch; most of the gap it spent in
    # pilosa.leaves, all of it under pilosa.executor.Count. Thread b, busy
    # in a span of its own all along, launched nothing then. The gap
    # 40..100 ends at an operation inside a program (no launch of its own):
    # the last launch before it is thread c's, which has no span there.
    host = {
        "python3/a": [("pilosa.executor.Count", 90 * MS, 330 * MS),
                      ("pilosa.leaves", 110 * MS, 250 * MS),
                      ("pilosa.dispatch", 360 * MS, 50 * MS),
                      ("PjitFunction(band)", 399 * MS, 1 * MS)],
        "python3/b": [("pilosa.http.request", 0, 900 * MS),
                      ("PjitFunction(bor)", 5 * MS, 1 * MS),
                      ("PJRT_LoadedExecutable_Execute", 598 * MS, 1 * MS,
                       {"run_id": 77})],
        "python3/c": [("PjitFunction(band)", 50 * MS, 1 * MS),
                      ("pilosa.plan", 120 * MS, 10 * MS)],
    }
    xplane_fixture.write(path, [
        ("/device:TPU:0", dev0, {}),
        ("/device:TPU:1", dev1, {}),
        ("/host:CPU", host, {}),
        ("Task Environment", {}, {"profile_start_time": 5_000_000_000,
                                  "profile_stop_time": 6_000_000_000}),
    ])
    return path


def test_known_busy_idle_and_sums(recorded):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "lib", "trace.py"), recorded],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["window_s"] == pytest.approx(1.0)
    # device 0: 10..40, 100..105, 400..410, 600..610 = 55 ms; device 1: 60
    assert got["busy_s"] == pytest.approx((0.055 + 0.060) / 2)
    assert got["op_sum_s"] == pytest.approx((0.060 + 0.060) / 2)
    assert got["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    # named by compiled program where the device has that line (device 0),
    # by operation where it has not (device 1)
    ops = dict(got["device_ops"])
    assert ops == {"jit_band": pytest.approx(0.4),
                   "jit_bor": pytest.approx(0.010),
                   "fusion.1": pytest.approx(0.060)}
    # 105..400 by the launch of its own function (thread a), 410..600 by
    # the run_id (thread b), 40..100 by the last launch (thread c: no span)
    assert got["idle_gaps"] == [
        ["pilosa.leaves", pytest.approx(0.295)],
        ["pilosa.http.request", pytest.approx(0.190)],
        ["unattributed", pytest.approx(0.060)]]


def test_a_gap_is_named_by_the_span_that_owns_most_of_it():
    from lib import trace

    spans = {7: [(0.0, 100.0, "pilosa.http.request"),
                 (10.0, 90.0, "pilosa.executor.Count"),
                 (20.0, 30.0, "pilosa.leaves"),
                 (30.0, 80.0, "pilosa.dispatch")]}
    launches = [(5.0, 3, "bor"), (79.0, 7, "band"), (79.5, 3, "bor")]
    # by the profiler's own link where the trace has it ...
    assert trace.name_gaps([(40.0, 40.0)], {80.0: ("bor", 41)}, launches,
                           spans, {41: 7, 40: 3}) == ["pilosa.dispatch"]
    # ... else by the program's own function where the device names it ...
    assert trace.name_gaps([(40.0, 40.0)], {80.0: ("band", None)}, launches,
                           spans) == ["pilosa.dispatch"]
    assert trace.name_gaps([(40.0, 40.0)], {80.0: ("band", 99)}, launches,
                           spans, {41: 7}) == ["pilosa.dispatch"]
    # ... else by the last launch before the gap's end: thread 3, no span
    assert trace.name_gaps([(40.0, 40.0)], {}, launches, spans) == [
        "unattributed"]
    # the span's own time counts, not its children's: 12..20 and 90..95
    # are executor.Count's and http.request's own
    assert trace.name_gaps([(12.0, 10.0)], {}, [(1.0, 7, "f")], spans) == [
        "pilosa.executor.Count"]
    assert trace.name_gaps([(91.0, 4.0)], {}, [(1.0, 7, "f")], spans) == [
        "pilosa.http.request"]
    # no launch before the gap's end: the thread is not known
    assert trace.name_gaps([(0.0, 4.0)], {}, [(5.0, 7, "f")], spans) == [
        "unattributed"]
