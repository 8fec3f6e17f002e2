"""The benchmark's own command on the CPU, in tier-1 (PERF.md section 7
asked for it): `benchmarks/run.py --rehearse --shards 2` on `taxi.flight`
- the 20 fields and both 10,000-row grids over import-roaring, the
flight's warm-up, a short window, the comparison with the plain reference
- comes out `correct`, and with `--control` (the reference's count hook
broken) it does not, on every label. The benchmark's other tests stay
under benchmarks/tests (`python -m pytest benchmarks/tests`).

The server is the harness's child on the CPU backend and never takes a
chip; it gets one CPU device, as the cell gets one chip (pytest's own
process runs on eight virtual ones, tests/conftest.py)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LABELS = {"q1", "q2", "q3", "q4", "grid_pickup", "grid_drop", "count"}


@pytest.fixture(scope="module")
def runs():
    """Both runs at once: each spends most of its time waiting."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    argv = [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
            "--workload", "taxi.flight", "--seed", "2900000077",
            "--seconds", "20", "--trace", "0", "--rehearse", "--shards", "2"]
    procs = {name: subprocess.Popen(argv + extra, cwd=REPO, env=env,
                                    stdout=subprocess.PIPE,
                                    stderr=subprocess.PIPE, text=True)
             for name, extra in (("reference", []), ("control", ["--control"]))}
    out = {}
    for name, p in procs.items():
        stdout, stderr = p.communicate(timeout=1200)
        assert p.returncode == 0, stderr[-3000:]
        out[name] = json.loads(stdout.strip().splitlines()[-1])
    return out


def test_taxi_flight_rehearsal_is_correct(runs):
    res = runs["reference"]
    # a window long enough for the 30 answers the comparison needs, on a
    # machine that runs the rest of the suite beside it
    assert res["correct"] is True and res["failed"] == 0, res["compared"]
    assert res["device"]["platform"] == "cpu" and res["metrics"] == {}
    assert res["shards"] == 2 and res["workload"] == "taxi.flight"
    compared = res["compared"]
    assert compared["wrong_answers"] == {"value": 0, "limit": 0}
    assert compared["http_failures"] == {"value": 0, "limit": 0}
    assert compared["readback_count_gap"] == {"value": 0, "limit": 0}
    assert set(res["extra"]["checked_by_label"]) <= LABELS
    assert {"query_p50_ms", "query_p95_ms", "queries_per_s",
            "setup_s"} == set(res["rehearsal_metrics"])


def test_taxi_flight_control_is_not_correct(runs):
    res = runs["control"]
    assert res["correct"] is False
    wrong = res["extra"]["wrong_by_label"]
    checked = res["extra"]["checked_by_label"]
    # every label looked at reads wrong: the control breaks every call
    assert set(wrong) == set(checked) and checked
