"""The whole harness, driven on the CPU at 2 shards: the last line's keys,
no metric without --rehearse and without a chip, the control and a broken
server both read not correct. Each run starts a server child on the CPU
backend ([mesh] platform = "cpu": it can never take a chip)."""

import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, REPO
import run as harness

CELLS = ["segmentation.adhoc"]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cli(*extra, cwd=REPO, script=None, timeout=900):
    script = script or os.path.join(BENCH, "run.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *extra], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def last_line(out) -> dict:
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_the_contracts_line(cell, trace):
    out = run_cli("--workload", cell, "--seed", "3000000001", "--seconds",
                  "2", "--trace", str(trace), "--rehearse", "--shards", "2")
    assert out.returncode == 0, out.stderr[-3000:]
    res = last_line(out)
    assert RESULT_KEYS <= set(res) and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["metrics"] == {} and "breakdown" not in res
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    group = "per_layer" if trace else "end_to_end"
    entry = {w["name"]: w for w in bench["workloads"]}[cell]
    allowed = {m["name"] for m in harness.metrics_of(bench, entry, group)
               if m["source"] != "device_trace"}
    got = set(res["rehearsal_metrics"])
    assert got <= allowed
    assert got >= ({"dispatches_per_query"} if trace else allowed)
    # the numbers compared stand beside their limits, last on stderr too
    tail = out.stderr.strip().splitlines()[-len(res["compared"]):]
    assert all("compared " in line for line in tail)
    assert res["compared"]["wrong_answers"] == {"value": 0, "limit": 0}


def test_no_chip_no_result():
    out = run_cli("--workload", CELLS[0], "--seed", "1", "--seconds", "2",
                  "--trace", "0")
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_cli("--workload", CELLS[0], "--seed", "1", "--seconds", "2",
                  "--trace", "0", "--rehearse", "--shards", "2",
                  cwd=tmp_path,
                  script=str(tmp_path / "benchmarks" / "run.py"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def args_for(cell, **kw):
    base = dict(workload=cell, seed=77, seconds=2.0, trace=0, rehearse=True,
                shards=2, control=False, logs="")
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_not_correct(cell, capfd):
    """The reference in the server's place with exactness broken (counts
    from every other shard, doubled)."""
    assert harness.run_cell(args_for(cell, control=True)) == 0
    res = json.loads(capfd.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False
    assert res["compared"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_it_is_produced_reads_not_correct(cell, capfd):
    """Skips the look for a chip (the rehearsal) and drives the rest of a
    run over a server whose Count is off by one on some answers."""
    faulty = os.path.join(os.path.dirname(__file__), "faulty_server.py")
    rc = harness.run_cell(
        args_for(cell), make_server_argv=lambda cfg: [
            sys.executable, faulty, cfg])
    assert rc == 0
    res = json.loads(capfd.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False
    assert res["failed"] == res["compared"]["wrong_answers"]["value"] > 0
