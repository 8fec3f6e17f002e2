"""BENCHMARK.json against the contract's character rules, and every name
in it against the file the harness will look for."""

import json
import os
import re

import pytest

from conftest import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["benchmarks"]
    assert all(PATH.match(p) for p in bench["paths"])


def test_names_and_units(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for n in names:
            assert NAME.match(n), (group, n)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_cells_and_configs(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", f"{w['traffic']}.json"))
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and PATH.match(c["file"])
        assert 1 <= len(c["source"]) <= 200 and len(c["why"]) <= 200
        with open(os.path.join(REPO, c["file"])) as fh:
            doc = json.load(fh)
        for key in c["reduced"]:
            assert NAME.match(key) and key in doc and key in doc["reduced"]
        assert doc["source"] == c["source"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    assert len({c["file"] for c in configs.values()}) == len(configs)
    assert len({c["source"] for c in configs.values()}) == len(configs)


def test_per_layer_metrics_have_readers_and_targets(bench):
    end = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in end
        assert set(m.get("workloads", [])) <= cells
        assert os.path.isfile(os.path.join(
            BENCH, "layer_metrics", f"{m['name']}.py")), m["name"]
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    with open(os.path.join(REPO, "PERF.md")) as fh:
        perf = fh.read()
    for layer in {m["layer"] for m in bench["per_layer"]}:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"
