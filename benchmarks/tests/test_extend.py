"""A later PR adds a configuration, a traffic mix, a per-layer metric, a
data kind, a request generator and calls other than Count as new files and
entries, and edits no file that is there: done here in a temporary copy,
and the new cells run through the real server (on the CPU, at 2 shards)."""

import filecmp
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, REPO

TINY = {
    "name": "tiny", "source": "a test", "index": "tiny", "shards": 2,
    "fields": [
        {"name": "tag", "options": {"type": "set", "cacheType": "ranked",
                                    "cacheSize": 1000},
         "rows": 12, "first_id": 3, "set_bits_per_shard": 60000,
         "row_exponent": 1.3, "row_ratio": 0.05,
         "column_exponent": 1.01, "column_ratio": 0.5}],
    "reduced": {}, "assumed": {}}
PAIRS = {
    "clients": 4, "field": "tag",
    "tree": {"max_depth": 3, "max_args": 3,
             "ops": ["Difference", "Intersect", "Union"]},
    "templates": 20, "template_seed": 4, "warmup_requests": 8,
    "check_sample": 50, "check_min": 5}
METRIC = '''"""Requests of the window, as the harness counted them."""


def read(ctx):
    return float(ctx["requests"])
'''

# a deployment of records: two categorical fields, and one of a kind that
# no file of the benchmark knows
SET = {"type": "set", "cacheType": "ranked", "cacheSize": 1000}
RIDES = {
    "name": "rides", "source": "another test", "index": "rides", "shards": 2,
    "fields": [
        {"name": "cab", "kind": "categorical", "options": SET, "rows": 3,
         "first_id": 1, "weights": [6, 3, 1]},
        {"name": "pc", "kind": "categorical", "options": SET, "rows": 6,
         "value_exponent": 1.5, "value_ratio": 0.02, "present": 0.9},
        {"name": "lane", "kind": "stripes", "options": SET, "rows": 7}],
    "reduced": {}, "assumed": {}}
STRIPES = '''"""A data kind of the test's own: row r holds the columns c with
c % rows == r."""

import numpy as np

from lib.datagen import SHARD_WIDTH, Row


def make_field(seed, fi, spec, n_shards, pool):
    cols = np.arange(n_shards * SHARD_WIDTH, dtype=np.uint32)
    return {r + spec.get("first_id", 0): Row(n_shards,
                                             cols[r::spec["rows"]].copy())
            for r in range(spec["rows"])}
'''
PC = {"row": {"field": "pc", "draw": "uniform"}}
FLIGHT = {
    "generator": "flight", "clients": 4, "warmup_requests": 6,
    "check_sample": 100000, "check_min": 8,
    "queries": [
        {"call": "TopN", "label": "top", "field": "cab", "n": 2},
        {"call": "TopN", "label": "top_under", "field": "lane", "n": 3,
         "filter": {"intersect": [PC, {"row": {"field": "cab",
                                               "draw": "by_size"}}]}},
        {"call": "GroupBy", "label": "group", "fields": ["pc", "cab"]},
        {"call": "GroupBy", "label": "group_under",
         "fields": ["cab", "lane", "pc"],
         "filter": {"row": {"field": "lane", "draw": "uniform"}}},
        {"call": "Count", "label": "count", "weight": 2, "tree": {
            "difference": [{"row": {"field": "cab", "id": 1}}, PC]}}]}
SWEEP = '''"""A generator of the test's own: Count of each row of one field in
turn, from where the seed says."""

import itertools
import threading

from lib import query


class Traffic:
    label_key = "by_turn"

    def __init__(self, mix, data, seed):
        ids = data.row_ids(mix["field"])
        self.mix, self._lock = mix, threading.Lock()
        self._turn = itertools.cycle(ids[seed % len(ids):]
                                     + ids[:seed % len(ids)])

    def _request(self):
        ast = ("count", ("row", self.mix["field"], next(self._turn)))
        return {"pql": query.to_pql(ast), "ast": ast, "label": "turn"}

    def warmup(self):
        return [self._request() for _ in range(self.mix["warmup_requests"])]

    def take(self):
        with self._lock:
            return self._request()
'''
LANES = {"generator": "sweep", "clients": 2, "field": "lane",
         "warmup_requests": 3, "check_sample": 40, "check_min": 5}


def same_tree(a: str, b: str, extra: set) -> None:
    """Every file of `a` is in `b`, byte for byte; `b` has `extra` more."""
    cmp = filecmp.dircmp(a, b, ignore=["__pycache__"])
    stack, added = [(cmp, "")], set()
    while stack:
        c, at = stack.pop()
        assert not c.left_only and not c.diff_files and not c.funny_files, \
            (at, c.left_only, c.diff_files)
        _, differ, errors = filecmp.cmpfiles(c.left, c.right, c.common_files,
                                             shallow=False)
        assert not differ and not errors, (at, differ, errors)
        added |= {os.path.join(at, n) for n in c.right_only}
        stack += [(sub, os.path.join(at, n)) for n, sub in c.subdirs.items()]
    assert added == extra


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("later_pr")
    bench_dir = root / "benchmarks"
    shutil.copytree(BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "pilosa_tpu"), root / "pilosa_tpu")
    new = {
        "configs/tiny/config.json": json.dumps(TINY),
        "configs/rides/config.json": json.dumps(RIDES),
        "traffic/pairs.json": json.dumps(PAIRS),
        "traffic/flight4.json": json.dumps(FLIGHT),
        "traffic/lanes.json": json.dumps(LANES),
        "layer_metrics/window_requests.py": METRIC,
        "lib/data_kinds/stripes.py": STRIPES,
        "lib/generators/sweep.py": SWEEP,
    }
    for rel, text in new.items():
        os.makedirs(bench_dir / os.path.dirname(rel), exist_ok=True)
        assert not (bench_dir / rel).exists()
        (bench_dir / rel).write_text(text)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    before = json.loads(json.dumps(bench))
    for name in ("tiny", "rides"):
        bench["configs"].append({
            "name": name, "source": f"test {name}", "reduced": [],
            "why": "a test", "file": f"benchmarks/configs/{name}/config.json"})
    for config, mix in (("tiny", "pairs"), ("rides", "flight4"),
                        ("rides", "lanes")):
        bench["workloads"].append({
            "name": f"{config}.{mix}", "config": config, "traffic": mix,
            "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "window_requests", "unit": "queries", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "queries_per_s", "workloads": ["tiny.pairs"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # files and entries only: nothing that was there has changed
    same_tree(BENCH, str(bench_dir),
              {"configs/tiny", "configs/rides", "traffic/pairs.json",
               "traffic/flight4.json", "traffic/lanes.json",
               "layer_metrics/window_requests.py",
               "lib/data_kinds/stripes.py", "lib/generators/sweep.py"})
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert bench[group][:len(before[group])] == before[group]
    assert {k: bench[k] for k in ("command", "paths", "run_seconds")} == \
        {k: before[k] for k in ("command", "paths", "run_seconds")}
    return root


def run(root, cell, *extra):
    out = subprocess.run(
        [sys.executable, str(root / "benchmarks/run.py"), "--workload", cell,
         "--seed", "2900000009", "--seconds", "2", "--rehearse", *extra],
        cwd=root, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stderr


def test_a_new_cell_is_files_and_entries_only(copy):
    res, _ = run(copy, "tiny.pairs", "--trace", "1")
    assert res["correct"] is True
    assert res["rehearsal_metrics"]["window_requests"]["value"] == \
        res["attempted"] > 0
    assert set(res["compared"]) >= {"wrong_answers", "http_failures"}


def test_a_new_kind_and_topn_and_groupby_are_files_only(copy):
    """A `flight` mix over categorical fields and a field of a kind the
    copy added: TopN, TopN under a tree, GroupBy, GroupBy under a filter
    and Count, all checked against the reference, all equal."""
    res, err = run(copy, "rides.flight4", "--trace", "0")
    assert res["correct"] is True, err[-3000:]
    assert res["failed"] == 0 and res["attempted"] >= 6
    assert list(res["compared"]) == ["wrong_answers", "http_failures",
                                     "readback_count_gap", "answers_checked"]
    # every answer of the window was looked at, each call among them
    assert res["compared"]["answers_checked"]["value"] == res["attempted"]
    labels = {"top", "top_under", "group", "group_under", "count"}
    assert set(res["extra"]["checked_by_label"]) == labels
    assert set(res["extra"]["by_query"]) == labels
    assert res["extra"]["by_query"]["count"]["n"] > 0


def test_the_control_breaks_topn_and_groupby_too(copy):
    res, err = run(copy, "rides.flight4", "--trace", "0", "--control")
    assert res["correct"] is False
    wrong = res["compared"]["wrong_answers"]["value"]
    assert wrong > 0.8 * res["attempted"], err[-2000:]
    assert set(res["extra"]["wrong_by_label"]) == {
        "top", "top_under", "group", "group_under", "count"}


def test_a_new_generator_is_a_file_only(copy):
    res, err = run(copy, "rides.lanes", "--trace", "0")
    assert res["correct"] is True, err[-3000:]
    assert set(res["extra"]["by_turn"]) == {"turn"}
    assert res["compared"]["answers_checked"]["value"] >= 5
