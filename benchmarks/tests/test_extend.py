"""A later PR adds a configuration, a traffic mix and a per-layer metric
as new files and entries, and edits no file that is there: done here in a
temporary copy, and the new cell runs."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, REPO

CONFIG = {
    "name": "tiny", "source": "a test", "index": "tiny", "shards": 2,
    "fields": [
        {"name": "tag", "options": {"type": "set", "cacheType": "ranked",
                                    "cacheSize": 1000},
         "rows": 12, "first_id": 3, "set_bits_per_shard": 60000,
         "row_exponent": 1.3, "row_ratio": 0.05,
         "column_exponent": 1.01, "column_ratio": 0.5}],
    "reduced": {}, "assumed": {}}
MIX = {
    "clients": 4, "field": "tag",
    "tree": {"max_depth": 3, "max_args": 3,
             "ops": ["Difference", "Intersect", "Union"]},
    "templates": 20, "template_seed": 4, "warmup_requests": 8,
    "check_sample": 50, "check_min": 5}
METRIC = '''"""Requests of the window, as the harness counted them."""


def read(ctx):
    return float(ctx["requests"])
'''


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "pilosa_tpu"), tmp_path / "pilosa_tpu")
    os.makedirs(tmp_path / "benchmarks/configs/tiny")
    (tmp_path / "benchmarks/configs/tiny/config.json").write_text(
        json.dumps(CONFIG))
    (tmp_path / "benchmarks/traffic/pairs.json").write_text(json.dumps(MIX))
    (tmp_path / "benchmarks/layer_metrics/window_requests.py").write_text(
        METRIC)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({
        "name": "tiny", "source": "a test", "reduced": [], "why": "a test",
        "file": "benchmarks/configs/tiny/config.json"})
    bench["workloads"].append({"name": "tiny.pairs", "config": "tiny",
                               "traffic": "pairs", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({
        "name": "window_requests", "unit": "queries", "better": "higher",
        "source": "program_counter", "layer": "load generator",
        "moves": "queries_per_s", "workloads": ["tiny.pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    out = subprocess.run(
        [sys.executable, str(tmp_path / "benchmarks/run.py"), "--workload",
         "tiny.pairs", "--seed", "9", "--seconds", "1", "--trace", "1",
         "--rehearse"], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["rehearsal_metrics"]["window_requests"]["value"] == \
        res["attempted"] > 0
    assert set(res["compared"]) >= {"wrong_answers", "http_failures"}
