"""chip_smoke.py's parts that need no chip: the compile-cache placement
rule its children follow, and its plain reference on a 2-shard toy."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, **env) -> str:
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=full,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


_CACHE_PROBE = """
import json, jax
set_in_code = []
real = jax.config.update
def spy(name, value):
    set_in_code.append(name)
    real(name, value)
jax.config.update = spy
from pilosa_tpu.parallel import mesh
d = mesh.configure_compile_cache()
print(json.dumps({"dir": d, "set_in_code": set_in_code,
                  "effective": jax.config.jax_compilation_cache_dir,
                  "floor": jax.config.jax_persistent_cache_min_compile_time_secs}))
"""


def test_cache_dir_from_environment_sets_nothing_in_code(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself; the program
    sets no cache directory (only the keep-everything floor)."""
    out = json.loads(_run(_CACHE_PROBE,
                          JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert out["dir"] == str(tmp_path) == out["effective"]
    assert "jax_compilation_cache_dir" not in out["set_in_code"]
    assert out["floor"] == 0.0


def test_cache_dir_default_is_one_fixed_in_checkout_path():
    """Unset: two processes agree on the same path inside the checkout —
    no tempfile name, pid or timestamp — and chip_smoke counts entries in
    that same directory."""
    import chip_smoke

    a, b = (json.loads(_run(_CACHE_PROBE)) for _ in range(2))
    want = os.path.join(REPO, ".jax_cache")
    assert a["dir"] == b["dir"] == a["effective"] == want
    assert a["floor"] == 0.0
    saved = os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    try:
        assert chip_smoke.cache_dir() == want
    finally:
        if saved is not None:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = saved


_TOY = """
import sys
import numpy as np
import chip_smoke as cs

z = cs.Sizes(shards=2, dense_rows=3, sparse_rows=2, run_rows=2, g_rows=2,
             h_rows=2, tag_rows=40, tag_head=3000, bsi_shards=1,
             bsi_max=255, topn=10)
d = cs.make_data(z, seed=5)
sets = {r: set(c.tolist()) for r, c in d.f.items()}
a, s, r = d.dense_ids[0], d.sparse_ids[0], d.run_ids[0]
for x, y in ((a, s), (s, r), (r, a), (a, d.dense_ids[1]), (r, d.run_ids[1])):
    X, Y = d.f[x], d.f[y]
    assert cs.ref_intersect(X, Y).tolist() == sorted(sets[x] & sets[y])
    assert cs.ref_union(X, Y).tolist() == sorted(sets[x] | sets[y])
    assert cs.ref_difference(X, Y).tolist() == sorted(sets[x] - sets[y])
    assert cs.ref_xor(X, Y).tolist() == sorted(sets[x] ^ sets[y])
# the three per-shard cardinality bands the device representations key on
per_shard = lambda row: np.bincount(d.f[row] >> 20, minlength=z.shards)
assert per_shard(a).min() > 4096 and per_shard(s).max() < 4096
assert len(sets[r]) == z.shards * 2 * z.run_len
assert sets[r] & sets[d.run_ids[1]] and sets[r] & sets[a]
# existence = what goes through /import: values, tags, sparse rows
assert set(d.exists.tolist()) == (
    set(range(z.bsi_shards << 20)) | set(d.tag_cols.tolist())
    | set().union(*(sets[q] for q in d.sparse_ids)))
# TopN: count desc, id asc, zero rows dropped, filter honoured
tags = {}
for row, col in zip(d.tag_rows.tolist(), d.tag_cols.tolist()):
    tags.setdefault(row, set()).add(col)
h0 = set(d.h[0].tolist())
for within, keep in ((None, lambda c: True), (d.h[0], lambda c: c in h0)):
    counts = cs.ref_row_counts(d.tag_rows, d.tag_cols, z.tag_rows, within)
    want = sorted(((sum(map(keep, cols)), row)
                   for row, cols in tags.items()),
                  key=lambda t: (-t[0], t[1]))
    want = [{"id": row, "count": n} for n, row in want if n][:z.topn]
    assert cs.ref_topn(np.arange(z.tag_rows), counts, z.topn) == want
# ValCount and GroupBy
v = d.values[:1000]
assert cs.ref_valcount(v, "sum") == {"value": int(sum(v.tolist())),
                                     "count": 1000}
assert cs.ref_valcount(v, "min") == {"value": min(v.tolist()),
                                     "count": v.tolist().count(min(v))}
assert cs.ref_valcount(v[:0], "max") == {"value": 0, "count": 0}
gb = cs.ref_groupby(d.g, d.h, "g", "h")
assert [(e["group"][0]["rowID"], e["group"][1]["rowID"]) for e in gb] \\
    == sorted((x, y) for x in d.g for y in d.h
              if set(d.g[x].tolist()) & set(d.h[y].tolist()))
assert all(e["count"] == len(set(d.g[e["group"][0]["rowID"]].tolist())
                             & set(d.h[e["group"][1]["rowID"]].tolist()))
           for e in gb)
# the roaring payload carries exactly the shard's bits at row*2^20 + col
from pilosa_tpu.storage.roaring import Bitmap
got = Bitmap.from_bytes(cs._roaring({a: d.f[a], r: d.f[r]}, 1)).positions()
want = sorted([(a << 20) + c - (1 << 20) for c in sets[a] if c >> 20 == 1]
              + [(r << 20) + c - (1 << 20) for c in sets[r] if c >> 20 == 1])
assert got.tolist() == want
# a corrupted expectation is a recorded failure, never a pass
ck = cs.Checker(http=None)
assert ck.record("fam", "good", 7, 7) and not ck.record("fam", "bad", 7, 8)
assert ck.families == {"fam": False} and len(ck.failures) == 1
assert "jax" not in sys.modules, "the chip_smoke parent must stay off jax"
print("toy ok")
"""


def test_reference_algebra_on_a_toy_without_jax():
    assert _run(_TOY) == "toy ok"


def test_no_result_without_an_accelerator(tmp_path):
    """JAX_PLATFORMS=cpu (this sandbox): non-zero exit before any data is
    loaded, the platform found is named, and no result line is printed."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "platform ['cpu']" in proc.stderr
    assert "JAX_PLATFORMS='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout and "loaded" not in proc.stdout


def _is_contract_line(obj) -> bool:
    """The driver's rule for the last stdout line: exactly `ok` (a bool)
    and `device`, the device exactly platform and kind (text) and count (a
    whole number). One more key and the PR is refused."""
    return (isinstance(obj, dict) and set(obj) == {"ok", "device"}
            and isinstance(obj["ok"], bool)
            and isinstance(obj["device"], dict)
            and set(obj["device"]) == {"platform", "kind", "count"}
            and isinstance(obj["device"]["platform"], str)
            and isinstance(obj["device"]["kind"], str)
            and type(obj["device"]["count"]) is int)


def test_result_line_has_exactly_the_contract_keys():
    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    for ok in (True, False):
        line = chip_smoke.result_line(ok, device)
        assert "\n" not in line
        assert json.loads(line) == {"ok": ok, "device": device}
        assert _is_contract_line(json.loads(line))


@pytest.mark.slow
def test_rehearsal_passes_end_to_end():
    proc = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse-cpu"],
                          cwd=REPO, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] and _is_contract_line(last)
    assert last["device"]["platform"] == "cpu"
    assert lines[-2].startswith("summary ")
    summary = json.loads(lines[-2][len("summary "):])
    assert summary["ok"] and summary["rehearsal"]
    assert all(summary["phases"].values())
