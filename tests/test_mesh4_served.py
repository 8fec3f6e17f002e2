"""One node, four chips, served (benchmarks cell `segmentation-mesh4.adhoc`
at a test's size): a real `Server` on a mesh of four of the virtual CPU
devices, the configuration's own field at 4 shards (one a device) and at 6
(two pad shards), the cell's own request trees from 8 client threads, every
answer held against the benchmark's plain reference.

  * every answer equals the reference's, every request inside its own
    30 s (a hang fails a test, it does not stall the suite);
  * the rule for collectives (parallel/mesh.py on_collective_thread):
    after that load /debug/vars `mesh` has counted launches that hold a
    collective, all of them from one thread;
  * the share test: per-shard partials of Count programs over dense,
    sparse and mixed leaves, `Difference` and `Not` among them, add up
    chip by chip to the uncut numpy count, and a pad shard's partial is 0.
"""

import base64
import http.client
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import byfile, datagen, query, reference, roaring_wire  # noqa: E402

CLIENTS = 8
REQUESTS = 160
REQUEST_LIMIT_S = 30.0
EXISTS_FIELD = "seen"   # loaded over /import, which tracks existence


def call(port: int, method: str, path: str, body: bytes = b"{}",
         timeout: float = REQUEST_LIMIT_S):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"null")
    finally:
        conn.close()


def ok(port: int, method: str, path: str, doc=None):
    status, out = call(port, method, path,
                       json.dumps({} if doc is None else doc).encode())
    assert status == 200, (path, status, out)
    return out


both_sizes = pytest.mark.parametrize(
    "served", [4, 6], indirect=True, ids=lambda n: f"{n}-shards")


@pytest.fixture(scope="module")
def served(request, tmp_path_factory):
    import jax

    from pilosa_tpu.parallel.mesh import make_mesh
    from pilosa_tpu.server import Server
    from pilosa_tpu.utils import tracing

    n_shards = request.param
    with open(os.path.join(BENCH, "configs", "segmentation-mesh4",
                           "config.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH, "traffic", "adhoc.json")) as fh:
        mix = json.load(fh)
    data = datagen.make(config, seed=33, shards=n_shards)
    index, field = config["index"], config["fields"][0]["name"]

    # other tests of this process launch collective programs from their
    # own threads, straight into the kernels: count from here
    tracing.mesh_launches.reset()
    server = Server(str(tmp_path_factory.mktemp(f"mesh4-{n_shards}")),
                    port=0, mesh=make_mesh(jax.devices()[:4])).open()
    port = server.http.port
    try:
        ok(port, "POST", f"/index/{index}")
        ok(port, "POST", f"/index/{index}/field/{field}",
           {"options": data.options[field]})
        ok(port, "POST", f"/index/{index}/field/{EXISTS_FIELD}")
        rows = data.fields[field]
        for shard in range(n_shards):
            body = roaring_wire.fragment_payload(
                [(r, rows[r].shard_piece(shard)) for r in sorted(rows)])
            ok(port, "POST",
               f"/index/{index}/field/{field}/import-roaring/{shard}",
               {"views": {"standard": base64.b64encode(body).decode()}})
        exists = np.unique(np.random.default_rng(33).integers(
            0, n_shards * datagen.SHARD_WIDTH, size=3000)).astype(np.uint32)
        ok(port, "POST", f"/index/{index}/field/{EXISTS_FIELD}/import",
           {"rowIDs": [1] * exists.size, "columnIDs": exists.tolist()})

        gen = byfile.load("lib/generators", mix.get("generator")).Traffic(
            mix, data, 33)
        todo = [gen.take() for _ in range(REQUESTS)]
        got: list = [None] * REQUESTS
        before = ok(port, "GET", "/debug/vars")["mesh"]

        def client(k: int) -> None:
            for i in range(k, REQUESTS, CLIENTS):
                try:
                    got[i] = call(port, "POST", f"/index/{index}/query",
                                  todo[i]["pql"].encode())
                except Exception as e:  # noqa: BLE001 - a timeout is a
                    got[i] = (0, f"{type(e).__name__}: {e}")  # finding

        threads = [threading.Thread(target=client, args=(k,), daemon=True)
                   for k in range(CLIENTS)]
        deadline = time.monotonic() + REQUEST_LIMIT_S * REQUESTS / CLIENTS
        for t in threads:
            t.start()
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
        after = ok(port, "GET", "/debug/vars")["mesh"]
        yield {"server": server, "data": data, "index": index,
               "field": field, "todo": todo, "got": got, "exists": exists,
               "n_shards": n_shards, "before": before, "after": after}
    finally:
        server.close()


@both_sizes
def test_every_answer_is_the_references(served):
    ref = reference.Reference(served["data"])
    assert all(g is not None for g in served["got"]), "a client never ended"
    for req, (status, doc) in zip(served["todo"], served["got"]):
        assert status == 200, (req["pql"], status, doc)
        want = query.answer(ref, req["ast"])
        assert query.same(req["ast"], doc["results"], want), (
            req["pql"], doc["results"], want)


@both_sizes
def test_collectives_are_launched_by_one_thread(served):
    before, after = served["before"], served["after"]
    assert after["devices"] == 4 and after["shardSlots"] == 4
    assert after["collectiveLaunches"] > before["collectiveLaunches"]
    assert after["localLaunches"] > before["localLaunches"]
    assert after["collectiveThreads"] == 1


@pytest.mark.parametrize("served", [6], indirect=True, ids=["6-shards"])
def test_shares_add_up_and_pad_shards_count_nothing(served):
    """Chip by chip: the per-shard partials of a Count program, as the
    executor compiles and launches it, summed over each device's block,
    add up to the count numpy takes of the uncut rows (6 shards on 4
    devices: two pad shards)."""
    import jax

    from pilosa_tpu.ops import bitvector as bv
    from pilosa_tpu.pql import parse_string_cached

    server, data = served["server"], served["data"]
    field, n_shards = served["field"], served["n_shards"]
    ex = server.executor
    index = server.holder.index(served["index"])
    shards = ex._query_shards(index, None)
    assert len(shards) == n_shards
    rows = data.fields[field]
    by_size = sorted(rows, key=lambda r: rows[r].count())
    s0, s1 = by_size[0], by_size[1]         # below 4,096 bits a shard
    d0, d1, d2 = by_size[-1], by_size[-2], by_size[-3]
    ref = reference.Reference(data)
    exists = datagen.pack_columns(served["exists"], n_shards)

    def row(r):
        return f"Row({field}={r})"

    def words(r):
        return ref.row(field, r)

    cases = [
        (f"Intersect({row(d0)}, {row(d1)})", words(d0) & words(d1)),
        (f"Difference({row(d0)}, {row(d1)}, {row(d2)})",
         words(d0) & ~words(d1) & ~words(d2)),
        (f"Union({row(d0)}, {row(d1)}, {row(d2)})",
         words(d0) | words(d1) | words(d2)),
        (f"Intersect({row(s0)}, {row(s1)})", words(s0) & words(s1)),
        (f"Difference({row(s0)}, {row(s1)})", words(s0) & ~words(s1)),
        (f"Union({row(s0)}, {row(s1)})", words(s0) | words(s1)),
        (f"Difference({row(d0)}, {row(s0)})", words(d0) & ~words(s0)),
        (f"Intersect({row(s0)}, Union({row(d0)}, {row(s1)}))",
         words(s0) & (words(d0) | words(s1))),
        (f"Not({row(d0)})", exists & ~words(d0)),
        (f"Not({row(s0)})", exists & ~words(s0)),
        (f"Difference({row(d1)}, Not({row(s0)}))",
         words(d1) & ~(exists & ~words(s0))),
    ]
    seen_kinds = set()
    for pql, want_words in cases:
        want = reference.popcount(want_words)
        tree = parse_string_cached(f"Count({pql})").calls[0].children[0]
        program, leaves, kinds = ex._compile(index, tree, shards)
        seen_kinds.update(kinds)
        if "sparse" in kinds or "run" in kinds:
            part = bv.hybrid_count_dev(program, leaves, kinds)
        else:
            part = bv.popcount(ex.runner.row_leaves_dev(leaves, program))
        assert part.shape == (8,), pql   # 6 shards padded to 4 x 2
        chips = [int(np.asarray(s.data).sum())
                 for s in part.addressable_shards]
        assert len(chips) == 4 and len({s.device for s in
                                        part.addressable_shards}) == 4
        assert sum(chips) == want, (pql, chips, want)
        assert np.asarray(part)[n_shards:].tolist() == [0, 0], pql
        # and the served Count is the same number
        status, doc = call(server.http.port, "POST",
                           f"/index/{served['index']}/query",
                           f"Count({pql})".encode())
        assert status == 200 and doc["results"][0] == want, (pql, doc)
    assert {"dense", "sparse"} <= seen_kinds
    assert jax.device_count() >= 4


def test_collective_thread_keeps_the_callers_span_and_raises_to_it():
    """on_collective_thread: the launch is made on the one thread, in the
    caller's context (its open span counts it, with the devices it went
    to), a nested call runs in place, an exception comes back."""
    from pilosa_tpu.parallel import mesh as pmesh
    from pilosa_tpu.utils import tracing
    from pilosa_tpu.utils.telemetry import record_dispatch

    def launch():
        record_dispatch("ici_program", "test", devices=4, collective=True)
        return threading.current_thread().name

    with tracing.span("dispatch") as sp:
        first = pmesh.on_collective_thread(launch)
        nested = pmesh.on_collective_thread(
            lambda: pmesh.on_collective_thread(launch))
    assert first.startswith("mesh-collective") and nested == first
    assert sp.tags["dispatches"] == 2 and sp.tags["devices"] == 4
    with pytest.raises(ZeroDivisionError):
        pmesh.on_collective_thread(lambda: 1 // 0)


def test_one_device_counts_no_mesh_launch():
    """Without a mesh the block reads one device, a launch goes to one
    and moves none of the three counters; `collective` is the plain
    call."""
    import jax.numpy as jnp

    from pilosa_tpu.ops import bitvector as bv
    from pilosa_tpu.parallel.mesh import DeviceRunner
    from pilosa_tpu.utils import tracing

    runner = DeviceRunner()
    tracing.mesh_launches.watching = True  # as after any mesh in the process
    before = runner.mesh_snapshot()
    assert before["devices"] == 1 and before["shardSlots"] == 1
    rows = jnp.ones((2, 64), dtype=jnp.uint32)
    bv.intersect_chain_count_total((rows, rows, rows))  # traced once
    with tracing.span("dispatch") as sp:
        total = runner.collective(bv.intersect_chain_count_total,
                                  (rows, rows, rows))
    assert int(total) == 2 * 64  # one bit a word
    assert sp.tags["dispatches"] == 1 and sp.tags["devices"] == 1
    assert runner.mesh_snapshot() == before
