"""Device runner: batched bitmap-program evaluation over a shard-sharded mesh.

The unit of device work is a *shard slab*: leaves[L, S, W] — L bitmap-leaf
operands x S shards x W uint32 lanes. A query's bitmap call tree is compiled
to a small postfix-free nested-tuple program (static, hashable → one XLA
compilation per query *shape*, reused across queries); evaluation is one
fused bitwise program over the slab, counts are fused popcount reductions.

Distribution: leaves are placed with NamedSharding P(None, "shard", None) so
S partitions across the mesh's shard axis; GSPMD partitions the elementwise
program with zero communication, and inserts the ICI all-reduce only for
`*_total` results — the analog of the reference's per-node mapReduce with a
channel reduce (executor.go:2183-2321), with XLA collectives replacing HTTP.
"""

from __future__ import annotations

import concurrent.futures
import contextvars
import functools
import os
import re
import threading
from typing import Optional, Sequence

import jax
import jax.extend.backend
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pilosa_tpu.ops.bitvector import (
    SPARSE_SENTINEL,
    chunk_count_matrix,
    groupby_chunk_live,
    groupby_chunk_matrix,
    live_from_matrix,
    pairs_count,
    pairs_count_local,
    popcount,
)
from pilosa_tpu.analysis import lockwitness
from pilosa_tpu.utils import tracing
from pilosa_tpu.utils.telemetry import counted_jit, record_dispatch

SHARD_AXIS = "shard"
REPLICA_AXIS = "replica"


def make_mesh(devices: Optional[Sequence] = None, axis: str = SHARD_AXIS,
              replicas: int = 1) -> Mesh:
    """Mesh over all (or given) devices; the shard axis is the analog of
    the reference's node ring (cluster.go:857).

    replicas > 1 builds a 2-D ("replica", "shard") mesh: slab leaves are
    sharded over "shard" and replicated over "replica" (SURVEY §2.9
    strategy 3 — the ReplicaN copies of the reference mapped onto mesh
    slices), and the query *stream* data-parallelizes over "replica"
    (pair_stream_counts): each replica serves its slice of the queries
    against a full copy of the data."""
    devices = list(devices) if devices is not None else jax.devices()
    if replicas > 1:
        if len(devices) % replicas:
            raise ValueError(
                f"{len(devices)} devices not divisible by {replicas} replicas")
        return Mesh(np.array(devices).reshape(replicas, -1),
                    (REPLICA_AXIS, axis))
    return Mesh(np.array(devices), (axis,))


def group_by_slice(devices) -> list[list]:
    """Devices bucketed by TPU slice (ICI domain), slice ids ascending.
    Single-slice and CPU devices (no slice_index) land in one bucket."""
    buckets: dict = {}
    for d in devices:
        buckets.setdefault(getattr(d, "slice_index", 0), []).append(d)
    return [buckets[k] for k in sorted(buckets)]


def make_multislice_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """Multi-slice (DCN) mesh: one replica per TPU slice, shards within.

    The scaling-book hybrid-mesh recipe applied to this workload: the
    replica axis crosses slice boundaries and therefore rides DCN — which
    is fine, because with `pair_stream_counts` ONLY the query-stream
    scatter and the per-query count gather cross replicas (bytes per
    query, not data); every data-plane collective (the psum over "shard")
    stays inside a slice on ICI. Data is fully replicated per slice, so
    slices serve independent query throughput — the multi-slice form of
    the reference's ReplicaN node groups (SURVEY §2.9 strategy 3).

    Uses mesh_utils.create_hybrid_device_mesh when the backend exposes
    slice topology; falls back to slice-bucketed reshape (and to a plain
    1-D shard mesh on single-slice/CPU backends)."""
    devices = list(devices) if devices is not None else jax.devices()
    slices = group_by_slice(devices)
    if len(slices) <= 1:
        return make_mesh(devices)
    per = min(len(s) for s in slices)
    dropped = len(devices) - len(slices) * per
    if dropped:
        import warnings

        warnings.warn(
            f"multislice mesh: uneven slices truncated to {per} devices "
            f"each; {dropped} of {len(devices)} devices left idle")
    if not all(hasattr(d, "slice_index") for d in devices):
        # CPU/virtual devices carry no slice topology, so the hybrid-mesh
        # builder is GUARANTEED to fail ("... does not have attribute
        # slice_index") — multiple buckets here only ever mean a
        # substituted bucketer (dryrun/tests). Skip the doomed attempt
        # instead of warning on every mesh build; the warning below stays
        # reserved for real hardware whose topology query fails.
        arr = np.array([s[:per] for s in slices])
    else:
        try:
            from jax.experimental import mesh_utils

            arr = mesh_utils.create_hybrid_device_mesh(
                mesh_shape=(1, per), dcn_mesh_shape=(len(slices), 1),
                devices=[d for s in slices for d in s[:per]])
        except Exception as e:  # noqa: BLE001 — on real multi-slice
            # hardware a failure here degrades ICI ordering, so say so
            import warnings

            warnings.warn(
                "multislice mesh: create_hybrid_device_mesh failed "
                f"({type(e).__name__}: {e}); using slice-bucketed device "
                "order (collectives may not follow the physical ICI "
                "topology)")
            arr = np.array([s[:per] for s in slices])
    return Mesh(np.asarray(arr).reshape(len(slices), per),
                (REPLICA_AXIS, SHARD_AXIS))


def force_platform(platform: str, host_devices: int = 0,
                   reset: bool = False) -> None:
    """Force the jax platform BEFORE backend init — the one shared recipe
    (used by tests/conftest.py, __graft_entry__, and mesh_from_config).

    JAX honours the JAX_PLATFORMS env var (tier-1 runs with
    JAX_PLATFORMS=cpu exported); this sets both the env var, so child
    processes inherit the choice, and jax.config, so a `[mesh] platform`
    from a TOML wins over whatever the environment exported.
    host_devices > 0 additionally requests N virtual CPU host devices via
    XLA_FLAGS. reset=True drops already-initialized backends so the new
    flags take effect mid-process.
    """
    if host_devices > 0:
        flags = os.environ.get("XLA_FLAGS", "")
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       flags)
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={host_devices}"
        ).strip()
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
        jax.config.update("jax_platforms", platform)
    if reset:
        jax.extend.backend.clear_backends()


# the persistent compile cache's in-checkout home when the environment
# names none: a FIXED path (never a tempfile name, pid or timestamp), so
# every process started from this checkout — server, bench worker, a
# second chip_smoke run — finds what the first one compiled
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache BEFORE backend init and
    return the directory in use. If JAX_COMPILATION_CACHE_DIR is set the
    operator placed it: set no directory in code (JAX reads the variable
    itself). Otherwise use COMPILE_CACHE_DIR. Either way keep every
    program, however quick its compile: a cold start compiles dozens of
    sub-second bitmap programs, which the default 1 s floor would drop.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def mesh_from_config(devices: str = "auto", platform: str = "",
                     host_devices: int = 0, replicas: int = 1) -> Optional[Mesh]:
    """Build the production server's mesh from [mesh] config (cli/config.py).

    Must run before any other backend use in the process: platform forcing
    and the virtual-host-device flag only take effect at backend init.
    Returns None (single-device DeviceRunner) when the resolved device list
    has fewer than 2 entries — a 1-device mesh adds tracing overhead for
    nothing.
    """
    if host_devices > 0 and not platform:
        platform = "cpu"
    force_platform(platform, host_devices)
    configure_compile_cache()

    if devices == "none":
        return None
    avail = jax.devices()
    if devices != "auto":
        try:
            n = int(devices)
        except ValueError:
            raise ValueError(
                f"[mesh] devices must be 'auto', 'none', or an integer "
                f"count, got {devices!r}")
        if n <= 0 or n > len(avail):
            raise ValueError(
                f"[mesh] devices = {n} out of range: {len(avail)} available")
        avail = avail[:n]
    if len(avail) < 2:
        return None
    if replicas == 0:  # auto: one replica per TPU slice (DCN multi-slice)
        return make_multislice_mesh(avail)
    return make_mesh(avail, replicas=max(replicas, 1))


# -- one thread for every program that holds a collective ---------------------
# A program laid over several devices is enqueued on each of them in turn.
# Two threads that launch two such programs at once can reach the devices
# in different orders; if both programs hold a collective (a psum, or the
# all-reduce GSPMD puts where a jitted function sums over the shard axis
# of sharded operands), each then waits on one device for a partner that
# is queued behind the other, for ever. Programs without a collective do
# not wait for each other and may be launched from anywhere. So the rule,
# wherever a runner has a mesh: a program that holds a collective is
# launched on this thread and on no other. The launch is the enqueue alone
# (asynchronous); the caller fetches the result on its own thread.

_collective_pool = concurrent.futures.ThreadPoolExecutor(
    max_workers=1, thread_name_prefix="mesh-collective")
_on_collective_thread = threading.local()


def on_collective_thread(fn, *args, **kwargs):
    """fn(*args, **kwargs), made on the process's one collective thread in
    the caller's context (its open span counts the launch) and returned,
    or raised, to the caller."""
    if getattr(_on_collective_thread, "here", False):
        return fn(*args, **kwargs)

    def run():
        _on_collective_thread.here = True
        return fn(*args, **kwargs)

    return _collective_pool.submit(
        contextvars.copy_context().run, run).result()


# -- program evaluation ------------------------------------------------------
# program: nested tuples, e.g. ("and", ("leaf", 0), ("or", ("leaf", 1), ...)).
# Ops: leaf(i) | and | or | xor | andnot (binary: a &~ b) | not.
# "not" complements the full shard width; executor composes existence masks.


def _eval(leaves: jax.Array, program) -> jax.Array:
    op = program[0]
    if op == "leaf":
        return leaves[program[1]]
    if op == "not":
        return jnp.bitwise_not(_eval(leaves, program[1]))
    xs = [_eval(leaves, p) for p in program[1:]]
    acc = xs[0]
    for x in xs[1:]:
        if op == "and":
            acc = jnp.bitwise_and(acc, x)
        elif op == "or":
            acc = jnp.bitwise_or(acc, x)
        elif op == "xor":
            acc = jnp.bitwise_xor(acc, x)
        elif op == "andnot":
            acc = jnp.bitwise_and(acc, jnp.bitwise_not(x))
        else:
            raise ValueError(f"unknown op {op!r}")
    return acc


@counted_jit("program", static_argnames=("program",))
def eval_row(leaves: jax.Array, program) -> jax.Array:
    """[L, S, W] -> [S, W] dense result rows."""
    return _eval(leaves, program)


@counted_jit("program", cross_shard=True, static_argnames=("program",))
def eval_count_total(leaves: jax.Array, program) -> jax.Array:
    """[L, S, W] -> scalar total count. Under a sharded input GSPMD lowers the
    sum to an ICI all-reduce — the Count() reduce (executor.go:1521,2209)."""
    return jnp.sum(popcount(_eval(leaves, program)))


# -- ICI-native serving program cache ----------------------------------------
# The general serving-mode forms of the per-query kernels: the pair-stream
# and GroupBy kernels above proved the shard_map + lax.psum shape (per-device
# partials over the local shard slice, ONE collective on the interconnect);
# these extend that exact shape to arbitrary bitmap programs so the executor
# can serve any co-resident shard group as a single sharded program instead
# of HTTP scatter-gather (executor._ici_route). Programs are static and
# hashable, so the cache holds one compiled callable per
# (kind, mesh, program, n_leaves) — the per-mesh discipline of
# _pair_stream_fn, with hit/miss counters surfaced at /debug/vars
# `iciServing.programCache` (a cold cache on a hot path is the recompile
# storm the telemetry exists to catch).

_ici_programs: dict = {}
_ici_lock = threading.Lock()
_ici_stats = {"hits": 0, "misses": 0}


def ici_program_cache_stats() -> dict:
    with _ici_lock:
        return {"hits": _ici_stats["hits"], "misses": _ici_stats["misses"],
                "programs": len(_ici_programs)}


def _ici_cached(key, build):
    with _ici_lock:
        fn = _ici_programs.get(key)
        if fn is not None:
            _ici_stats["hits"] += 1
            return fn
    fn = build()  # trace/compile happens at first call, outside the lock
    with _ici_lock:
        _ici_stats["misses"] += 1
        return _ici_programs.setdefault(key, fn)


def _build_count_mesh(mesh: Mesh, program, n_leaves: int):
    spec = P(SHARD_AXIS, None)

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(tuple(spec for _ in range(n_leaves)),),
        out_specs=P(), check_vma=False)
    def run(leaves):
        # per-device partial over the local shard slice, one ICI
        # all-reduce — the explicit form of eval_count_total's GSPMD
        # lowering (executor.go:1521,2209's channel reduce)
        local = jnp.sum(popcount(_eval(leaves, program)))
        return jax.lax.psum(local, SHARD_AXIS)

    return run


def _build_row_mesh(mesh: Mesh, program, n_leaves: int):
    spec = P(SHARD_AXIS, None)

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(tuple(spec for _ in range(n_leaves)),),
        out_specs=spec, check_vma=False)
    def run(leaves):
        # purely elementwise: zero collectives, the result stays sharded
        # in HBM for further composition (BSI filters, TopN sources,
        # GroupBy filter folds — the "Row composition" serving form)
        return _eval(leaves, program)

    return run


def eval_count_mesh(mesh: Mesh, leaves: tuple, program) -> jax.Array:
    """[L x [S', W]] -> scalar total count as ONE sharded program with an
    explicit psum over the shard axis (ICI)."""
    fn = _ici_cached(("count", mesh, program, len(leaves)),
                     lambda: _build_count_mesh(mesh, program, len(leaves)))

    # the family stays a literal at every site: pilosa-lint reads it there
    def launch():
        record_dispatch("ici_program", mesh, "count", program, len(leaves),
                        devices=mesh.size, collective=True)
        return fn(tuple(leaves))

    return on_collective_thread(launch)


def eval_row_mesh(mesh: Mesh, leaves: tuple, program) -> jax.Array:
    """[L x [S', W]] -> [S', W] dense result, sharded across the slice
    (never per-device-replicated: each device holds only its shard
    slots' words, exactly like the resident leaves it was computed
    from)."""
    fn = _ici_cached(("row", mesh, program, len(leaves)),
                     lambda: _build_row_mesh(mesh, program, len(leaves)))
    record_dispatch("ici_program", mesh, "row", program, len(leaves),
                    devices=mesh.size)
    return fn(tuple(leaves))


@counted_jit("stream", cross_shard=True)
def count_pair_stream(rows: jax.Array, ii: jax.Array, jj: jax.Array,
                      carry: jax.Array) -> jax.Array:
    """Serve a stream of K Count(Intersect(Row(i), Row(j))) queries against a
    resident row slab in ONE dispatch: rows[R, S, W], ii/jj int32[K] row
    indices -> summed count folded into carry (uint32).

    This is the batched form of the executor's hottest query — each scan step
    is an independent query (dynamic row gather straight from HBM into the
    fused and+popcount reduce, no intermediates), the scan amortizes dispatch
    overhead over the batch the way the reference's goroutine fan-out
    amortizes scheduling (executor.go:2183,2283). The carry chains dispatches
    for benchmarking without touching the slab."""
    def body(c, ij):
        i, j = ij
        a = jax.lax.dynamic_index_in_dim(rows, i, axis=0, keepdims=False)
        b = jax.lax.dynamic_index_in_dim(rows, j, axis=0, keepdims=False)
        cnt = jnp.sum(popcount(jnp.bitwise_and(a, b)))
        return c + cnt.astype(jnp.uint32), None
    tot, _ = jax.lax.scan(body, carry, (ii, jj))
    return tot


def scatter_queries(mesh: Mesh, ii: np.ndarray, jj: np.ndarray):
    """Shared replica-scatter scaffolding for query streams: pads K to a
    multiple of the replica count with (0, 0) no-op queries (dropped after
    gather) and places ii/jj sharded over the replica axis (replicated on
    a 1-D shard mesh). Returns (ii_dev, jj_dev, real_k, rep_spec)."""
    n_rep = mesh.shape.get(REPLICA_AXIS, 1)
    rep_spec = P(REPLICA_AXIS) if REPLICA_AXIS in mesh.shape else P()
    k = ii.shape[0]
    pad = (-k) % n_rep
    if pad:
        ii = np.concatenate([ii, np.zeros(pad, ii.dtype)])
        jj = np.concatenate([jj, np.zeros(pad, jj.dtype)])
    ii_d = jax.device_put(ii.astype(np.int32), NamedSharding(mesh, rep_spec))
    jj_d = jax.device_put(jj.astype(np.int32), NamedSharding(mesh, rep_spec))
    return ii_d, jj_d, k, rep_spec


def pair_stream_counts(mesh: Mesh, rows: jax.Array, ii: np.ndarray,
                       jj: np.ndarray) -> np.ndarray:
    """Per-query counts for a stream of K Count(Intersect(Row i, Row j))
    queries on a replica×shard mesh — the throughput form of the serving
    path (SURVEY §2.9 strategy 3).

    SPMD layout: rows[R, S, W] sharded P(None, "shard", None) and
    *replicated* over "replica"; the query stream ii/jj[K] shards over
    "replica" so each replica slice scans only its K/replicas queries
    against its full data copy. Inside shard_map each step is the fused
    gather+and+popcount; the only collective is a psum over "shard" (ICI)
    for each query's global count. Returns host int64[K].
    """
    # on a 1-D ('shard',) mesh there is no replica axis: every device scans
    # the full stream (replicated), sharded only over the data
    ii_d, jj_d, k, rep_spec = scatter_queries(mesh, ii, jj)

    def launch():
        record_dispatch("stream_mesh", mesh, rows, ii_d, jj_d,
                        devices=mesh.size, collective=True)
        return _pair_stream_fn(mesh)(rows, ii_d, jj_d)

    out = np.asarray(on_collective_thread(launch)).astype(np.int64)
    return out[:k]


@functools.lru_cache(maxsize=None)
def _pair_stream_fn(mesh: Mesh):
    """Per-mesh cached shard_map program for pair_stream_counts: a closure
    rebuilt per call would miss jax.jit's cache (keyed on the function
    object) and silently recompile EVERY call — which would also make the
    telemetry dispatch counter report the site as cached while it
    recompiles (the exact failure the storm detector exists to catch)."""
    rep_spec = P(REPLICA_AXIS) if REPLICA_AXIS in mesh.shape else P()

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, SHARD_AXIS, None), rep_spec, rep_spec),
        out_specs=rep_spec,
        check_vma=False)
    def run(rows_blk, ii_blk, jj_blk):
        def body(_, ij):
            i, j = ij
            a = jax.lax.dynamic_index_in_dim(rows_blk, i, 0, keepdims=False)
            b = jax.lax.dynamic_index_in_dim(rows_blk, j, 0, keepdims=False)
            local = jnp.sum(popcount(jnp.bitwise_and(a, b)))
            return 0, jax.lax.psum(local, SHARD_AXIS)
        _, counts = jax.lax.scan(body, 0, (ii_blk, jj_blk))
        return counts

    return run


# -- GroupBy cross-count mesh form -------------------------------------------
# Per-device partial count matrices over the local shard slice, one psum
# over the shard axis — the [P, R, S] intermediate never crosses devices
# and the zero-prune runs on the replicated [P, R] result. The replica
# axis (if any) holds full data copies, so every replica computes the same
# matrix.


@functools.lru_cache(maxsize=None)
def _groupby_cmat_mesh_fn(mesh: Mesh, n_axes: int):
    slab_spec = P(None, SHARD_AXIS, None)

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(tuple(slab_spec for _ in range(n_axes)),
                  tuple(P() for _ in range(n_axes)), slab_spec, P()),
        out_specs=P(), check_vma=False)
    def run(axis_slabs, idx, axis, n_valid):
        # the shared chunk composition on the local shard slice (masked
        # padding rows are zero on every device, so masking commutes with
        # the psum), then one ICI all-reduce over the shard axis
        local = chunk_count_matrix(axis_slabs, idx, axis, n_valid)
        return jax.lax.psum(local, SHARD_AXIS)

    return run


def groupby_chunk_matrix_mesh(mesh: Mesh, axis_slabs: tuple, idx: tuple,
                              axis: jax.Array, n_valid) -> jax.Array:
    """Sharded groupby_chunk_matrix: per-device partial [P, R] counts, one
    ICI psum. A device array — no host sync."""
    axis_slabs, idx = tuple(axis_slabs), tuple(idx)

    def launch():
        record_dispatch("groupby_mesh", mesh, len(idx), axis_slabs, idx,
                        axis, devices=mesh.size, collective=True)
        return _groupby_cmat_mesh_fn(mesh, len(idx))(
            axis_slabs, idx, axis, n_valid)

    return on_collective_thread(launch)


def groupby_chunk_live_mesh(mesh: Mesh, axis_slabs: tuple, idx: tuple,
                            axis: jax.Array, n_valid, bound: int):
    """Sharded groupby_chunk_live: the mesh count matrix, pruned on
    device. Returns device arrays — no host sync."""
    return live_from_matrix(
        groupby_chunk_matrix_mesh(mesh, axis_slabs, idx, axis, n_valid),
        bound)


# -- TopN recount from sorted columns, mesh form ------------------------------


@functools.lru_cache(maxsize=None)
def _pairs_count_mesh_fn(mesh: Mesh, n_slots: int, ndim: int):
    """Per-mesh, per-size shard_map program of ops/bitvector.pairs_count:
    every device counts over its own shard slots (the entry, in either
    layout, is sharded on its second axis and the filter on its first),
    one psum on the interconnect, the count vector replicated."""
    entry = P(None, SHARD_AXIS, *([None] * (ndim - 2)))

    @jax.jit
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(entry, P(SHARD_AXIS, None)),
        out_specs=P(), check_vma=False)
    def pairs_count_mesh(pairs, src):
        return jax.lax.psum(pairs_count_local(pairs, src, n_slots),
                            SHARD_AXIS)

    return pairs_count_mesh


class DeviceRunner:
    """Executes shard-slab programs, optionally over a mesh.

    With a mesh, slabs are padded to a multiple of the mesh size on the shard
    axis (pad shards are all-zero; harmless for or/and/xor/andnot+count since
    the executor only reads real shards' outputs / zero rows count zero —
    the ragged fan-out strategy for pjit static shapes).
    """

    def __init__(self, mesh: Optional[Mesh] = None,
                 ici_serving: Optional[bool] = None):
        self.mesh = mesh
        # ICI-native serving kernels: general bitmap programs run as
        # explicit shard_map + psum programs from the per-mesh program
        # cache (eval_count_mesh / eval_row_mesh) instead of relying on
        # GSPMD's lowering of the jit forms. Only meaningful with a mesh;
        # PILOSA_TPU_ICI=0 is the kill switch ([cluster] ici-serving=off
        # reaches here through the Server wiring).
        if ici_serving is None:
            ici_serving = os.environ.get("PILOSA_TPU_ICI", "1") != "0"
        self.ici_serving = bool(ici_serving) and mesh is not None
        if mesh is not None:
            # from here on launches are counted by the devices they go to
            tracing.mesh_launches.watching = True

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else self.mesh.size

    @property
    def n_shard_slots(self) -> int:
        """Devices along the shard axis — what leaf padding must align to
        (the replica axis holds copies, not partitions)."""
        return 1 if self.mesh is None else self.mesh.shape[SHARD_AXIS]

    @property
    def n_replicas(self) -> int:
        return (1 if self.mesh is None
                else self.mesh.shape.get(REPLICA_AXIS, 1))

    def mesh_snapshot(self) -> dict:
        """The /debug/vars `mesh` block: the devices a leaf is laid over
        and, of the launches that went to more than one, how many held a
        collective and how many threads made those (0 or 1)."""
        return {"devices": self.n_devices, "shardSlots": self.n_shard_slots,
                **tracing.mesh_launches.snapshot()}

    def collective(self, fn, *args, **kwargs):
        """Launch fn(*args, **kwargs), a jitted function that reduces over
        the shard axis of its operands (counted_jit's `cross_shard`). With
        a mesh its program holds the all-reduce GSPMD puts there, and is
        launched on the one collective thread; on one device it is the
        plain call."""
        if self.mesh is None:
            return fn(*args, **kwargs)
        return on_collective_thread(fn, *args, **kwargs)

    def _put_shard_padded(self, arr: np.ndarray, shard_axis: int,
                          fill: int = 0) -> jax.Array:
        """Pad `shard_axis` to a multiple of the shard slots and place on
        device(s): that axis shards over the mesh, every other axis (and
        the replica axis) replicates. `fill` is the pad value — zero for
        dense bitvectors (a zero pad shard is empty), the sparse sentinel
        for hybrid index-array leaves (a ZERO pad slot would read as
        "column 0 set" on every pad shard)."""
        # lock-order witness choke point: a host->device upload while
        # holding a witnessed lock stalls that lock's siblings behind the
        # transfer (no-op unless PILOSA_TPU_LOCKCHECK=1)
        lockwitness.note_blocking("dispatch", "put_shard_padded")
        pad = (-arr.shape[shard_axis]) % self.n_shard_slots
        if pad:
            widths = [(0, 0)] * arr.ndim
            widths[shard_axis] = (0, pad)
            arr = np.pad(arr, widths, constant_values=fill)
        arr = np.ascontiguousarray(arr)
        if self.mesh is None:
            return jax.device_put(arr)
        spec = [None] * arr.ndim
        spec[shard_axis] = SHARD_AXIS
        return jax.device_put(arr, NamedSharding(self.mesh, P(*spec)))

    def put_leaf(self, rows: np.ndarray, fill: int = 0) -> jax.Array:
        """Place one leaf [S, W] on device(s), padded to a multiple of the
        shard-axis size and sharded over it — the unit cached by the HBM
        residency manager (parallel/residency.py). On a replica×shard mesh
        the unmentioned replica axis replicates: every replica slice holds
        a full copy of the leaf (ReplicaN on-mesh, SURVEY §2.9). Hybrid
        sparse leaves [S, K] place the same way (axis 0 shards) with
        `fill` set to the sparse sentinel."""
        return self._put_shard_padded(rows, 0, fill=fill)

    def put_pairs(self, pairs: np.ndarray) -> jax.Array:
        """Place one pairs entry (ops/bitvector.py), by pairs int32[2, S, K]
        or by column int32[1, S, 32, W]: the shard axis, the second of
        both, padded with what reads as no bit (the sparse sentinel, no
        rank) and sharded like a leaf's."""
        return self._put_shard_padded(
            pairs, 1, fill=-1 if pairs.ndim == 4 else SPARSE_SENTINEL)

    def pairs_count(self, pairs: jax.Array, src: jax.Array,
                    n_slots: int) -> jax.Array:
        """int32[n_slots] device counts of a pairs entry's rows under the
        filter plane `src` [S', W], launched and not fetched. With a mesh
        the explicit shard_map + psum form, launched like every program
        that holds a collective on the one collective thread."""
        if self.mesh is None:
            return pairs_count(pairs, src, n_slots)

        def launch():
            record_dispatch("ici_program", self.mesh, "pairs", n_slots,
                            pairs, src, devices=self.mesh.size,
                            collective=True)
            return _pairs_count_mesh_fn(self.mesh, n_slots,
                                        pairs.ndim)(pairs, src)

        return on_collective_thread(launch)

    def put_plane_slab(self, planes: np.ndarray) -> jax.Array:
        """Place a [depth, S, W] BSI plane slab on device(s), shard-axis
        padded and sharded like a batch of leaves (every plane partitioned
        over the same shard slots, replicated over the replica axis)."""
        return self._put_shard_padded(planes, 1)

    # -- leaf-list evaluation (HBM-resident leaves, no per-query restack) ---
    # `leaves` is a Python list of [S, W] device arrays (a jit pytree arg):
    # cached leaves stay in HBM and only the compiled program runs per query.

    def row_leaves(self, leaves: list, program, n_shards: int) -> np.ndarray:
        out = np.asarray(self.row_leaves_dev(leaves, program))
        return out[:n_shards]

    def row_leaves_dev(self, leaves: list, program) -> jax.Array:
        """Dense result as a device array [S(padded), W] — stays in HBM for
        further device-side composition (BSI filters, TopN sources). In
        ICI serving mode the program runs as an explicit shard_map and the
        result lands SHARDED across the slice, like its input leaves.

        Dense uint32 leaves only: hybrid programs with sparse operands
        route through ops.bitvector.eval_hybrid instead (the executor's
        compile step decides) — the slice-local route still accepts them
        because the sparse kernels are per-shard local, so GSPMD
        partitions them over the mesh with zero communication; only the
        explicit shard_map program cache below falls back."""
        if self.mesh is not None and self.ici_serving:
            return eval_row_mesh(self.mesh, tuple(leaves), program)
        return eval_row(tuple(leaves), program)

    def count_total_leaves(self, leaves: list, program) -> int:
        return int(self.count_total_leaves_dev(leaves, program))

    def count_total_leaves_dev(self, leaves: list, program) -> jax.Array:
        """The program's total count as a device scalar, launched and not
        fetched: the caller's fetch is what waits for the device."""
        # pad shards are all-zero so they contribute nothing to the count —
        # EXCEPT under "not", which complements pad shards to all-ones; the
        # executor always masks Not() through the existence row (itself a
        # leaf with zero pad shards), keeping pad contributions at zero.
        if self.mesh is not None and self.ici_serving:
            # explicit shard_map + psum serving form: per-device partial
            # counts over the local shard slice, one ICI all-reduce
            return eval_count_mesh(self.mesh, tuple(leaves), program)
        return self.collective(eval_count_total, tuple(leaves), program)

    # -- GroupBy cross-count dispatch (single device / mesh routing) -------

    def groupby_chunk(self, axis_slabs, idx, axis, n_valid, bound: int):
        """(n_live, flat_idx[bound], counts[bound]) device arrays for one
        level chunk — dispatched asynchronously so the executor can enqueue
        every chunk of a level before its single host sync."""
        axis_slabs, idx = tuple(axis_slabs), tuple(idx)
        if self.mesh is not None:
            return groupby_chunk_live_mesh(self.mesh, axis_slabs, idx, axis,
                                           n_valid, bound)
        return groupby_chunk_live(axis_slabs, idx, axis, n_valid, bound)

    def groupby_cmat(self, axis_slabs, idx, axis, n_valid) -> jax.Array:
        """Dense [chunk, R] count matrix (device array) — the fallback when
        a chunk's live set overflows the static pruning bound."""
        axis_slabs, idx = tuple(axis_slabs), tuple(idx)
        if self.mesh is not None:
            return groupby_chunk_matrix_mesh(self.mesh, axis_slabs, idx,
                                             axis, n_valid)
        return groupby_chunk_matrix(axis_slabs, idx, axis, n_valid)
