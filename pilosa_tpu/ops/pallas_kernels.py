"""Pallas TPU kernels for the bitmap hot loop.

The XLA path (parallel/mesh.py) already fuses bitwise ops into the popcount
reduce; these kernels additionally control blocking explicitly — one shard's
lane block per grid step, accumulated in SMEM — so multi-operand programs
never materialize intermediates in HBM, and give a place to fuse future
device-side container decompression. On a TPU the kernels compile through
Mosaic; on the CPU backend (tier-1) they run in interpret mode, which
checks results but neither tiling nor VMEM — chip_smoke.py's kernel phase
compiles every entry point here at production shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pilosa_tpu.utils.telemetry import counted_jit

# one shard row = 32768 uint32 lanes = [256, 128] tiles; block 16 shards
# deep to amortize grid overhead. Each operand block is double-buffered by
# the pipeline, so a kernel holds n_operands * blk * W * 4 B * 2 in VMEM:
# 8 MiB for two [16, 32768] operands, inside the 16 MiB scoped limit.
# Kernels whose operand count varies size their block from the arity
# (_program_block).
SHARD_BLOCK = 16
# operand-block VMEM budget (double buffers included): leaves headroom
# under the 16 MiB scoped limit for the output tile and compiler scratch
_VMEM_OPERAND_BUDGET = 12 << 20


def _interpret() -> bool:
    """Interpret mode is a decision per platform, not a default: the CPU
    backend interprets (tier-1 needs it), a TPU compiles, and any other
    backend is refused rather than silently interpreted."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"pallas kernels support the tpu (compiled) and cpu (interpreted) "
        f"backends, not {backend!r}")


def _and_count_kernel(blk, a_ref, b_ref, out_ref):
    """Fused and+popcount for one shard block; per-shard partial counts.

    Output rides as a [1, 128] lane-aligned tile per grid step (TPU vector
    stores need 128-lane alignment); the blk real counts sit in the leading
    lanes, the wrapper strips the padding."""
    inter = jnp.bitwise_and(a_ref[...], b_ref[...])
    counts = jnp.sum(jax.lax.population_count(inter).astype(jnp.int32), axis=-1)
    out_ref[...] = jnp.broadcast_to(counts[:, None], (blk, 128))


def _pad_shards(x: jax.Array, axis: int) -> jax.Array:
    """Zero-pad the shard axis up to a SHARD_BLOCK multiple — TPU blocks'
    second-to-last dim must be a multiple of 8 (the int32 sublane tile) or
    the full axis. Zero shards produce zero/garbage per-shard counts that
    callers slice off; they never fold into real shards' counts."""
    s = x.shape[axis]
    pad = (-s) % SHARD_BLOCK
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@counted_jit("pallas")
def intersect_count(a: jax.Array, b: jax.Array) -> jax.Array:
    """[S, W] x [S, W] -> int32[S] per-shard intersection counts."""
    s, w = a.shape
    a, b = _pad_shards(a, 0), _pad_shards(b, 0)
    sp = a.shape[0]
    blk = SHARD_BLOCK
    padded = pl.pallas_call(
        functools.partial(_and_count_kernel, blk),
        grid=(sp // blk,),
        in_specs=[
            pl.BlockSpec((blk, w), lambda i: (i, 0)),
            pl.BlockSpec((blk, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((blk, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, 128), jnp.int32),
        interpret=_interpret(),
    )(a, b)
    return padded[:s, 0]


def _program_block(n_leaves: int, w: int) -> tuple[int, int]:
    """(shard, word) block for an n-leaf program: the deepest block whose
    double-buffered operands fit _VMEM_OPERAND_BUDGET. Depth halves down
    to the 8-sublane minimum first; past that (7+ full-width leaves) the
    word axis splits, which costs an accumulation over a second grid axis."""
    blk_s, blk_w = SHARD_BLOCK, w
    while n_leaves * blk_s * blk_w * 4 * 2 > _VMEM_OPERAND_BUDGET:
        if blk_s > 8:
            blk_s //= 2
        elif blk_w % 256 == 0:  # halves stay 128-lane multiples
            blk_w //= 2
        else:
            raise ValueError(
                f"{n_leaves} leaves of width {w} exceed the VMEM budget")
    return blk_s, blk_w


def _program_count_kernel(program, n_leaves, blk, *refs):
    """Evaluate a static bitmap program over leaf blocks, fused popcount,
    accumulated over the word grid axis (innermost, so the output block
    stays pinned while operand blocks stream)."""
    leaf_refs = refs[:n_leaves]
    out_ref = refs[n_leaves]
    wb = pl.program_id(1)

    def ev(p):
        if p[0] == "leaf":
            return leaf_refs[p[1]][...]
        if p[0] == "not":
            return jnp.bitwise_not(ev(p[1]))
        xs = [ev(q) for q in p[1:]]
        acc = xs[0]
        for x in xs[1:]:
            if p[0] == "and":
                acc = jnp.bitwise_and(acc, x)
            elif p[0] == "or":
                acc = jnp.bitwise_or(acc, x)
            elif p[0] == "xor":
                acc = jnp.bitwise_xor(acc, x)
            else:  # andnot
                acc = jnp.bitwise_and(acc, jnp.bitwise_not(x))
        return acc

    res = ev(program)
    counts = jnp.sum(jax.lax.population_count(res).astype(jnp.int32), axis=-1)

    @pl.when(wb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.broadcast_to(counts[:, None], (blk, 128))


@counted_jit("pallas", static_argnames=("program",))
def program_count(leaves, program) -> jax.Array:
    """leaves (tuple of [S, W], or stacked [L, S, W]) -> int32[S]: whole
    bitmap-expression popcount in one pass, no HBM intermediates
    regardless of program depth.

    Prefer the tuple form on the serving path: HBM-resident leaves feed
    the kernel directly, where the stacked form would first materialize a
    fresh [L, S, W] copy of the whole operand slab per query.

    Padded shards are sliced off the per-shard counts before returning, so
    even Not-rooted programs (whose complement turns zero padding into all
    ones) stay correct."""
    if isinstance(leaves, (tuple, list)):
        leaf_list = [_pad_shards(x, 0) for x in leaves]
        s = leaves[0].shape[0]
    else:
        s = leaves.shape[1]
        padded_stack = _pad_shards(leaves, 1)
        leaf_list = [padded_stack[j] for j in range(leaves.shape[0])]
    n_leaves = len(leaf_list)
    sp, w = leaf_list[0].shape
    blk, wblk = _program_block(n_leaves, w)
    kernel = functools.partial(_program_count_kernel, program, n_leaves, blk)
    padded = pl.pallas_call(
        kernel,
        grid=(sp // blk, w // wblk),
        in_specs=[pl.BlockSpec((blk, wblk), lambda i, j: (i, j))
                  for _ in range(n_leaves)],
        out_specs=pl.BlockSpec((blk, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, 128), jnp.int32),
        interpret=_interpret(),
    )(*leaf_list)
    return padded[:s, 0]


# -- GroupBy cross-count matrix ----------------------------------------------
# counts[P, R] = popcount(prefix[p] & axis[r]) summed over all words. The
# XLA form relies on loop fusion to keep the [P, R, W] intermediate out of
# HBM; this kernel makes the blocking explicit: one (8-prefix, 128-row,
# 512-word) tile triple per grid step, the [8, 128, 512] AND+popcount in
# VMEM (~2 MiB), partial [8, 128] counts accumulated in the revisited
# output block across the word grid axis (innermost, so the accumulator
# stays pinned while operand tiles stream HBM->VMEM double-buffered).

CC_P_BLK = 8     # prefix tile: int32 sublane minimum
CC_R_BLK = 128   # axis-row tile: int32 lane width
CC_W_BLK = 512   # word tile per step (a: 16 KiB, b: 256 KiB in VMEM)


def _cross_count_kernel(a_ref, b_ref, out_ref):
    wb = pl.program_id(2)
    a, b = a_ref[...], b_ref[...]
    inter = jnp.bitwise_and(a[:, None, :], b[None, :, :])
    partial = jnp.sum(jax.lax.population_count(inter).astype(jnp.int32),
                      axis=-1)  # [CC_P_BLK, CC_R_BLK]

    @pl.when(wb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


def _pad_axis_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@counted_jit("pallas")
def cross_count_matrix(prefix: jax.Array, axis: jax.Array) -> jax.Array:
    """prefix [P, ..., W] x axis [R, ..., W] -> int32[P, R] cross-count
    matrix (leading axes flattened into the word axis). The Pallas form of
    bitvector.cross_count_matrix, selected by PILOSA_TPU_PALLAS; parity is
    tested in tests/test_pallas.py. Zero padding (prefixes to 8, rows to
    128, words to 512) is sliced off the result; padded words AND to zero
    so they never contribute counts."""
    p = prefix.reshape(prefix.shape[0], -1)
    r = axis.reshape(axis.shape[0], -1)
    np_, nr = p.shape[0], r.shape[0]
    p = _pad_axis_to(_pad_axis_to(p, 0, CC_P_BLK), 1, CC_W_BLK)
    r = _pad_axis_to(_pad_axis_to(r, 0, CC_R_BLK), 1, CC_W_BLK)
    pp, wt = p.shape
    rp = r.shape[0]
    out = pl.pallas_call(
        _cross_count_kernel,
        grid=(pp // CC_P_BLK, rp // CC_R_BLK, wt // CC_W_BLK),
        in_specs=[
            pl.BlockSpec((CC_P_BLK, CC_W_BLK), lambda i, j, k: (i, k)),
            pl.BlockSpec((CC_R_BLK, CC_W_BLK), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((CC_P_BLK, CC_R_BLK), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pp, rp), jnp.int32),
        interpret=_interpret(),
    )(p, r)
    return out[:np_, :nr]


# The GroupBy chunk pipeline itself (gather + cross count + mask + prune)
# lives ONCE in bitvector.chunk_count_matrix / groupby_chunk_live; this
# kernel plugs in as their `cross_fn` so the Pallas path can never drift
# from the XLA contract.


def _pair_stream_kernel(ii_ref, jj_ref, a_ref, b_ref, out_ref):
    """One (query, shard-block) grid step of the Count(Intersect) stream:
    the scalar-prefetched ii/jj pick which rows' blocks the pipeline DMAs
    (a_ref/b_ref are [1, blk, W] windows of the SAME resident slab), and
    the per-query count accumulates across the inner shard-block dim into
    a per-query [8, 128] tile (the minimal legal int32 output block; the
    wrapper reads lane [0, 0])."""
    sb = pl.program_id(1)
    inter = jnp.bitwise_and(a_ref[0], b_ref[0])  # [blk, W]
    partial = jnp.sum(jax.lax.population_count(inter).astype(jnp.int32))

    @pl.when(sb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@counted_jit("pallas")
def pair_stream_counts(rows: jax.Array, ii: jax.Array,
                       jj: jax.Array) -> jax.Array:
    """[R, S, W] x int32[K] x int32[K] -> int32[K] per-query intersection
    counts — the Pallas form of the serving hot loop (mesh.py
    count_pair_stream's lax.scan + dynamic gather).

    Explicit-blocking rationale: each query's two operand rows stream
    HBM->VMEM in [blk, W] windows with the data-dependent row index fed
    through scalar prefetch (PrefetchScalarGridSpec), so the pipeline
    double-buffers the DMAs for grid step (q, sb+1) while (q, sb) computes
    — the scan path instead serializes a full-row gather per query. The
    fused and+popcount touches each word exactly once in VMEM."""
    _, s, w = rows.shape
    k = ii.shape[0]
    rows = _pad_shards(rows, 1)
    sp = rows.shape[1]
    blk = SHARD_BLOCK
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k, sp // blk),
        in_specs=[
            pl.BlockSpec((1, blk, w), lambda q, sb, ii, jj: (ii[q], sb, 0)),
            pl.BlockSpec((1, blk, w), lambda q, sb, ii, jj: (jj[q], sb, 0)),
        ],
        # one [8, 128] tile per query — (1, 128) is below the int32 tile
        # minimum and fails TPU lowering
        out_specs=pl.BlockSpec((1, 8, 128), lambda q, sb, ii, jj: (q, 0, 0)),
    )
    out = pl.pallas_call(
        _pair_stream_kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((k, 8, 128), jnp.int32),
        interpret=_interpret(),
    )(ii, jj, rows, rows)
    return out[:, 0, 0]


# -- TopN: fused popcount-rank over the candidate slab ------------------------
# The XLA recount path (ops/topn.py tanimoto_counts) dispatches three
# popcounts over the same [R, W] slab — three passes over the operands in
# HBM. This kernel is the popcount-audit form: ONE blocked pass computes
# the intersection counts, row counts and src count together, packed into
# a single int32 output (single dispatch, single host fetch). Ranking
# stays outside (lax.top_k / the host heap): TopN tie-breaking is
# (count, -row_id) exact and a device top_k would break ties by slab
# position (executor.py _topn_src_walk rationale).

TN_R_BLK = 128   # candidate-row tile: int32 lane width of the output
TN_W_BLK = 2048  # word tile per step (rows: 1 MiB, src: 8 KiB in VMEM)


def _topn_counts_kernel(rows_ref, src_ref, out_ref):
    wb = pl.program_id(1)
    rows = rows_ref[...]                               # [TN_R_BLK, W_BLK]
    src = src_ref[...]                                 # [1, W_BLK]
    inter = jnp.sum(jax.lax.population_count(
        jnp.bitwise_and(rows, src)).astype(jnp.int32), axis=-1)
    rcnt = jnp.sum(jax.lax.population_count(rows).astype(jnp.int32),
                   axis=-1)
    scnt = jnp.sum(jax.lax.population_count(src).astype(jnp.int32))
    # pack the three count families into one [8, TN_R_BLK] tile via
    # select-by-row-index (TPU-safe; no scatter): row 0 = |row ∩ src|,
    # row 1 = |row|, row 2 = |src| broadcast. Each row block owns its own
    # output columns, so scnt is charged in EVERY row block; only the
    # word axis accumulates (wb), summing the per-word-block partials to
    # the full |src| exactly once per column.
    ridx = jax.lax.broadcasted_iota(jnp.int32, (8, TN_R_BLK), 0)
    partial = jnp.where(ridx == 0, inter[None, :], 0)
    partial = partial + jnp.where(ridx == 1, rcnt[None, :], 0)
    partial = partial + jnp.where(ridx == 2, scnt, 0)

    @pl.when(wb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@counted_jit("pallas")
def topn_counts_packed(rows: jax.Array, src: jax.Array) -> jax.Array:
    """uint32[R, W] candidate slab x uint32[W] src row -> int32[3, R]
    packed counts: [0] = |row ∩ src| per row, [1] = |row| per row,
    [2] = |src| broadcast. The Pallas form of the TopN recount's count harvest
    (parity tested in tests/test_pallas.py); zero padding (rows to 128,
    words to 2048) contributes no counts and is sliced off."""
    r, w = rows.shape
    rows_p = _pad_axis_to(_pad_axis_to(rows, 0, TN_R_BLK), 1, TN_W_BLK)
    src_p = _pad_axis_to(src.reshape(1, -1), 1, TN_W_BLK)
    rp, wp = rows_p.shape
    out = pl.pallas_call(
        _topn_counts_kernel,
        grid=(rp // TN_R_BLK, wp // TN_W_BLK),
        in_specs=[
            pl.BlockSpec((TN_R_BLK, TN_W_BLK), lambda i, j: (i, j)),
            pl.BlockSpec((1, TN_W_BLK), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((8, TN_R_BLK), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, rp), jnp.int32),
        interpret=_interpret(),
    )(rows_p, src_p)
    return out[:3, :max(r, 1)]


def top_rows(rows: jax.Array, k: int):
    """(counts, indices) of the k highest-popcount rows — the Pallas form
    of ops/topn.top_rows: counts come from the blocked single-pass kernel
    (src = 0 so only the row-count lane is live), ranking is lax.top_k on
    the device-resident count vector."""
    packed = topn_counts_packed(rows, jnp.zeros_like(rows[0]))
    return jax.lax.top_k(packed[1], min(k, rows.shape[0]))


# -- BSI compare/sum: the plane sweep as one blocked kernel -------------------
# The XLA compare (ops/bsi.py _compare) unrolls the depth sweep into fused
# bitwise ops, but `matched`/`remaining` are XLA values the compiler may
# spill between plane steps. Here the sweep runs per (shard, word) block
# with both accumulators pinned in VMEM across the whole static-depth
# unroll — each plane word streams HBM->VMEM exactly once. The predicate
# enters as a scalar-prefetched per-plane bit vector (SMEM reads inside
# the kernel), NOT as a static value: predicates change per query and must
# not recompile the kernel.

BSI_S_BLK = 8    # shard tile: int32 sublane minimum
BSI_W_BLK = 512  # word tile (depth≤64: planes ≤ 1 MiB per block in VMEM)

# op codes duplicated from ops/bsi.py to avoid a circular import
_LT, _LTE, _GT, _GTE, _EQ, _NEQ = "lt", "lte", "gt", "gte", "eq", "neq"


def _bsi_compare_kernel(op, depth, pred_ref, planes_ref, exists_ref,
                        out_ref):
    exists = exists_ref[...]                        # [S_BLK, W_BLK] uint32

    def m(i):
        # all-ones / all-zeros uint32 scalar mask from predicate bit i
        return jnp.uint32(0) - pred_ref[i].astype(jnp.uint32)

    if op in (_EQ, _NEQ):
        r = exists
        for i in range(depth):
            r = jnp.bitwise_and(
                r, jnp.bitwise_xor(planes_ref[i],
                                   jnp.bitwise_not(m(i))))
        if op == _NEQ:
            r = jnp.bitwise_and(exists, jnp.bitwise_not(r))
        out_ref[...] = r
        return
    matched = jnp.zeros_like(exists)
    remaining = exists
    for i in range(depth - 1, -1, -1):
        mask = m(i)
        plane = planes_ref[i]
        if op in (_LT, _LTE):
            matched = jnp.bitwise_or(matched, jnp.bitwise_and(
                jnp.bitwise_and(remaining, jnp.bitwise_not(plane)), mask))
        else:
            matched = jnp.bitwise_or(matched, jnp.bitwise_and(
                jnp.bitwise_and(remaining, plane), jnp.bitwise_not(mask)))
        remaining = jnp.bitwise_and(
            remaining, jnp.bitwise_xor(plane, jnp.bitwise_not(mask)))
    if op in (_LTE, _GTE):
        matched = jnp.bitwise_or(matched, remaining)
    out_ref[...] = matched


@counted_jit("pallas", static_argnames=("op",))
def bsi_compare(planes: jax.Array, exists: jax.Array, pred_bits,
                op: str) -> jax.Array:
    """uint32[depth, S, W] planes x uint32[S, W] exists x int32[depth]
    predicate bits -> uint32[S, W] match mask — the Pallas form of
    ops/bsi.compare (parity tested in tests/test_pallas.py). Zero-padded
    shards/words carry zero exists bits, so they match nothing."""
    pred_bits = jnp.asarray(pred_bits, dtype=jnp.int32)
    depth, s, w = planes.shape
    planes_p = _pad_axis_to(_pad_axis_to(planes, 1, BSI_S_BLK), 2,
                            BSI_W_BLK)
    exists_p = _pad_axis_to(_pad_axis_to(exists, 0, BSI_S_BLK), 1,
                            BSI_W_BLK)
    sp, wp = exists_p.shape
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(sp // BSI_S_BLK, wp // BSI_W_BLK),
        in_specs=[
            pl.BlockSpec((depth, BSI_S_BLK, BSI_W_BLK),
                         lambda i, j, pred: (0, i, j)),
            pl.BlockSpec((BSI_S_BLK, BSI_W_BLK),
                         lambda i, j, pred: (i, j)),
        ],
        out_specs=pl.BlockSpec((BSI_S_BLK, BSI_W_BLK),
                               lambda i, j, pred: (i, j)),
    )
    out = pl.pallas_call(
        functools.partial(_bsi_compare_kernel, op, depth),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((sp, wp), jnp.uint32),
        interpret=_interpret(),
    )(pred_bits, planes_p, exists_p)
    return out[:s, :w]


def _bsi_sum_kernel(depth, planes_ref, filt_ref, out_ref):
    wb = pl.program_id(1)
    filt = filt_ref[...]                            # [S_BLK, W_BLK]
    cols = [jnp.sum(jax.lax.population_count(
        jnp.bitwise_and(planes_ref[i], filt)).astype(jnp.int32), axis=-1)
        for i in range(depth)]
    cols.append(jnp.sum(jax.lax.population_count(filt).astype(jnp.int32),
                        axis=-1))
    partial = jnp.stack(cols, axis=-1)              # [S_BLK, depth + 1]
    partial = jnp.pad(partial, ((0, 0), (0, 128 - depth - 1)))

    @pl.when(wb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += partial


@counted_jit("pallas")
def bsi_sum_counts(planes: jax.Array, filter_row: jax.Array) -> jax.Array:
    """uint32[depth, S, W] planes x uint32[S, W] filter -> int32[depth+1,
    S]: per-plane filtered popcounts with the filter's own count as the
    last row — the Pallas form of ops/bsi.sum_counts, one blocked pass
    over the plane slab with every per-plane AND+popcount sharing the
    filter tile in VMEM (the XLA form reloads it per plane unless fusion
    saves it). depth+1 must fit the 128-lane count tile."""
    depth, s, w = planes.shape
    if depth + 1 > 128:
        raise ValueError(f"bit depth {depth} exceeds the packed-count tile")
    planes_p = _pad_axis_to(_pad_axis_to(planes, 1, BSI_S_BLK), 2,
                            BSI_W_BLK)
    filt_p = _pad_axis_to(_pad_axis_to(filter_row, 0, BSI_S_BLK), 1,
                          BSI_W_BLK)
    sp, wp = filt_p.shape
    out = pl.pallas_call(
        functools.partial(_bsi_sum_kernel, depth),
        grid=(sp // BSI_S_BLK, wp // BSI_W_BLK),
        in_specs=[
            pl.BlockSpec((depth, BSI_S_BLK, BSI_W_BLK),
                         lambda i, j: (0, i, j)),
            pl.BlockSpec((BSI_S_BLK, BSI_W_BLK), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((BSI_S_BLK, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((sp, 128), jnp.int32),
        interpret=_interpret(),
    )(planes_p, filt_p)
    return out[:s, :depth + 1].T


# -- mesh composition (shard_map wrappers) -----------------------------------
# pallas_call computes on per-device blocks, so composing with a mesh is a
# shard_map whose body runs the single-device kernel on its local shard
# slice and psums the partials over the shard axis on ICI — PILOSA_TPU_PALLAS
# works on the same replica×shard meshes as the XLA path.


@functools.lru_cache(maxsize=None)
def _program_count_mesh_fn(mesh, program, n_leaves: int):
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.parallel.mesh import SHARD_AXIS

    @counted_jit("pallas")
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(tuple(P(SHARD_AXIS, None) for _ in range(n_leaves)),),
        out_specs=P(), check_vma=False)
    def run(leaves_blk):
        counts = program_count(leaves_blk, program)  # local [S_loc]
        return jax.lax.psum(jnp.sum(counts), SHARD_AXIS)

    return run


def program_count_mesh(mesh, leaves: tuple, program) -> jax.Array:
    """tuple of [S, W] leaves (each sharded over the mesh's shard axis,
    replicated over any replica axis) -> scalar total count. The Pallas
    mesh form of mesh.eval_count_total: each device runs the explicitly-
    blocked kernel on its local shard slices — straight from the resident
    leaves, no per-query restack — and the psum rides ICI."""
    leaves = tuple(leaves)
    return _program_count_mesh_fn(mesh, program, len(leaves))(leaves)


@functools.lru_cache(maxsize=None)
def _pair_stream_mesh_fn(mesh):
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.parallel.mesh import REPLICA_AXIS, SHARD_AXIS

    rep_spec = P(REPLICA_AXIS) if REPLICA_AXIS in mesh.shape else P()

    @counted_jit("pallas")
    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, SHARD_AXIS, None), rep_spec, rep_spec),
        out_specs=rep_spec, check_vma=False)
    def run(rows_blk, ii_blk, jj_blk):
        local = pair_stream_counts(rows_blk, ii_blk, jj_blk)  # [K_loc]
        return jax.lax.psum(local, SHARD_AXIS)

    return run


def pair_stream_counts_mesh(mesh, rows: jax.Array, ii: np.ndarray,
                            jj: np.ndarray) -> np.ndarray:
    """Replica-scattered Pallas query stream: the scalar-prefetch kernel
    under shard_map — queries split over the replica axis (each slice
    scans K/R against its full data copy), data split over the shard
    axis, per-query counts psum'd on ICI. The Pallas form of
    mesh.pair_stream_counts. Returns host int64[K]."""
    from pilosa_tpu.parallel.mesh import scatter_queries

    ii_d, jj_d, k, _ = scatter_queries(mesh, ii, jj)
    out = np.asarray(_pair_stream_mesh_fn(mesh)(rows, ii_d, jj_d))
    return out[:k].astype(np.int64)
