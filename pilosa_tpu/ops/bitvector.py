"""Dense shard-bitvector algebra: the TPU replacement for roaring container ops.

The reference implements 45 pairwise container kernels (9 type-pair
specializations x 5 ops, roaring/roaring.go:2162-3353) because its operands are
compressed CPU-resident containers. On TPU the design inverts: operands are
*dense* bitvectors in HBM — one uint32 lane array per (row, shard) — so every
op is a single vectorized bitwise instruction over the lanes and popcount is
`lax.population_count` + reduce, which XLA fuses into the producing op. There
is deliberately no array/run/bitmap case analysis on device; compression lives
only in host-side storage (pilosa_tpu.storage.roaring).

Layout: bit position p of a shard lives at word p >> 5, bit p & 31
(little-endian), matching the roaring bitmap-container word layout
(roaring/roaring.go:53) so host<->device conversion is a reinterpret-cast.

All public kernels accept arrays whose *last* axis is the word axis and
broadcast over leading axes, so the same code path serves one row, a stacked
[rows, words] fragment slab, or a sharded [shards, rows, words] mesh operand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from pilosa_tpu.constants import SHARD_WIDTH, WORD_BITS
from pilosa_tpu.utils.telemetry import counted_jit

# ---------------------------------------------------------------------------
# Bitwise algebra (reference semantics: roaring/roaring.go:378-750 Intersect/
# Union/Difference/Xor; here they are single XLA ops over uint32 lanes).
# ---------------------------------------------------------------------------


@counted_jit("bitwise")
def band(a: jax.Array, b: jax.Array) -> jax.Array:
    """Intersection: a & b."""
    return jnp.bitwise_and(a, b)


@counted_jit("bitwise")
def bor(a: jax.Array, b: jax.Array) -> jax.Array:
    """Union: a | b."""
    return jnp.bitwise_or(a, b)


@counted_jit("bitwise")
def bxor(a: jax.Array, b: jax.Array) -> jax.Array:
    """Symmetric difference: a ^ b."""
    return jnp.bitwise_xor(a, b)


@counted_jit("bitwise")
def bandnot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Difference: a &~ b."""
    return jnp.bitwise_and(a, jnp.bitwise_not(b))


@counted_jit("bitwise")
def bnot(a: jax.Array) -> jax.Array:
    """Complement over the full shard width (caller intersects with an
    existence row for Not() semantics, reference executor.go:1478-1520)."""
    return jnp.bitwise_not(a)


# ---------------------------------------------------------------------------
# Popcount reductions (reference: popcount/popcountAndSlice
# roaring/roaring.go:3801-3818, IntersectionCount roaring/roaring.go:353).
#
# Per-operand counts are int32: one shard row holds at most 2^20 bits, and a
# [rows] or [shards] axis of partial counts is reduced host-side (Python int)
# or via psum where totals stay < 2^31. Keeping device accumulators int32
# avoids x64 emulation on TPU.
# ---------------------------------------------------------------------------


@counted_jit("count")
def popcount(x: jax.Array) -> jax.Array:
    """Number of set bits, reduced over the last (word) axis -> int32."""
    return jnp.sum(lax.population_count(x).astype(jnp.int32), axis=-1)


@counted_jit("count")
def intersect_count(a: jax.Array, b: jax.Array) -> jax.Array:
    """popcount(a & b) without materializing a & b in HBM (XLA fuses)."""
    return popcount(jnp.bitwise_and(a, b))


@counted_jit("count")
def union_count(a: jax.Array, b: jax.Array) -> jax.Array:
    return popcount(jnp.bitwise_or(a, b))


@counted_jit("count")
def difference_count(a: jax.Array, b: jax.Array) -> jax.Array:
    return popcount(jnp.bitwise_and(a, jnp.bitwise_not(b)))


@counted_jit("count")
def xor_count(a: jax.Array, b: jax.Array) -> jax.Array:
    return popcount(jnp.bitwise_xor(a, b))


@counted_jit("count", cross_shard=True)
def intersect_chain_count_total(leaves: tuple) -> jax.Array:
    """Total popcount of an N-way intersection in ONE fused dispatch — the
    planner's Count(Intersect(...)) pushdown kernel (pilosa_tpu/planner.py).

    The AND chain and the popcount reduction fuse in XLA, so no [S, W]
    intermediate of the chain ever lands in HBM and no row bitmap is
    materialized on host: only the final int32 scalar crosses the link.
    Compiles once per chain *arity* (the leaves tuple's pytree shape)
    rather than once per nested program tree, so cardinality-reordered
    chains of the same width share a compilation."""
    acc = leaves[0]
    for x in leaves[1:]:
        acc = jnp.bitwise_and(acc, x)
    return jnp.sum(popcount(acc))


@counted_jit("count")
def row_popcounts(rows: jax.Array) -> jax.Array:
    """Per-row set-bit counts for a stacked [..., rows, words] slab -> int32.

    This is the device-side replacement for the reference's per-row rank cache
    counts (cache.go:136): instead of maintaining a heap of (row, count) pairs
    on writes, counts are recomputed in one fused pass when ranking is needed.
    """
    return popcount(rows)


# ---------------------------------------------------------------------------
# GroupBy cross-count primitives: one fused dispatch evaluates a whole
# [prefixes x axis-rows] level of the cross product and prunes zero
# combinations ON DEVICE, so the host sees one small (indices, counts)
# transfer per level instead of a count matrix per chunk. This is the
# batched-popcount insight of the CPU bitmap literature (Chambi et al.,
# Roaring; Muła/Kurz/Lemire AVX2 popcount) lifted to the slab layout: the
# reference walks the cross product one combination at a time
# (executor.go:897-1090 groupByIterator); here a level is a single
# vectorized counts[P, R] = popcount(prefix ⊗ axis) pass.
# ---------------------------------------------------------------------------


@counted_jit("groupby", cross_shard=True)
def cross_count_matrix(prefix: jax.Array, axis: jax.Array) -> jax.Array:
    """counts[P, R]: intersection popcounts of every (prefix, axis-row) pair.

    prefix [P, S, W] x axis [R, S, W] -> int32 [P, R], reduced over shards
    and words. The [P, R, S, W] broadcast-AND fuses into the popcount
    reduction (XLA loop fusion — it never materializes in HBM); callers
    bound P·R·S·W per dispatch (the executor's chunk sizing)."""
    return jnp.sum(intersect_count(prefix[:, None], axis[None]), axis=-1)


def gather_prefix(axis_slabs, idx) -> jax.Array:
    """AND-reduce the prefix rows [chunk, S, W] gathered per-axis from the
    resident axis slabs — traced inside the chunk dispatch so the gathers
    and the reduction fuse with the downstream cross count."""
    pref = axis_slabs[0][idx[0]]
    for k in range(1, len(idx)):
        pref = jnp.bitwise_and(pref, axis_slabs[k][idx[k]])
    return pref


def mask_prefix_rows(cmat: jax.Array, n_valid: jax.Array) -> jax.Array:
    """Zero count-matrix rows past n_valid: chunks are padded to a static
    prefix count (one compile per level), and a padding row gathers row 0's
    data — its counts must not surface as live combinations."""
    rows = lax.broadcasted_iota(jnp.int32, cmat.shape, 0)
    return jnp.where(rows < n_valid, cmat, 0)


@counted_jit("groupby", static_argnames=("bound",))
def live_from_matrix(cmat: jax.Array, bound: int):
    """On-device zero-count pruning: (n_live, flat_idx[bound], counts[bound]).

    flat_idx ascends over the row-major flattening of cmat — exactly the
    reference's lexicographic iterator order — with entries past the real
    live count filled by the out-of-range sentinel P·R (counts 0). n_live
    is the TRUE number of nonzero combinations: when it exceeds `bound`
    the caller must refetch the full matrix (the static bound keeps the
    per-level transfer small without ever silently dropping groups)."""
    flat = cmat.reshape(-1)
    n = flat.shape[0]
    n_live = jnp.sum((flat != 0).astype(jnp.int32))
    (idx,) = jnp.nonzero(flat, size=bound, fill_value=n)
    counts = jnp.where(idx < n, flat[jnp.minimum(idx, n - 1)], 0)
    return n_live, idx.astype(jnp.int32), counts


def chunk_count_matrix(axis_slabs, idx, axis, n_valid) -> jax.Array:
    """The ONE chunk composition both GroupBy callers trace (the single
    device programs below, the mesh's shard_map in parallel/mesh.py):
    gather + AND the prefix slab from the component axes, cross-count
    against the level's axis slab, mask padding rows."""
    return mask_prefix_rows(
        cross_count_matrix(gather_prefix(axis_slabs, idx), axis), n_valid)


@counted_jit("groupby", cross_shard=True, static_argnames=("bound",))
def groupby_chunk_live(axis_slabs: tuple, idx: tuple, axis: jax.Array,
                       n_valid: jax.Array, bound: int):
    """One pipelined GroupBy level chunk, fully on device: the chunk
    composition plus the zero-prune. Returns device arrays only — the
    executor enqueues every chunk of a level before its single host sync."""
    cmat = chunk_count_matrix(axis_slabs, idx, axis, n_valid)
    return live_from_matrix(cmat, bound)


@counted_jit("groupby", cross_shard=True)
def groupby_chunk_matrix(axis_slabs: tuple, idx: tuple, axis: jax.Array,
                         n_valid: jax.Array) -> jax.Array:
    """Dense [chunk, R] count matrix for one chunk — the overflow fallback
    when a chunk's live combinations exceed the pruning bound."""
    return chunk_count_matrix(axis_slabs, idx, axis, n_valid)


# ---------------------------------------------------------------------------
# Range mutations, used by row-level writes and Not/flip semantics
# (reference: bitmapSetRange/bitmapZeroRange/bitmapXorRange
# roaring/roaring.go:2685-2771). Implemented as masked bitwise ops built from
# an iota over bit positions — static-shape, branch-free, XLA-friendly.
# ---------------------------------------------------------------------------


def _bit_positions(n_words: int) -> jax.Array:
    """Absolute bit position of every (word, bit) lane: shape [n_words, 32]."""
    w = lax.broadcasted_iota(jnp.uint32, (n_words, WORD_BITS), 0)
    b = lax.broadcasted_iota(jnp.uint32, (n_words, WORD_BITS), 1)
    return w * WORD_BITS + b


@counted_jit("bitwise", static_argnames=("n_words",))
def range_mask(start: jax.Array, end: jax.Array, n_words: int) -> jax.Array:
    """uint32[n_words] with bits [start, end) set."""
    pos = _bit_positions(n_words)
    keep = (pos >= start) & (pos < end)
    bits = jnp.where(keep, jnp.uint32(1) << (pos % WORD_BITS), jnp.uint32(0))
    # Each lane holds a distinct power of two, so summing the bit axis
    # assembles the word without carries.
    return jnp.sum(bits, axis=-1).astype(jnp.uint32)


@counted_jit("bitwise")
def set_range(x: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.bitwise_or(x, mask)


@counted_jit("bitwise")
def zero_range(x: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.bitwise_and(x, jnp.bitwise_not(mask))


@counted_jit("bitwise")
def xor_range(x: jax.Array, mask: jax.Array) -> jax.Array:
    return jnp.bitwise_xor(x, mask)


# ---------------------------------------------------------------------------
# Hybrid sparse containers: padded sorted-index rows for low-cardinality
# operands (the roaring array-container idea ported to XLA; arXiv:1402.6407
# container taxonomy). A sparse row leaf is int32[..., K]: sorted
# shard-local column ids, padded with SPARSE_SENTINEL — K slots of 4 bytes
# instead of a 128 KiB dense plane, so resident capacity scales with
# CARDINALITY, not shard width. Kernels broadcast over leading axes like
# the dense algebra (one row [S, K], or anything stacked above it); every
# kernel that returns a list returns it sorted and sentinel-padded, so
# compositions chain freely. Every sparse×sparse kernel is a MERGE: one
# sort of the concatenated operands and a compare with the neighbour — no
# gather, no scatter, no loop. On the chip a sort of [64, 8192] costs a
# fraction of a millisecond while a gather costs ~10 ns an element, so a
# binary-search probe (log2(K)+1 dependent gathers, what sparse ∩ / − ran
# before the merge) cost seventy times a merge (PERF.md §6). A list meeting a
# plane becomes a plane: the list-to-plane bridge (_bridge) is a product
# on the matrix unit with no per-entry addressing, fused with the one
# bitwise op. The planner chooses representation per operand
# (pilosa_tpu/planner.py choose_representation) and eval_hybrid() below
# evaluates a mixed sparse/dense program tree.
# ---------------------------------------------------------------------------

# one past the last legal column offset; sorts after every real entry.
# Fits int32 (SHARD_WIDTH = 2^20), and its word index (SHARD_WIDTH >> 5)
# is one past the last dense lane, which no one-hot of _bridge matches.
SPARSE_SENTINEL = SHARD_WIDTH

# sparse∪sparse output keeps Ka+Kb slots; past this the padded arrays stop
# being meaningfully cheaper than a plane (W = 32768 lanes) and eval_hybrid
# densifies the union instead of growing index arrays toward plane size
SPARSE_UNION_CAP = 1 << 14


def _member_in_sorted(vals: jax.Array, ref: jax.Array) -> jax.Array:
    """Membership of vals[..., Kv] in sorted ref[..., Kr], elementwise
    bool, by one binary probe per value — the galloping/skewed regime of
    arXiv:1401.6399 (cost Kv·log Kr). Serves patch_sparse_rows only (a
    handful of removes against a whole row); sparse ∩ / − merge instead
    (_in_both), because each probe step is a dependent gather. Sentinel
    padding never matches (pads in ref are excluded by the value test on
    vals)."""
    kv, kr = vals.shape[-1], ref.shape[-1]
    v2 = vals.reshape(-1, kv)
    r2 = ref.reshape(-1, kr)
    pos = jax.vmap(lambda r, v: jnp.searchsorted(r, v))(r2, v2)
    pos = jnp.minimum(pos, kr - 1)
    hit = jnp.take_along_axis(r2, pos, axis=-1) == v2
    return (hit & (v2 < SPARSE_SENTINEL)).reshape(vals.shape)


def _resort(vals: jax.Array, keep: jax.Array) -> jax.Array:
    """Mask non-kept entries to the sentinel and restore sorted order
    (masking alone breaks it: the sentinel outranks every survivor)."""
    return jnp.sort(jnp.where(keep, vals, SPARSE_SENTINEL), axis=-1)


@counted_jit("sparse")
def sparse_count(sp: jax.Array) -> jax.Array:
    """Set-bit count of a sparse row: entries below the sentinel -> int32
    (the popcount analog; pad shards and pad slots contribute zero)."""
    return jnp.sum((sp < SPARSE_SENTINEL).astype(jnp.int32), axis=-1)


def _in_both(a: jax.Array, b: jax.Array):
    """(vals[..., Ka+Kb], mine, paired): the merge that answers "which of
    a's entries does b hold". Keys a<<1 and (b<<1)|1 (values and the
    sentinel stay below 2^21) sort a's copy of a shared value directly
    before b's; inputs are sorted-unique per row, so an entry of a is in
    b iff the next key is its key|1. `mine` marks a's live entries: pad
    slots of a and b pair up like any shared value, so they are masked
    here, once."""
    keys = jnp.sort(jnp.concatenate([a << 1, (b << 1) | 1], axis=-1),
                    axis=-1)
    edge = jnp.full(keys.shape[:-1] + (1,), -1, dtype=keys.dtype)
    nxt = jnp.concatenate([keys[..., 1:], edge], axis=-1)
    vals = keys >> 1
    mine = ((keys & 1) == 0) & (vals < SPARSE_SENTINEL)
    return vals, mine, nxt == (keys | 1)


@counted_jit("sparse")
def sparse_intersect(a: jax.Array, b: jax.Array) -> jax.Array:
    """sparse ∩ sparse -> sparse[..., min(Ka, Kb)]: a's entries that the
    merge pairs with one of b's."""
    vals, mine, paired = _in_both(a, b)
    return _resort(vals, mine & paired)[..., :min(a.shape[-1], b.shape[-1])]


@counted_jit("sparse")
def sparse_difference(a: jax.Array, b: jax.Array) -> jax.Array:
    """sparse &~ sparse -> sparse[..., Ka]: a's entries the merge leaves
    unpaired."""
    vals, mine, paired = _in_both(a, b)
    return _resort(vals, mine & ~paired)[..., :a.shape[-1]]


def _dense_bit_test(sp: jax.Array, dense: jax.Array) -> jax.Array:
    """Gather-and-test: for each sparse entry, its bit in the dense
    operand — K word gathers. Only the by-pairs recount
    (pairs_count_local) takes it; a list meeting a plane in a program
    tree goes through _bridge. Sentinel slots test the last real lane
    and are masked out by the range check."""
    safe = jnp.minimum(sp, SPARSE_SENTINEL - 1)
    w = jnp.take_along_axis(dense, safe >> 5, axis=-1)
    bit = (w >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    return (bit != 0) & (sp < SPARSE_SENTINEL)


# words a row of the bridge's product covers: word w of the plane lies at
# row w // BRIDGE_WORDS, column w % BRIDGE_WORDS of it. At 32,768 words
# a shard that is 256 rows of 128, which on a v5e is 3-8 % faster a
# launch than 128 rows of 256 at every shape timed, 1 to 64 shards of 64
# to 16,384 slots (benches/sparse_kernels.py, PERF.md section 6)
BRIDGE_WORDS = 128


def _bridge(sp: jax.Array, n_words: int) -> jax.Array:
    """The list-to-plane bridge: sparse[..., K] -> uint32[..., n_words]
    as a batched product on the matrix unit, with no per-entry gather or
    scatter. Entry c is word w = c >> 5 = B·a + b (B = BRIDGE_WORDS) and
    bit j = c & 31, whose value 1 << j is split into two 16-bit halves.
    A shard's plane is then onehot(a)ᵀ [A, K] @ V [K, 2B], V[k, h·B + b]
    entry k's half h — both operands built with the slot index minor and
    contracted over it, the form the matrix unit takes without a
    transpose. Exact: the operands are 0, 1 and powers of two below 2^16
    (exact in bfloat16), entries are unique per row, so an output element
    sums distinct powers of two below 2^16 in a float32 accumulator.
    Sentinel slots, and any column past the plane, take a = -1 and match
    no one-hot. The operands are fused into the product: nothing of
    their [K, A] and [K, 2B] size is written to memory."""
    lead, k = sp.shape[:-1], sp.shape[-1]
    flat = sp.reshape(-1, k)
    b_words = min(BRIDGE_WORDS, n_words)
    a_rows = -(-n_words // b_words)
    w = flat >> 5
    a = jnp.where(flat < n_words * WORD_BITS, w // b_words, -1)
    col = ((flat >> 4) & 1) * b_words + w % b_words
    half = (jnp.int32(1) << (flat & 15)).astype(jnp.bfloat16)
    onehot = (a[:, None, :] == lax.broadcasted_iota(
        jnp.int32, (1, a_rows, 1), 1)).astype(jnp.bfloat16)
    values = jnp.where(col[:, None, :] == lax.broadcasted_iota(
        jnp.int32, (1, 2 * b_words, 1), 1), half[:, None, :],
        jnp.bfloat16(0))
    got = lax.dot_general(onehot, values, (((2,), (2,)), ((0,), (0,))),
                          preferred_element_type=jnp.float32)
    got = got.astype(jnp.uint32)
    words = got[..., :b_words] | (got[..., b_words:] << 16)
    return words.reshape(-1, a_rows * b_words)[:, :n_words].reshape(
        *lead, n_words)


@counted_jit("sparse")
def sparse_intersect_dense(sp: jax.Array, dense: jax.Array) -> jax.Array:
    """sparse ∩ dense -> the plane uint32[..., W]: the bridge and one AND,
    fused into one program."""
    return jnp.bitwise_and(_bridge(sp, dense.shape[-1]), dense)


@counted_jit("sparse")
def sparse_difference_dense(sp: jax.Array, dense: jax.Array) -> jax.Array:
    """sparse &~ dense -> the plane uint32[..., W], fused as above."""
    return jnp.bitwise_and(_bridge(sp, dense.shape[-1]),
                           jnp.bitwise_not(dense))


@counted_jit("sparse")
def sparse_dense_count(sp: jax.Array, dense: jax.Array) -> jax.Array:
    """popcount(sparse ∩ dense) -> int32[...]: the fused plane of
    sparse_intersect_dense counted in the same program."""
    return popcount(jnp.bitwise_and(_bridge(sp, dense.shape[-1]), dense))


def _merge_sorted(a: jax.Array, b: jax.Array):
    """(merged[..., Ka+Kb], dup_prev, dup_next): sorted concatenation with
    adjacent-duplicate masks. Inputs are sorted-unique per row, so a value
    present in both appears as exactly one adjacent pair."""
    srt = jnp.sort(jnp.concatenate([a, b], axis=-1), axis=-1)
    edge = jnp.full(srt.shape[:-1] + (1,), -1, dtype=srt.dtype)
    dup_prev = srt == jnp.concatenate([edge, srt[..., :-1]], axis=-1)
    dup_next = srt == jnp.concatenate([srt[..., 1:], edge], axis=-1)
    return srt, dup_prev, dup_next


@counted_jit("sparse")
def sparse_union(a: jax.Array, b: jax.Array) -> jax.Array:
    """sparse ∪ sparse -> sparse[..., Ka+Kb] (drop the second copy of
    every duplicated value)."""
    srt, dup_prev, _ = _merge_sorted(a, b)
    return _resort(srt, ~dup_prev & (srt < SPARSE_SENTINEL))


@counted_jit("sparse")
def sparse_xor(a: jax.Array, b: jax.Array) -> jax.Array:
    """sparse ^ sparse -> sparse[..., Ka+Kb] (keep values appearing in
    exactly one operand)."""
    srt, dup_prev, dup_next = _merge_sorted(a, b)
    keep = ~dup_prev & ~dup_next & (srt < SPARSE_SENTINEL)
    return _resort(srt, keep)


@counted_jit("sparse", static_argnames=("n_words",))
def sparse_to_dense(sp: jax.Array, n_words: int) -> jax.Array:
    """Materialize sparse[..., K] -> dense uint32[..., n_words] — the
    bridge (_bridge) alone, for ops that need planes (Not, unions with a
    plane, GroupBy slabs, BSI folds, the final Row result)."""
    return _bridge(sp, n_words)


# -- the small rows of one field, recounted under a filter in one launch -----
# A `pairs` entry (executor._pairs_entry) holds every row of a field whose
# fullest shard has no more bits than the sparse threshold, in one of three
# layouts, the shard axis second in all. By pairs, int32[2, S, K]: per
# shard the rows' concatenated sorted columns (plane 0, sentinel-padded)
# and beside each the row's rank in the entry's id list (plane 1). By
# column, int32[1, S, 32, W], bit-major: at [0, s, b, w] the rank of the
# one row that holds column 32 · w + b of shard s, -1 where none does;
# only a field whose columns hold at most one of those rows allows it (a
# record's attribute: one value a column), and the executor takes it
# where its recount is the faster one (PAIRS_BY_COLUMN_SLOTS below) and
# its 4 MiB a shard fit the entry's share of the residency budget. A
# filtered TopN recounts all of the rows at once: the filter plane's bit
# at every stored column, summed by rank — where the dense walk stacks
# one [S, W] plane a candidate row whatever it holds. By pairs the bit
# test is a gather (8 ns a slot on the chip). By column nothing is
# gathered, and bit-major is what lets the filter's words be unpacked
# where they lie: bit b of a tile of words, `(words >> b) & 1`, is a lane
# vector that lines up with row b of the entry's tile element for element,
# with no reshape across lanes (column order, bit minor, needs a
# [tile, 32] -> [32 · tile] relayout a step). As a window slab,
# uint32[1, S, N, R]: the rows' own words, word-major, over the N words
# [lo, lo + N) that cover every stored column of every shard, the row of
# rank r on lane r (R = pairs_count_slots(rows), so the count vector is the
# slab's last axis): a row of a few dozen bits is N words where by pairs it
# is as many slots of 8 bytes, and where the columns of all the rows lie in
# a narrow window (a fingerprint's 2,048 positions) that is the smaller
# entry. Its recount is a streaming AND + popcount summed over the shard
# and word axes, with neither a gather nor a one-hot product.

# entry slots one scan step covers, all of one shard: by column a tile of
# 2,048 filter words and the [32, 2048] ranks under their bits, by pairs
# 65,536 gathered slots. The step's two one-hot operands ([H, PAIRS_STEP]
# and [128, PAIRS_STEP] bfloat16, 32 MiB at H = 128) are fused into the
# product and never leave the chip's own memory; a float32 partial sum is
# exact below 2^24 and turned to int32 every step. On a v5e, by column at
# [32 shards, 10,000 rows]: 2^14 17.2 ms, 2^15 11.1, 2^16 9.8, 2^17 9.6
# (benches/recount_kernels.py --steps)
PAIRS_STEP = 1 << 16

# slots a shard (the power of two over the fullest shard's stored bits)
# from which an entry that may lie by column does: the layout is chosen by
# the recount's time, not by the entry's bytes. By pairs a launch pays the
# gather, 7.6-8.9 ns a slot from 2^15 slots a shard up; by column it passes
# over all 2^20 columns of a shard whatever they hold, 4 MiB a shard. On a
# v5e at 32 shards, by pairs / by column, ms a launch (H = 1; H = 128
# within 0.4 of the first and 1.3 above the second): 2^10 1.5 / 8.7, 2^13
# 3.2 / 8.7, 2^14 5.3 / 8.8, 2^15 9.3 / 9.0 (H = 128: 9.3 / 9.8), 2^16
# 17.0 / 8.8, 2^17 32.8 / 8.9, 2^19 127.0 / 8.7 (benches/recount_kernels.py
# --crossover, PERF.md section 6). The two cross at 2^15, within 5 % of
# each other on either side of it by H, and there the pairs are a
# sixteenth of the bytes: by pairs; the count vector's length moves
# neither side by more than an eighth, so the rule does not read it.
PAIRS_BY_COLUMN_SLOTS = 1 << 16


def pairs_count_slots(n_rows: int) -> int:
    """Length of the count vector for `n_rows` entry rows: 128 · H, H a
    power of two, so that entries of about one size share a program."""
    h = 1
    while h * 128 < n_rows:
        h <<= 1
    return h * 128


def pairs_by_column(shards: list) -> np.ndarray:
    """Host-side builder of an entry by column: [(columns, ranks)] a shard
    (shard-local columns, none twice) -> int32[1, S, 32, W] bit-major."""
    arr = np.full((1, len(shards), WORD_BITS, SHARD_WIDTH // WORD_BITS), -1,
                  np.int32)
    for s, (cols, rank) in enumerate(shards):
        arr[0, s, cols & (WORD_BITS - 1), cols >> 5] = rank
    return arr


def pairs_by_pairs(shards: list, slots: int) -> np.ndarray:
    """Host-side builder of an entry by pairs: [(columns, ranks)] a shard,
    in the order they are to lie -> int32[2, S, slots], sentinel-padded."""
    arr = np.full((2, len(shards), slots), SPARSE_SENTINEL, np.int32)
    for s, (cols, rank) in enumerate(shards):
        arr[0, s, :cols.size] = cols
        arr[1, s, :rank.size] = rank
    return arr


# words a window slab covers at the least: one sublane tile of the [N, R]
# words a shard, which fewer words would be padded to on the device anyway
SLAB_WORDS_MIN = 8


def slab_window(shards: list) -> tuple[int, int]:
    """(lo, n) of the one window slab over [(columns, ranks)] a shard: n
    the power of two of words, at least SLAB_WORDS_MIN, over the words
    that any shard's columns touch, lo its first word, moved down where
    the window would pass the shard's last word."""
    touched = [(int(c.min()) >> 5, int(c.max()) >> 5) for c, _ in shards
               if c.size]
    lo = min((a for a, _ in touched), default=0)
    hi = max((b for _, b in touched), default=0) + 1
    n = SLAB_WORDS_MIN
    while n < hi - lo:
        n <<= 1
    return min(lo, SHARD_WIDTH // WORD_BITS - n), n


def pairs_by_slab(shards: list, lo: int, n_words: int,
                  n_slots: int) -> np.ndarray:
    """Host-side builder of an entry as a window slab: [(columns, ranks)]
    a shard -> uint32[1, S, n_words, n_slots], at [0, s, w, r] word lo + w
    of shard s of the row of rank r. Every stored bit is one bit position
    of the shard's [n_words, n_slots] words, all of them set in one pass
    of the storage library's positions_to_dense."""
    from pilosa_tpu.native import positions_to_dense

    arr = np.empty((1, len(shards), n_words, n_slots), np.uint32)
    for s, (cols, rank) in enumerate(shards):
        pos = ((cols >> 5) - lo).astype(np.int64)
        pos *= n_slots
        pos += rank
        pos <<= 5
        pos |= cols & 31
        arr[0, s] = positions_to_dense(
            pos.view(np.uint64), 0, n_words * n_slots * WORD_BITS
        ).reshape(n_words, n_slots)
    return arr


def _slab_count(slab: jax.Array, src: jax.Array, lo) -> jax.Array:
    """counts int32[R] of a window slab uint32[1, S, N, R] under src
    uint32[S, W]: Σ_s Σ_w popcount(slab[0, s, w, r] & src[s, lo + w]), the
    filter's window broadcast along the lanes. One streaming pass over the
    slab, fused into the sum: nothing of its size is materialized."""
    win = lax.dynamic_slice_in_dim(src, lo, slab.shape[2], axis=1)
    hit = lax.population_count(slab[0] & win[:, :, None])
    return jnp.sum(hit.astype(jnp.int32), axis=(0, 1))


def pairs_count_local(pairs: jax.Array, src: jax.Array, n_slots: int,
                      lo=None) -> jax.Array:
    """counts int32[n_slots] of one block of shards: pairs int32[2, S, K]
    or, by column, int32[1, S, 32, W], or a window slab uint32[1, S, N,
    n_slots] whose window starts at word `lo` (an operand, so that a
    rebuilt entry's window compiles nothing; the slab is
    `_slab_count`'s); src uint32[S, W]. The other two layouts: one scan over
    steps of PAIRS_STEP slots, each within one shard. A step takes its
    ranks as r int32[A, T], slots along lanes, -1 where the filter has no
    bit (by pairs A = 1 and the bit is `_dense_bit_test`'s gather from
    that shard's plane; by column A = 32 and bit b of the tile's T words,
    a lane vector, lines up with row b of the entry's tile), then sums by
    rank as a product of two one-hot matrices on the matrix unit (rank =
    128 · hi + lo; counts[hi, lo] = Σ_k [hi_k = hi] · [lo_k = lo]), both
    built with the slot index minor, r broadcast along sublanes, and
    contracted over it: hiT · loTᵀ, the form the matrix unit takes
    without a transpose. Exact in float32 for a step of 2^16 slots.
    Nothing of the entry's size is materialized beside it: sixteen
    request threads may have this program in flight at once. Timings of
    the alternatives: benches/recount_kernels.py, PERF.md section 6."""
    if pairs.dtype == jnp.uint32:
        return _slab_count(pairs, src, 0 if lo is None else lo)
    by_column = pairs.ndim == 4
    if by_column:
        pairs = pairs[0]
        step = min(PAIRS_STEP // WORD_BITS, src.shape[1])    # words
    else:
        step = min(PAIRS_STEP, pairs.shape[2])
        pairs = jnp.pad(pairs, ((0, 0), (0, 0), (0, -pairs.shape[2] % step)),
                        constant_values=SPARSE_SENTINEL)
    n_shards, per_shard = src.shape[0], pairs.shape[-1] // step
    hi_ids = jnp.arange(n_slots // 128, dtype=jnp.int32)[None, :, None]
    lo_ids = jnp.arange(128, dtype=jnp.int32)[None, :, None]
    bit_ids = jnp.arange(WORD_BITS, dtype=jnp.uint32)[:, None]

    def one(acc, i):
        s, at = i // per_shard, (i % per_shard) * step
        if by_column:
            r = lax.dynamic_slice(pairs, (s, 0, at), (1, WORD_BITS, step))[0]
            words = lax.dynamic_slice(src, (s, at), (1, step))
            b = ((words >> bit_ids) & 1) != 0
        else:
            blk = lax.dynamic_slice(pairs, (0, s, at), (2, 1, step))
            plane = lax.dynamic_index_in_dim(src, s, axis=0, keepdims=False)
            r = blk[1]
            b = _dense_bit_test(blk[0, 0], plane)[None]
        r = jnp.where(b, r, -1)          # -1 >> 7 is -1: it matches no hi
        hi_t = ((r >> 7)[:, None, :] == hi_ids).astype(jnp.bfloat16)
        lo_t = ((r & 127)[:, None, :] == lo_ids).astype(jnp.bfloat16)
        got = lax.dot_general(hi_t, lo_t, (((0, 2), (0, 2)), ((), ())),
                              preferred_element_type=jnp.float32)
        return acc + got.astype(jnp.int32), None

    acc, _ = lax.scan(one, jnp.zeros((n_slots // 128, 128), jnp.int32),
                      jnp.arange(n_shards * per_shard, dtype=jnp.int32))
    return acc.reshape(-1)


@counted_jit("sparse", cross_shard=True, static_argnames=("n_slots",))
def pairs_count(pairs: jax.Array, src: jax.Array, n_slots: int,
                lo=None) -> jax.Array:
    """|row ∩ src| for every row of a pairs entry, summed over shards on
    the device: one launch and one fetch of n_slots int32 a TopN."""
    return pairs_count_local(pairs, src, n_slots, lo)


def sparse_from_columns(columns: np.ndarray, slots: int) -> np.ndarray:
    """Host-side builder: sorted shard-local offsets -> one padded sparse
    row int32[slots] (the dense_from_columns analog)."""
    out = np.full(slots, SPARSE_SENTINEL, dtype=np.int32)
    cols = np.sort(np.asarray(columns, dtype=np.int64))
    n = min(cols.size, slots)
    out[:n] = cols[:n]
    return out


# ---------------------------------------------------------------------------
# Run containers: sorted inclusive-interval rows for long-run operands (the
# roaring run container, arXiv:1603.06549 "Consistently faster and smaller
# compressed bitmaps with Roaring", lifted to XLA). A run row leaf is
# int32[..., 2, R]: [..., 0, :] holds interval starts, [..., 1, :] inclusive
# lasts, sorted ascending by start, disjoint and non-adjacent, padded with
# RUN_SENTINEL starts — 2·R slots of 4 bytes instead of a 128 KiB plane, so
# an existence/time-range row of a few long runs costs tens of bytes per
# shard. Every kernel returns the same sorted sentinel-padded layout; the
# validity predicate is `start < RUN_SENTINEL` (pad shards from
# _put_shard_padded fill the WHOLE slot with the sentinel, so lasts in pad
# slots are never trusted). eval_hybrid() evaluates mixed dense/sparse/run
# trees: intersections keep the cheap representation, everything else
# materializes the run side via run_to_dense.
# ---------------------------------------------------------------------------

# shared with the sparse rep: one past the last legal column offset
RUN_SENTINEL = SPARSE_SENTINEL


def _runs_contain(starts: jax.Array, lasts: jax.Array, vals: jax.Array):
    """(contains, containing_last): for each vals[..., K] point, whether it
    falls inside one of the sorted disjoint runs [starts, lasts][..., R],
    and that run's inclusive last. One binary probe per point (the
    galloping regime again: cost K·log R). Sentinel runs never contain —
    their start equals RUN_SENTINEL, above every legal value."""
    kv, r = vals.shape[-1], starts.shape[-1]
    v2 = vals.reshape(-1, kv)
    s2 = starts.reshape(-1, r)
    l2 = lasts.reshape(-1, r)
    pos = jax.vmap(lambda s, v: jnp.searchsorted(s, v, side="right"))(s2, v2)
    idx = jnp.maximum(pos - 1, 0)
    s = jnp.take_along_axis(s2, idx, axis=-1)
    last = jnp.take_along_axis(l2, idx, axis=-1)
    contains = ((pos > 0) & (v2 >= s) & (v2 <= last)
                & (s < RUN_SENTINEL) & (v2 < RUN_SENTINEL))
    return (contains.reshape(vals.shape),
            last.reshape(vals.shape))


@counted_jit("run")
def run_count(runs: jax.Array) -> jax.Array:
    """Set-bit count of a run row: branch-free interval-length sum
    Σ (last − start + 1) over valid slots -> int32[...] (the popcount
    analog — cost R, independent of how many bits the runs cover)."""
    starts, lasts = runs[..., 0, :], runs[..., 1, :]
    length = jnp.where(starts < RUN_SENTINEL, lasts - starts + 1, 0)
    return jnp.sum(length.astype(jnp.int32), axis=-1)


def _run_overlaps(a: jax.Array, b: jax.Array):
    """(cand, ok, end_min): the overlap intervals of two run rows. Every
    overlap is [max(sa_i, sb_j), min(la_i, lb_j)] for an overlapping
    pair, and its start is always one of the operands' starts — so the
    candidate set is the merged starts, each probed once into BOTH
    operands (2·(Ra+Rb) binary probes, never the O(Ra·Rb) pair matrix).
    `ok[..., k]` marks cand[..., k] as a real overlap start with
    inclusive end end_min[..., k]."""
    sa, la = a[..., 0, :], a[..., 1, :]
    sb, lb = b[..., 0, :], b[..., 1, :]
    cand = jnp.sort(jnp.concatenate([sa, sb], axis=-1), axis=-1)
    in_a, end_a = _runs_contain(sa, la, cand)
    in_b, end_b = _runs_contain(sb, lb, cand)
    # a start shared by both operands emits the identical overlap twice —
    # keep the first of each adjacent-equal candidate pair
    edge = jnp.full(cand.shape[:-1] + (1,), -1, dtype=cand.dtype)
    dup = cand == jnp.concatenate([edge, cand[..., :-1]], axis=-1)
    ok = in_a & in_b & ~dup & (cand < RUN_SENTINEL)
    return cand, ok, jnp.minimum(end_a, end_b)


@counted_jit("run")
def run_intersect(a: jax.Array, b: jax.Array) -> jax.Array:
    """run ∩ run -> run[..., 2, Ra+Rb] by interval merge. Two disjoint
    interval sets produce at most Ra+Rb−1 overlaps, so the static output
    width loses nothing; the argsort restores the sorted-sentinel
    contract for downstream kernels."""
    cand, ok, end_min = _run_overlaps(a, b)
    starts = jnp.where(ok, cand, RUN_SENTINEL)
    lasts = jnp.where(ok, end_min, RUN_SENTINEL)
    order = jnp.argsort(starts, axis=-1)
    return jnp.stack([jnp.take_along_axis(starts, order, axis=-1),
                      jnp.take_along_axis(lasts, order, axis=-1)], axis=-2)


@counted_jit("run")
def run_intersect_count(a: jax.Array, b: jax.Array) -> jax.Array:
    """|run ∩ run| -> int32[...] in one pass: the Count(Intersect)
    pushdown never needs the overlap list SORTED, so this skips
    run_intersect's argsort (the dominant cost — measured ~3x faster
    than the two-step count at bench scale) and sums overlap lengths
    straight off the probe results."""
    cand, ok, end_min = _run_overlaps(a, b)
    length = jnp.where(ok, end_min - cand + 1, 0)
    return jnp.sum(length.astype(jnp.int32), axis=-1)


@counted_jit("run")
def sparse_intersect_run(sp: jax.Array, runs: jax.Array) -> jax.Array:
    """sparse ∩ run -> sparse[..., K]: one containment probe per sparse
    entry (K·log R) — the result stays sparse, never wider than sp."""
    contains, _ = _runs_contain(runs[..., 0, :], runs[..., 1, :], sp)
    return _resort(sp, contains)


@counted_jit("run")
def sparse_difference_run(sp: jax.Array, runs: jax.Array) -> jax.Array:
    """sparse &~ run -> sparse[..., K]: sp entries outside every run."""
    contains, _ = _runs_contain(runs[..., 0, :], runs[..., 1, :], sp)
    return _resort(sp, ~contains & (sp < SPARSE_SENTINEL))


@counted_jit("run", static_argnames=("n_words",))
def run_to_dense(runs: jax.Array, n_words: int) -> jax.Array:
    """Materialize run[..., 2, R] -> dense uint32[..., n_words] — the
    bridge for plane-demanding ops and the run∩dense mask. Diff-array
    scan: +1 at each start, −1 past each last, prefix-sum, then pack the
    resulting bit column to words (each lane a distinct power of two, so
    the pack is a carry-free sum). Sentinel slots scatter past the plane
    and mode="drop" discards them."""
    width = n_words * WORD_BITS
    lead, r = runs.shape[:-2], runs.shape[-1]
    s = runs[..., 0, :].reshape(-1, r)
    last = runs[..., 1, :].reshape(-1, r)

    def one(si, li):
        valid = si < RUN_SENTINEL
        lo = jnp.where(valid, si, width + 1)
        hi = jnp.where(valid, li + 1, width + 1)
        diff = (jnp.zeros((width + 1,), jnp.int32)
                .at[lo].add(1, mode="drop")
                .at[hi].add(-1, mode="drop"))
        bit = (jnp.cumsum(diff)[:width] > 0).reshape(n_words, WORD_BITS)
        shifts = jnp.uint32(1) << lax.broadcasted_iota(
            jnp.uint32, (n_words, WORD_BITS), 1)
        return jnp.sum(jnp.where(bit, shifts, jnp.uint32(0)), axis=-1)

    return jax.vmap(one)(s, last).reshape(*lead, n_words)


@counted_jit("run", static_argnames=("n_words",))
def run_intersect_dense(runs: jax.Array, dense: jax.Array,
                        n_words: int) -> jax.Array:
    """run ∩ dense -> dense uint32[..., n_words]: materialize the run mask
    on device and AND it in one dispatch (XLA fuses the scan into the
    bitwise pass — the mask never lands in HBM by itself)."""
    return jnp.bitwise_and(run_to_dense(runs, n_words), dense)


@counted_jit("run", static_argnames=("n_words",))
def run_dense_count(runs: jax.Array, dense: jax.Array,
                    n_words: int) -> jax.Array:
    """popcount(run ∩ dense) -> int32[...] without the intersection ever
    materializing in HBM (the Count(Intersect(run_row, dense)) pushdown)."""
    return popcount(jnp.bitwise_and(run_to_dense(runs, n_words), dense))


def runs_from_columns(columns: np.ndarray, slots: int) -> np.ndarray:
    """Host-side builder: shard-local offsets -> one padded run row
    int32[2, slots] (the sparse_from_columns analog). Interval breaks are
    the positions where consecutive sorted values differ by more than one
    (the np.diff trick storage/roaring.py Container._runs uses). Intervals
    past `slots` are dropped — callers size slots from the fragment's run
    statistics, so a lossy build indicates a stale stat and the generation
    key retires the leaf on the next write anyway."""
    out = np.full((2, slots), RUN_SENTINEL, dtype=np.int32)
    cols = np.sort(np.asarray(columns, dtype=np.int64))
    if cols.size == 0:
        return out
    return runs_from_intervals(intervals_from_sorted(cols), slots)


def intervals_from_sorted(cols: np.ndarray) -> np.ndarray:
    """Sorted unique offsets -> int64[n, 2] inclusive [start, last] rows."""
    if cols.size == 0:
        return np.empty((0, 2), dtype=np.int64)
    breaks = np.flatnonzero(np.diff(cols) != 1)
    starts = np.concatenate([cols[:1], cols[breaks + 1]])
    lasts = np.concatenate([cols[breaks], cols[-1:]])
    return np.stack([starts, lasts], axis=1)


def runs_from_intervals(intervals: np.ndarray, slots: int) -> np.ndarray:
    """[n, 2] inclusive interval rows -> one padded run row int32[2, slots]
    (the direct from-storage upload path: Fragment.row_runs feeds this
    without ever building a dense plane)."""
    out = np.full((2, slots), RUN_SENTINEL, dtype=np.int32)
    iv = np.asarray(intervals, dtype=np.int64).reshape(-1, 2)
    n = min(iv.shape[0], slots)
    out[0, :n] = iv[:n, 0]
    out[1, :n] = iv[:n, 1]
    return out


# ---------------------------------------------------------------------------
# Batched ingest patch kernels (ISSUE 16): apply one coalesced write batch
# to a RESIDENT leaf in place of evicting it. The host pre-reduces the
# batch to per-word masks (dense) or per-shard sorted add/remove arrays
# (sparse), so the device work is one gather+bitwise+scatter — a few KiB
# over the link instead of a full 128 KiB-per-shard re-upload on the next
# read of a freshly-written row.
# ---------------------------------------------------------------------------


@counted_jit("ingest")
def patch_dense_words(plane: jax.Array, sidx: jax.Array, widx: jax.Array,
                      set_mask: jax.Array, clear_mask: jax.Array) -> jax.Array:
    """Patch a dense row leaf uint32[S', W] at (sidx, widx) word slots:
    new = (old | set_mask) & ~clear_mask. The masks are per-word
    reductions of the whole batch (host-side bitwise_or accumulation), so
    each (shard, word) coordinate appears at most once — a scatter-add
    would corrupt already-set bits with carries; gather-modify-set is
    exact. Pad entries carry sidx == S' (one past the shard axis) with
    zero masks: the gather clamps to a real word it leaves unchanged and
    mode="drop" discards the out-of-range write."""
    cur = plane[sidx, widx]
    new = (cur | set_mask) & ~clear_mask
    return plane.at[sidx, widx].set(new, mode="drop")


@counted_jit("ingest")
def patch_sparse_rows(sp: jax.Array, adds: jax.Array,
                      removes: jax.Array) -> jax.Array:
    """Patch a sparse row leaf int32[S', K] with per-shard sorted
    sentinel-padded add[S', A] / remove[S', R] column arrays: the
    sorted-dedup union of the adds minus the removes, re-padded back to
    the SAME K slots (the caller verified the post-batch cardinality
    still fits K, else it drops the entry and lets the next read
    re-upload through the hybrid chooser)."""
    k = sp.shape[-1]
    srt = jnp.sort(jnp.concatenate([sp, adds], axis=-1), axis=-1)
    edge = jnp.full(srt.shape[:-1] + (1,), -1, dtype=srt.dtype)
    dup_prev = srt == jnp.concatenate([edge, srt[..., :-1]], axis=-1)
    merged = jnp.sort(jnp.where(dup_prev, SPARSE_SENTINEL, srt), axis=-1)
    keep = ~_member_in_sorted(merged, removes) & (merged < SPARSE_SENTINEL)
    return jnp.sort(jnp.where(keep, merged, SPARSE_SENTINEL),
                    axis=-1)[..., :k]


def eval_hybrid(program, leaves: list, kinds: list,
                n_words: int = SHARD_WIDTH // WORD_BITS, on_bridge=None):
    """Evaluate a nested-tuple bitmap program over MIXED dense/sparse/run
    leaves -> (kind, device array). The representation flows bottom-up:
    sparse∩sparse and sparse∩run stay sparse (a merge, a containment
    probe), a list meeting a plane becomes a plane (the bridge fused with
    the AND or AND-NOT: one program), run∩run stays run via interval
    merge, run∩dense materializes the fused run mask, differences keep
    the left operand's kind where a list kernel exists, unions of two
    small sparse rows stay sparse until SPARSE_UNION_CAP, and Not — whose
    complement is dense by construction — materializes, as do run
    operands of unions/xors (point-set growth under ∪/^ is unbounded for
    intervals). Dispatched eagerly per node (operand shapes differ per
    node, so one fused program would recompile per query shape anyway).
    `on_bridge(k)`, if given, is told of every launch that bridges a
    sparse[..., k] list to a plane, alone or fused."""
    def bridged(sp):
        if on_bridge is not None:
            on_bridge(sp.shape[-1])
        return sp

    def dense_of(kind, arr):
        if kind == "sparse":
            return sparse_to_dense(bridged(arr), n_words)
        if kind == "run":
            return run_to_dense(arr, n_words)
        return arr

    def ev(p):
        op = p[0]
        if op == "leaf":
            return kinds[p[1]], leaves[p[1]]
        if op == "not":
            k, a = ev(p[1])
            return "dense", bnot(dense_of(k, a))
        k, acc = ev(p[1])
        for q in p[2:]:
            k2, x = ev(q)
            if op == "and":
                if k == "sparse" and k2 == "sparse":
                    acc = sparse_intersect(acc, x)
                elif k == "sparse" and k2 == "run":
                    acc = sparse_intersect_run(acc, x)
                elif k == "run" and k2 == "sparse":
                    acc, k = sparse_intersect_run(x, acc), "sparse"
                elif k == "run" and k2 == "run":
                    acc = run_intersect(acc, x)
                elif k == "sparse":
                    acc, k = sparse_intersect_dense(bridged(acc), x), "dense"
                elif k2 == "sparse":
                    acc = sparse_intersect_dense(bridged(x), acc)
                elif k == "run":
                    acc, k = run_intersect_dense(acc, x, n_words), "dense"
                elif k2 == "run":
                    acc = run_intersect_dense(x, acc, n_words)
                else:
                    acc = band(acc, x)
            elif op == "andnot":
                if k == "sparse" and k2 == "sparse":
                    acc = sparse_difference(acc, x)
                elif k == "sparse" and k2 == "run":
                    acc = sparse_difference_run(acc, x)
                elif k == "sparse":
                    acc, k = sparse_difference_dense(bridged(acc), x), "dense"
                else:
                    acc = bandnot(dense_of(k, acc), dense_of(k2, x))
                    k = "dense"
            elif op in ("or", "xor"):
                if (k == "sparse" and k2 == "sparse"
                        and acc.shape[-1] + x.shape[-1] <= SPARSE_UNION_CAP):
                    acc = (sparse_union if op == "or" else sparse_xor)(acc, x)
                else:
                    acc = (bor if op == "or" else bxor)(
                        dense_of(k, acc), dense_of(k2, x))
                    k = "dense"
            else:
                raise ValueError(f"unknown op {op!r}")
        return k, acc

    return ev(program)


def hybrid_count(program, leaves: list, kinds: list,
                 n_words: int = SHARD_WIDTH // WORD_BITS) -> int:
    """Total count of a mixed dense/sparse/run program: hybrid_count_dev's
    per-shard partials, fetched (the one blocking call) and summed on the
    host."""
    return int(np.asarray(
        hybrid_count_dev(program, leaves, kinds, n_words)).sum())


def hybrid_count_dev(program, leaves: list, kinds: list,
                     n_words: int = SHARD_WIDTH // WORD_BITS,
                     on_bridge=None) -> jax.Array:
    """Per-shard counts of a mixed dense/sparse/run program, launched and
    NOT fetched — sparse results count their live slots, run results sum
    interval lengths (neither ever materializes a plane), dense results
    popcount. Every kernel is enqueued asynchronously; the caller's fetch
    of the returned array is what waits for the device. `on_bridge` as
    eval_hybrid's.

    The reduction stays PER-SHARD on device and sums on host: every
    hybrid kernel is per-shard local (zero collectives), so on a mesh the
    sharded program partitions with no cross-device dependencies and
    concurrent request threads can dispatch freely — a device-side total
    would insert a GSPMD all-reduce, and a program that holds a
    collective has to be launched on the process's one collective thread
    (parallel/mesh.py on_collective_thread), a hop a request for each of
    these kernels' callers."""
    # all-run AND (the Count(Intersect) pushdown's common shape): fold
    # with run_intersect and finish with the fused run_intersect_count —
    # the final overlap list is never sorted or materialized
    if (isinstance(program, tuple) and program[0] == "and"
            and len(program) >= 3
            and all(isinstance(q, tuple) and q[0] == "leaf"
                    and kinds[q[1]] == "run" for q in program[1:])):
        ops = [leaves[q[1]] for q in program[1:]]
        acc = ops[0]
        for x in ops[1:-1]:
            acc = run_intersect(acc, x)
        return run_intersect_count(acc, ops[-1])

    kind, arr = eval_hybrid(program, leaves, kinds, n_words=n_words,
                            on_bridge=on_bridge)
    if kind == "sparse":
        return sparse_count(arr)
    if kind == "run":
        return run_count(arr)
    return popcount(arr)


# ---------------------------------------------------------------------------
# Host <-> device conversion (numpy, zero-copy friendly).
# ---------------------------------------------------------------------------


def dense_from_columns(columns: np.ndarray, width: int = SHARD_WIDTH) -> np.ndarray:
    """Pack sorted-or-not column offsets (within one shard) into a dense
    little-endian uint32 bitvector of `width` bits."""
    if width % WORD_BITS:
        raise ValueError(f"width must be a multiple of {WORD_BITS}")
    bits = np.zeros(width, dtype=np.uint8)
    cols = np.asarray(columns, dtype=np.int64)
    if cols.size:
        if cols.min() < 0 or cols.max() >= width:
            raise ValueError("column offset out of shard range")
        bits[cols] = 1
    packed = np.packbits(bits, bitorder="little")
    return packed.view("<u4").copy()


def columns_from_dense(words: np.ndarray) -> np.ndarray:
    """Inverse of dense_from_columns: set-bit positions as int64 offsets."""
    words = np.ascontiguousarray(np.asarray(words, dtype=np.uint32))
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    return np.flatnonzero(bits).astype(np.int64)
