"""TopN ranking kernels: top-k over row popcounts.

The reference ranks rows with a write-maintained rank cache + min-heap with
threshold pruning (fragment.go:1018-1150, cache.go:136-302). On TPU the
design inverts: row counts are *recomputed* in one fused popcount pass over a
stacked [rows, words] slab — HBM bandwidth makes a full scan of the candidate
slab cheaper than maintaining heap state on writes — and ranking is
`lax.top_k`. The two-phase distributed TopN (approximate per-shard candidates,
then exact recount of the winning row ids — executor.go:694-761) is preserved:
this module provides the per-shard phases; cross-shard Pairs merging stays
host-side exactly like the reference's Pairs.Add (cache.go:317-397).

Tanimoto thresholding (fragment.go:1121-1136) is a select mask over the same
fused counts: keep rows with 100·|A∩B| ≥ T·(|A|+|B|−|A∩B|).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from pilosa_tpu.ops.bitvector import popcount
from pilosa_tpu.utils.telemetry import counted_jit


@counted_jit("topn", static_argnames=("k",))
def top_rows(rows: jax.Array, k: int):
    """(counts, indices) of the k highest-popcount rows of a [R, W] slab.

    Indices are positions into the slab; the caller maps them back to row ids
    (the slab is a gather of candidate rows, not necessarily contiguous ids).
    """
    counts = popcount(rows)
    k = min(k, rows.shape[0])
    return lax.top_k(counts, k)


@counted_jit("topn", static_argnames=("k",))
def top_rows_intersect(rows: jax.Array, src: jax.Array, k: int):
    """Top-k rows ranked by |row ∩ src| (TopN with a Src bitmap argument,
    fragment.go:1063-1080)."""
    counts = popcount(jnp.bitwise_and(rows, src[None]))
    k = min(k, rows.shape[0])
    return lax.top_k(counts, k)


@counted_jit("topn")
def tanimoto_counts(rows: jax.Array, src: jax.Array):
    """Fused per-row (intersection, row, src) counts for Tanimoto filtering.

    tanimoto(a, b) = |a∩b| / (|a| + |b| - |a∩b|); the reference keeps rows
    where ceil(100·tanimoto) > threshold (fragment.go:1096-1100). Division-free
    form evaluated host-side or via tanimoto_mask.
    """
    inter = popcount(jnp.bitwise_and(rows, src[None]))
    rcounts = popcount(rows)
    scount = popcount(src)
    return inter, rcounts, scount


@counted_jit("topn")
def tanimoto_counts_packed(rows: jax.Array, src: jax.Array) -> jax.Array:
    """tanimoto_counts folded into ONE dispatch and ONE host fetch:
    int32[3, R] with [0] = |row ∩ src|, [1] = |row|, [2] = |src|
    broadcast. The popcount-audit form (arXiv:1611.07612's fused-harvest
    idea applied at the dispatch level): the three separate popcounts of
    tanimoto_counts cost three device round trips on high-latency links."""
    inter = popcount(jnp.bitwise_and(rows, src[None]))
    rcounts = popcount(rows)
    scount = popcount(src)
    return jnp.stack(
        [inter, rcounts, jnp.broadcast_to(scount, inter.shape)], axis=0)


@counted_jit("topn", cross_shard=True)
def leaves_counts_packed(leaves: tuple, src: jax.Array) -> jax.Array:
    """tanimoto_counts_packed over row leaves as they lie in residency: a
    tuple of [S, W] planes and the filter plane [S, W] -> int32[3, R],
    summed over shards. Nothing is stacked: a slab of the leaves is a copy
    of every plane a request (and `jnp.stack` outside a program two more,
    one reshape and one broadcast a leaf), alive until the device gets to
    it, and sixteen requests' worth of them was most of a chip's memory;
    here every plane is read once where it lies."""
    def total(x):
        return jnp.sum(popcount(x))

    inter = jnp.stack([total(jnp.bitwise_and(l, src)) for l in leaves])
    rcounts = jnp.stack([total(l) for l in leaves])
    return jnp.stack(
        [inter, rcounts, jnp.broadcast_to(total(src), inter.shape)], axis=0)


@counted_jit("topn")
def tanimoto_mask(inter: jax.Array, rcounts: jax.Array, scount: jax.Array,
                  threshold: jax.Array) -> jax.Array:
    """Boolean keep-mask: 100·inter > threshold·(rcounts + scount − inter).

    STRICT, matching the reference's `ceil(100·count/union) <= T → skip`
    (fragment.go:1096-1100): for integer T, ceil(x) > T ⟺ x > T, so a row
    whose tanimoto equals exactly T/100 is dropped."""
    return 100 * inter > threshold * (rcounts + scount - inter)
