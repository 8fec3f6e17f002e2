"""Bit-sliced index (BSI) kernels: integer aggregation over bit planes.

The reference stores an int field as vertical bit-columns: rows 0..bitDepth-1
are place values and row bitDepth is the not-null/existence row
(fragment.go:597-618); `sum` is a per-plane popcount loop (fragment.go:718),
`min`/`max` a greedy bit descent (fragment.go:745-806) and `rangeOp` a
borrow/carry sweep over rows (fragment.go:808-985) — all sequential Go loops
over compressed containers.

Here each plane is a dense bitvector lane array and the sweeps are *unrolled*
at trace time over the (static) bit depth, producing one fused XLA program of
bitwise ops + popcounts with no data-dependent control flow: data-dependent
"if zeros exist" decisions become branch-free select masks.

Numeric protocol (avoids int64 emulation on TPU): kernels return *per-plane*
int32 popcounts or 0/1 bit-decision vectors; the host assembles arbitrary-
precision Python ints from them (Σ 2^i · counts[i]) and performs cross-shard /
cross-node reduction exactly. Predicates enter as per-plane 0/1 vectors, never
as wide scalars.

Plane layout: ``planes`` is uint32[depth, ..., W] (plane 0 = LSB), broadcast
over any batch axes between depth and the word axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu.ops.bitvector import popcount
from pilosa_tpu.utils.telemetry import counted_jit

# Comparison op codes (reference: pql/ast.go:451 Condition ops).
LT, LTE, GT, GTE, EQ, NEQ = "lt", "lte", "gt", "gte", "eq", "neq"


def _ones_mask(bit: jax.Array) -> jax.Array:
    """0/1 scalar (or batch) -> all-ones / all-zeros uint32 select mask."""
    return (jnp.uint32(0) - bit.astype(jnp.uint32))[..., None]


@counted_jit("bsi")
def plane_counts(planes: jax.Array, filter_row: jax.Array) -> jax.Array:
    """popcount(plane_i & filter) for every plane -> int32[depth, ...].

    Host computes  sum = Σ_i 2^i · Σ_shards counts[i]  exactly in Python ints
    (reference: fragment.go:718-741 `sum`).
    """
    return popcount(jnp.bitwise_and(planes, filter_row[None]))


@counted_jit("bsi")
def sum_counts(planes: jax.Array, filter_row: jax.Array) -> jax.Array:
    """plane_counts with the filter's own popcount appended as the last row
    -> int32[depth + 1, ...]: everything Sum needs in ONE dispatch and ONE
    host fetch (rows 0..depth-1 = per-plane counts, row depth = value
    count). Matters on high-latency device links where each fetch is a
    round trip."""
    pc = popcount(jnp.bitwise_and(planes, filter_row[None]))
    return jnp.concatenate([pc, popcount(filter_row)[None]], axis=0)


def bsi_min(planes: jax.Array, candidate: jax.Array):
    """Greedy high-to-low bit descent for the minimum value.

    `candidate` is exists & filter. At each plane, rows with a 0 bit are
    strictly smaller; restrict to them when any exist, otherwise the bit is
    forced to 1 (reference: fragment.go:745-775).

    Returns (bits int32[depth, ...], count int32[...]) — bits[i] is the i-th
    bit of the min; count is how many rows attain it.
    """
    depth = planes.shape[0]
    bits = []
    for i in range(depth - 1, -1, -1):
        zeros = jnp.bitwise_and(candidate, jnp.bitwise_not(planes[i]))
        has_zero = (popcount(zeros) > 0).astype(jnp.int32)
        keep = _ones_mask(has_zero)
        candidate = jnp.bitwise_or(
            jnp.bitwise_and(zeros, keep),
            jnp.bitwise_and(jnp.bitwise_and(candidate, planes[i]), jnp.bitwise_not(keep)),
        )
        bits.append(1 - has_zero)
    bits.reverse()
    return jnp.stack(bits), popcount(candidate)


def bsi_max(planes: jax.Array, candidate: jax.Array):
    """Mirror of bsi_min: prefer rows with a 1 bit (fragment.go:778-806)."""
    depth = planes.shape[0]
    bits = []
    for i in range(depth - 1, -1, -1):
        ones = jnp.bitwise_and(candidate, planes[i])
        has_one = (popcount(ones) > 0).astype(jnp.int32)
        keep = _ones_mask(has_one)
        candidate = jnp.bitwise_or(
            jnp.bitwise_and(ones, keep),
            jnp.bitwise_and(jnp.bitwise_and(candidate, jnp.bitwise_not(planes[i])), jnp.bitwise_not(keep)),
        )
        bits.append(has_one)
    bits.reverse()
    return jnp.stack(bits), popcount(candidate)


bsi_min = counted_jit("bsi")(bsi_min)
bsi_max = counted_jit("bsi")(bsi_max)


@counted_jit("bsi")
def bsi_min_packed(planes: jax.Array, candidate: jax.Array) -> jax.Array:
    """bsi_min with bits and count packed into one int32[depth + 1, ...] —
    single dispatch + single fetch (row depth = attaining-row count)."""
    bits, cnt = bsi_min(planes, candidate)
    return jnp.concatenate([bits, cnt[None]], axis=0)


@counted_jit("bsi")
def bsi_max_packed(planes: jax.Array, candidate: jax.Array) -> jax.Array:
    bits, cnt = bsi_max(planes, candidate)
    return jnp.concatenate([bits, cnt[None]], axis=0)


def _compare(planes, exists, pred_bits, op):
    """Branch-free bit-sliced comparison sweep (fragment.go:808-985).

    pred_bits: int32[depth] of 0/1, pred_bits[i] = i-th bit of the predicate.
    """
    depth = planes.shape[0]

    if op in (EQ, NEQ):
        r = exists
        for i in range(depth):
            m = _ones_mask(pred_bits[i].astype(jnp.uint32))
            # keep rows whose plane bit equals the predicate bit
            r = jnp.bitwise_and(r, jnp.bitwise_xor(planes[i], jnp.bitwise_not(m)))
        if op == NEQ:
            r = jnp.bitwise_and(exists, jnp.bitwise_not(r))
        return r

    # LT/LTE/GT/GTE: high-to-low sweep maintaining
    #   matched   — rows already strictly decided
    #   remaining — rows equal to the predicate so far
    matched = jnp.zeros_like(exists)
    remaining = exists
    for i in range(depth - 1, -1, -1):
        bit = pred_bits[i].astype(jnp.uint32)
        m = _ones_mask(bit)  # all-ones when predicate bit is 1
        if op in (LT, LTE):
            # predicate bit 1: rows with 0 here are strictly less
            matched = jnp.bitwise_or(
                matched, jnp.bitwise_and(jnp.bitwise_and(remaining, jnp.bitwise_not(planes[i])), m)
            )
        else:
            # predicate bit 0: rows with 1 here are strictly greater
            matched = jnp.bitwise_or(
                matched, jnp.bitwise_and(jnp.bitwise_and(remaining, planes[i]), jnp.bitwise_not(m))
            )
        # remaining keeps rows whose bit equals the predicate bit
        remaining = jnp.bitwise_and(remaining, jnp.bitwise_xor(planes[i], jnp.bitwise_not(m)))
    if op in (LTE, GTE):
        matched = jnp.bitwise_or(matched, remaining)
    return matched


# counted_jit, not raw jax.jit: BSI Range recompiles must show in the
# per-family XLA compile/dispatch telemetry like every other kernel
# (pilosa-lint `raw-jit` guards this for all of pilosa_tpu/ops/)
_compare = counted_jit("bsi", static_argnames=("op",))(_compare)


def compare(planes: jax.Array, exists: jax.Array, pred_bits,
            op: str) -> jax.Array:
    """Dense bitvector of rows (columns) whose BSI value satisfies `op pred`.

    BETWEEN is composed by the caller as GTE(a) & LTE(b), matching the
    reference's executeRangeBetweenShard (executor.go) semantics. Takes
    any batch shape: planes [depth, ..., W], exists [..., W]."""
    pred_bits = jnp.asarray(pred_bits, dtype=jnp.int32)
    if pred_bits.shape[0] != planes.shape[0]:
        raise ValueError("pred_bits length must equal plane depth")
    return _compare(planes, exists, pred_bits, op)


# ---------------------------------------------------------------------------
# Host-side helpers for the exact-integer protocol.
# ---------------------------------------------------------------------------


def value_to_bits(value: int, depth: int) -> np.ndarray:
    """Split a non-negative int into per-plane 0/1 bits (LSB first)."""
    if value < 0:
        raise ValueError("BSI stored values are offsets from the field min; must be >= 0")
    return np.array([(value >> i) & 1 for i in range(depth)], dtype=np.int32)


def bits_to_value(bits) -> int:
    """Assemble Python int from per-plane bits (LSB first)."""
    return sum((int(b) & 1) << i for i, b in enumerate(np.asarray(bits).tolist()))


def counts_to_sum(counts) -> int:
    """Σ 2^i · counts[i] as an exact Python int."""
    return sum(int(c) << i for i, c in enumerate(np.asarray(counts).tolist()))
