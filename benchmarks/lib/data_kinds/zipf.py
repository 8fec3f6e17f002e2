"""The data kind `zipf` (the default): what upstream's own benchmark tool
leaves behind (`pi bench zipf` of github.com/pilosa/tools, written down
from memory in configs/segmentation/config.json). `set_bits_per_shard` x
shards times it sets one bit, at a row and a column each drawn from the
Zipf-Mandelbrot law of lib/datagen.py. Ranks are scattered over the ids by
a permutation (the tool's PermutationGenerator): rows by a shuffle from the
seed, columns by the bijection rank -> (rank * A + b) mod n_columns. A bit
set twice is one bit.

Keys of a field's spec: `rows`, `first_id`, `set_bits_per_shard`,
`row_exponent`, `row_ratio`, `column_exponent`, `column_ratio`.

Every row draws from a generator of its own, keyed by (seed, field, row),
so the bytes do not depend on how the work is spread over threads.
"""

from __future__ import annotations

import numpy as np

from lib.datagen import SHARD_WIDTH, Row, rank_weights, zipf_offset

_PRIME = 2654435761   # prime, so coprime to any column count below it


def draw_ranks(rng, size: int, n: int, exponent: float,
               ratio: float) -> np.ndarray:
    """`size` ranks in [0, n) from the same law, by inverting the
    continuous distribution function (n is tens of millions here, and the
    offset a third of it, so a rank's probability differs from the discrete
    law's by parts in 10**8)."""
    v = zipf_offset(n, exponent, ratio)
    a = 1.0 - exponent
    lo, hi = v ** a, (v + n) ** a
    x = (lo - rng.random(size) * (lo - hi)) ** (1.0 / a) - v
    return np.minimum(x.astype(np.int64), n - 1)


def _row(seed, fi, rank, n_draws, n_shards, spec, shift) -> Row:
    rng = np.random.default_rng([seed, 0xDA7A, fi, rank])
    n_cols = n_shards * SHARD_WIDTH
    ranks = draw_ranks(rng, n_draws, n_cols, spec["column_exponent"],
                       spec["column_ratio"]).astype(np.uint64)
    cols = (ranks * np.uint64(_PRIME) + np.uint64(shift)) % np.uint64(n_cols)
    return Row(n_shards, np.unique(cols.astype(np.uint32)))


def make_field(seed: int, fi: int, spec: dict, n_shards: int, pool) -> dict:
    n_rows = spec["rows"]
    rng = np.random.default_rng([seed, 0xDA7A, fi])
    per_row = rng.multinomial(
        spec["set_bits_per_shard"] * n_shards,
        rank_weights(n_rows, spec["row_exponent"], spec["row_ratio"]))
    ids = rng.permutation(n_rows) + spec.get("first_id", 0)
    shift = int(rng.integers(0, n_shards * SHARD_WIDTH))
    jobs = [(int(ids[rank]), pool.submit(
        _row, seed, fi, rank, int(per_row[rank]), n_shards, spec, shift))
        for rank in range(n_rows)]
    return {row_id: fut.result() for row_id, fut in jobs}
