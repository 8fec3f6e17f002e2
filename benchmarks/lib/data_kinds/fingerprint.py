"""The data kind `fingerprint`: molecules as rows, the positions of their
fingerprint bits as columns, after upstream's chemical-similarity example
(Pilosa v1.2 docs, configs/chem-similarity/config.json) and the repo's
rendering of it, examples/similarity.py. Every column is a position in
[0, positions); a row is one molecule.

Molecules come in families of `family_size` analogues. A family draws a
motif: its size from a log-normal law (`motif_median`, `motif_sigma`,
clipped to [motif_min, motif_max]), its positions without replacement
from the position law. A member keeps each motif bit with probability
`motif_share` and adds Poisson(`noise_mean`) noise bits drawn, with
replacement, from the same law. The position law is the Zipf-Mandelbrot
one of lib/datagen.py over `positions` ranks (`position_exponent`,
`position_ratio`), the ranks scattered over the positions by a shuffle
from the seed, so that a few substructure bits lie in most molecules.
A member's size is then its family's motif size thinned, plus noise: the
sizes of one family are alike, those of two families are not, and the
Tanimoto band of a query prunes the other families.

Keys of a field's spec: `rows`, `first_id`, `positions`, `family_size`,
`motif_median`, `motif_sigma`, `motif_min`, `motif_max`, `motif_share`,
`noise_mean`, `position_exponent`, `position_ratio`. Row ids are the
molecules scattered over first_id .. first_id + rows - 1 by a shuffle
from the seed.

Each family draws from a generator keyed by (seed, field, family), so the
bytes do not depend on how the families are spread over threads; a
family is drawn whole with numpy, no Python loop over its members or
columns.
"""

from __future__ import annotations

import numpy as np

from lib.datagen import Row, rank_weights

_CHUNK = 64   # families a job


def _law(seed: int, fi: int, spec: dict) -> tuple:
    """(log-weight of each position, cumulative weights by position)."""
    n = spec["positions"]
    w = rank_weights(n, spec["position_exponent"], spec["position_ratio"])
    by_pos = np.empty(n)
    by_pos[np.random.default_rng([seed, 0xF1A6, fi]).permutation(n)] = w
    return np.log(by_pos), np.cumsum(by_pos)


def _family(seed: int, fi: int, fam: int, members: int, spec: dict,
            log_w: np.ndarray, cdf: np.ndarray) -> tuple:
    """(the family's members' sorted positions, one after another; how
    many each member holds)."""
    n_pos = spec["positions"]
    rng = np.random.default_rng([seed, 0xF1A6, fi, fam])
    size = int(np.clip(round(spec["motif_median"] * np.exp(
        spec["motif_sigma"] * rng.standard_normal())),
        spec["motif_min"], spec["motif_max"]))
    # `size` positions without replacement, each by its weight: the top
    # keys of log w + Gumbel noise
    keys = log_w - np.log(-np.log(rng.random(n_pos)))
    motif = np.argpartition(-keys, size - 1)[:size]
    kept = rng.random((members, size)) < spec["motif_share"]
    noise_n = rng.poisson(spec["noise_mean"], members)
    noise = np.minimum(np.searchsorted(cdf, rng.random(int(noise_n.sum())),
                                       side="right"), n_pos - 1)
    member = np.concatenate([np.nonzero(kept)[0],
                             np.repeat(np.arange(members), noise_n)])
    pos = np.concatenate([np.broadcast_to(motif, kept.shape)[kept], noise])
    key = np.unique(member.astype(np.int64) * n_pos + pos)
    counts = np.bincount(key // n_pos, minlength=members)
    return (key % n_pos).astype(np.uint32), counts


def make_field(seed: int, fi: int, spec: dict, n_shards: int, pool) -> dict:
    n_rows, fam_size = spec["rows"], spec["family_size"]
    log_w, cdf = _law(seed, fi, spec)
    n_fam = -(-n_rows // fam_size)

    def job(first: int) -> list:
        return [_family(seed, fi, f, min(fam_size, n_rows - f * fam_size),
                        spec, log_w, cdf)
                for f in range(first, min(first + _CHUNK, n_fam))]

    fams = [fam for part in pool.map(job, range(0, n_fam, _CHUNK))
            for fam in part]
    cols = np.concatenate([c for c, _ in fams])
    ends = np.cumsum(np.concatenate([n for _, n in fams]))
    starts = np.concatenate([[0], ends[:-1]])
    ids = (np.random.default_rng([seed, 0x1D5, fi]).permutation(n_rows)
           + spec.get("first_id", 0))
    return {int(i): Row(n_shards, cols[a:b])
            for i, a, b in zip(ids.tolist(), starts.tolist(), ends.tolist())}
