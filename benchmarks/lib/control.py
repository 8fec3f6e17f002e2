"""The control of the comparison that decides `correct`: the reference put
in the server's place with one stated guarantee broken. Every
configuration here states *exact answers*; the tempting shortcut is an
estimate from a sample of shards, scaled up (an approximate TopN or Count
is what upstream's rank cache gives when it is too small). The control
counts every other shard and doubles it: by `reshape` on packed words, by
`c >> 20` on columns. It is the reference's one count hook
(lib/reference.py), so it breaks every call that counts. A run with
`--control` compares these answers, not the server's, and has to come out
not correct.
"""

from __future__ import annotations

from .datagen import WORDS_PER_SHARD
from .reference import exact_count, popcount


def sampled_count(n_shards: int):
    def count(words, cols=None) -> int:
        if cols is None:
            per_shard = words.reshape(n_shards, WORDS_PER_SHARD)
            return 2 * popcount(per_shard[::2])
        return 2 * exact_count(words, cols[((cols >> 20) & 1) == 0])
    return count
