"""The request generator `similar`: upstream's chemical-similarity search
as a stream, TopN(field, Row(field=q), n=N, tanimotoThreshold=T) with the
query molecule q drawn uniformly over the field's rows, afresh for every
request from the run's seed, and T from the mix's thresholds.

Keys of a mix:
  generator  "similar"
  source     where the queries come from, in words
  clients    how many keep-alive client threads, each in a closed loop
  field      the field whose rows are the molecules
  n          N of every request
  thresholds {"T": weight}: a pass holds each T `weight` times, in an
             order permuted by the seed
  warmup_requests  drawn requests of the same law sent before the window
             opens, with rows drawn apart; no walk over the rows, which
             would be one request a molecule
  check_sample, check_min  how many answers the reference recomputes, and
             the fewest that make a run's comparison count

Every request's label, what the result line's `extra.by_threshold` groups
latencies by, is its threshold ("T70"). Every seed sends the same
thresholds as often, over other molecules in another order.
"""

from __future__ import annotations

import threading

import numpy as np

from lib import query


class Traffic:
    label_key = "by_threshold"

    def __init__(self, mix: dict, data, seed: int):
        self.mix = mix
        self.seed = seed
        self.field = mix["field"]
        self.n = int(mix["n"])
        self.ids = np.array(data.row_ids(self.field))
        self.one_pass = np.repeat(
            [int(t) for t in mix["thresholds"]],
            [int(w) for w in mix["thresholds"].values()])
        self._lock = threading.Lock()
        self._window = self._stream(np.random.default_rng([seed, 0x5131, 1]))

    def _request(self, q: int, t: int) -> dict:
        ast = ("similar", self.field, q, self.n, t)
        return {"pql": query.to_pql(ast), "ast": ast, "label": f"T{t}"}

    def _stream(self, rng):
        """Requests in passes; q uniform over the field's rows for each."""
        while True:
            for t in rng.permutation(self.one_pass):
                yield self._request(
                    int(self.ids[rng.integers(0, self.ids.size)]), int(t))

    def warmup(self) -> list:
        """`warmup_requests` drawn requests."""
        stream = self._stream(np.random.default_rng([self.seed, 0x5131, 2]))
        return [next(stream) for _ in range(self.mix["warmup_requests"])]

    def take(self) -> dict:
        """The window's next request (any client thread may ask)."""
        with self._lock:
            return next(self._window)
