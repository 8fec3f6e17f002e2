"""The request generator `trees` (the default): Count over random set-
operator trees, as upstream's `pi bench random-query` makes them. A mix is
a data file under benchmarks/traffic/ (JSON); this module turns it, the
configuration's data and the seed into a stream of requests. A later PR
adds a mix by adding a file, not code.

A mix is a fixed population of query templates, as a TPC flight is: the
templates are made once from the mix's own `template_seed`, and a run's
--seed only picks the order they are sent in and the rows that fill them.
A template is a tree whose every leaf names a size class of rows (the
power of two over a row's fullest shard), so every seed sends the same
set of tree sizes over operands of the same sizes, in another order and
with other rows: the work is the mix's, not the seed's.

Keys of a mix:
  source     where the shape of the queries comes from, in words
  clients    how many keep-alive client threads; each sends its next
             request when the last one is answered (a closed loop)
  field      the set field whose rows fill the leaves
  tree       {"max_depth", "max_args", "ops"}: a template is the random
             call tree of upstream's `pi bench random-query`
             (RandomBitmapCall in github.com/pilosa/tools bench/querygen.go,
             from memory): at depth 1 a leaf; above it one of four with
             equal odds, a leaf or one of the three `ops` over 2 to
             max_args - 1 sub-trees made the same way one level down. A
             leaf is a row drawn uniformly over the field's row ids: the
             template keeps a uniform draw in [0, 1), which picks the size
             class of the row at that rank by size, and the run's seed
             picks the row within the class
  templates  how many templates the population holds
  template_seed
  warmup_requests  requests of the same stream sent before the window
             opens, with rows drawn apart: the server has been serving
             this mix for a while when it is measured
  check_sample, check_min  how many answers the reference recomputes, and
             the fewest that make a run's comparison count
Every request is Count(<tree>); its `label`, what the result line's
`extra.by_leaves` groups latencies by, is its template's leaf count: a
request's time follows its leaves, and a percentile of the whole window
lies between two of these groups (PERF.md section 2).
"""

from __future__ import annotations

import threading

import numpy as np

from lib import query


def make_template(rng, tree: dict, depth: int):
    """("leaf", u) or (op, (template, ...))."""
    call = int(rng.integers(0, 4)) if depth > 1 else 0
    if call == 0:
        return ("leaf", float(rng.random()))
    n = 2 if tree["max_args"] <= 2 else \
        int(rng.integers(0, tree["max_args"] - 2)) + 2
    return (tree["ops"][call - 1].lower(),
            tuple(make_template(rng, tree, depth - 1) for _ in range(n)))


def n_leaves(template) -> int:
    if template[0] == "leaf":
        return 1
    return sum(n_leaves(c) for c in template[1])


def size_class(row) -> int:
    """The power of two over the row's fullest shard."""
    return max(int(row.bits_per_shard().max()) - 1, 0).bit_length()


class Traffic:
    label_key = "by_leaves"

    def __init__(self, mix: dict, data, seed: int):
        self.mix = mix
        self.seed = seed
        self.field = mix["field"]
        rows = data.fields[self.field]
        by_size = sorted(rows, key=lambda r: (rows[r].count(), r))
        self.class_at_rank = [size_class(rows[r]) for r in by_size]
        self.rows_of_class = {
            c: np.array([r for r in by_size if size_class(rows[r]) == c])
            for c in set(self.class_at_rank)}
        rng = np.random.default_rng([mix["template_seed"], 0x7E47])
        self.templates = [make_template(rng, mix["tree"],
                                        mix["tree"]["max_depth"])
                          for _ in range(mix["templates"])]
        self._lock = threading.Lock()
        self._window = self._stream(np.random.default_rng([seed, 0x7AFF, 1]))

    def _fill(self, rng, template):
        if template[0] == "leaf":
            rank = int(template[1] * len(self.class_at_rank))
            ids = self.rows_of_class[self.class_at_rank[rank]]
            return ("row", self.field, int(ids[rng.integers(0, ids.size)]))
        return (template[0], tuple(self._fill(rng, c) for c in template[1]))

    def _stream(self, rng):
        while True:
            for i in rng.permutation(len(self.templates)):
                ast = ("count", self._fill(rng, self.templates[i]))
                yield {"pql": query.to_pql(ast), "ast": ast,
                       "label": str(n_leaves(self.templates[i]))}

    def warmup(self) -> list:
        stream = self._stream(np.random.default_rng([self.seed, 0x7AFF, 2]))
        return [next(stream) for _ in range(self.mix["warmup_requests"])]

    def take(self) -> dict:
        """The window's next request (any client thread may ask)."""
        with self._lock:
            return next(self._window)
