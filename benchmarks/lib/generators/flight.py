"""The request generator `flight`: a fixed list of queries with parameter
slots, as a benchmark's flight of queries is (the four of the
billion-taxi-rides benchmark, TPC's), sent in seeded permuted passes. A
mix is a data file under benchmarks/traffic/ (JSON) and says which calls,
over which fields, with which slots; a later PR adds one by adding a file.

Keys of a mix:
  generator  "flight"
  source     where the queries come from, in words
  clients    how many keep-alive client threads, each in a closed loop
  queries    a list of {"call", "weight", "label", ...}: `call` names a
             file under lib/calls/ (Count, TopN, GroupBy, whatever is
             there) and the other keys are that call's own (see its
             docstring): fields by name, `n` a literal, trees as
             lib/query.py's JSON. A tree's leaf is a literal row,
             {"row": {"field": f, "id": 3}}, or a slot,
             {"row": {"field": f, "draw": "uniform" | "by_size"}}:
             `uniform` over the field's row ids, `by_size` a row with the
             odds of its share of the field's bits (the value of a column
             picked at random). `weight` (a whole number, default 1) is
             how often the query comes in a pass; `label` (default the
             call's name) is what the result line's `extra.by_query`
             groups latencies by
  warmup_requests  requests of the same stream sent before the window
             opens, with slots drawn apart
  check_sample, check_min  how many answers the reference recomputes, and
             the fewest that make a run's comparison count

A pass holds every query `weight` times, in an order permuted by the
run's seed, and every slot is drawn afresh each time from the run's seed:
a fixed list would be answered from the program's result cache. Every seed
sends the same calls as often, over other rows in another order.
"""

from __future__ import annotations

import threading

import numpy as np

from lib import query


class Traffic:
    label_key = "by_query"

    def __init__(self, mix: dict, data, seed: int):
        self.mix = mix
        self.seed = seed
        self.data = data
        self.queries = mix["queries"]
        self.calls = [query.call_of(q["call"]) for q in self.queries]
        self.one_pass = np.repeat(
            np.arange(len(self.queries)),
            [int(q.get("weight", 1)) for q in self.queries])
        self._ids: dict = {}
        self._lock = threading.Lock()
        self._window = self._stream(np.random.default_rng([seed, 0xF117, 1]))

    def _draw(self, rng, field: str, how: str) -> int:
        if field not in self._ids:
            rows = self.data.fields[field]
            ids = np.array(sorted(rows))
            size = np.array([rows[r].count() for r in ids], dtype=np.float64)
            self._ids[field] = (ids, np.cumsum(size) / size.sum())
        ids, share = self._ids[field]
        if how == "uniform":
            return int(ids[rng.integers(0, ids.size)])
        if how == "by_size":
            return int(ids[min(np.searchsorted(share, rng.random(),
                                               side="right"), ids.size - 1)])
        raise ValueError(f"unknown draw {how!r}")

    def _stream(self, rng):
        def tree(doc):
            return query.tree_from_json(
                doc, lambda field, how: self._draw(rng, field, how))

        while True:
            for i in rng.permutation(self.one_pass):
                spec = self.queries[i]
                ast = self.calls[i].build(spec, tree)
                yield {"pql": self.calls[i].to_pql(ast), "ast": ast,
                       "label": spec.get("label", spec["call"])}

    def warmup(self) -> list:
        stream = self._stream(np.random.default_rng([self.seed, 0xF117, 2]))
        return [next(stream) for _ in range(self.mix["warmup_requests"])]

    def take(self) -> dict:
        """The window's next request (any client thread may ask)."""
        with self._lock:
            return next(self._window)
