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
             opens, with slots drawn apart. Before them the warm-up walks
             every value of every slot once (`_walk`): every row is met
             before the window, every combination of rows is not
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

    def _rows(self, field: str) -> tuple:
        """(row ids in order, each id's cumulated share of the bits)."""
        if field not in self._ids:
            rows = self.data.fields[field]
            ids = np.array(sorted(rows))
            size = np.array([rows[r].count() for r in ids], dtype=np.float64)
            self._ids[field] = (ids, np.cumsum(size) / size.sum())
        return self._ids[field]

    def _draw(self, rng, field: str, how: str) -> int:
        ids, share = self._rows(field)
        if how == "uniform":
            return int(ids[rng.integers(0, ids.size)])
        if how == "by_size":
            return int(ids[min(np.searchsorted(share, rng.random(),
                                               side="right"), ids.size - 1)])
        raise ValueError(f"unknown draw {how!r}")

    def _request(self, i: int, draw) -> dict:
        """Query `i` of the mix, its slots filled by draw(field, how)."""
        spec = self.queries[i]
        ast = self.calls[i].build(
            spec, lambda doc: query.tree_from_json(doc, draw))
        return {"pql": self.calls[i].to_pql(ast), "ast": ast,
                "label": spec.get("label", spec["call"])}

    def _stream(self, rng):
        while True:
            for i in rng.permutation(self.one_pass):
                yield self._request(
                    i, lambda field, how: self._draw(rng, field, how))

    def _walk(self):
        """Every value of every slot, by rule and not by luck: query by
        query, the k-th request gives each of the query's slots the k-th
        row id of its field (round again where a field is shorter) until
        the longest is through; a query without a slot is sent once. A row
        drawn `by_size` once in a thousand is a program shape of its own
        (its sparse list is shorter), and a drawn warm-up seldom meets
        it.

        The slots step together: every value is walked once, not every
        combination. A program's shape also follows which of a query's
        operands are sparse, so a query with two slots whose fields both
        hold rows under the program's sparse threshold can still meet a
        new shape inside the window. No shipped query has two such slots
        (`taxi`: `passenger_count` alone holds sparse rows); the check on
        the chip is `window_compiles` 0, not this walk."""
        for i in range(len(self.queries)):
            slots: list = []

            def first(field, how):
                slots.append(field)
                return self._rows(field)[0][0]

            self._request(i, first)     # only to learn the query's slots
            longest = max((self._rows(f)[0].size for f in slots), default=1)
            for k in range(longest):
                def kth(field, how, k=k):
                    ids = self._rows(field)[0]
                    return ids[k % ids.size]

                yield self._request(i, kth)

    def warmup(self) -> list:
        """The walk, then `warmup_requests` of the drawn stream."""
        stream = self._stream(np.random.default_rng([self.seed, 0xF117, 2]))
        return list(self._walk()) + [
            next(stream) for _ in range(self.mix["warmup_requests"])]

    def take(self) -> dict:
        """The window's next request (any client thread may ask)."""
        with self._lock:
            return next(self._window)
