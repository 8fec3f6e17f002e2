"""The server's span table between the two ends of the window: the one
reader the span metrics of layer_metrics/ share.

/debug/vars `spans` is `{"nowMs": <the server's perf_counter, ms>,
"byName": {<span>: {"n", "wallMs", "selfMs", "cpuMs", "buckets"}}}`,
summed since the server started (pilosa_tpu/utils/tracing.py SpanStats). A
span's selfMs is its duration less what its child spans cover, so the
selfMs of all names partition the wall of the roots, and a layer's share
of a request is the selfMs of its spans. `http.request` is the root of one
served work request (a query, in a window that sends nothing else); status
and debug routes are `http.other`.

A program without the table (the parent of the PR that brought it) gives
every reader None: the line leaves the metric out.
"""

from __future__ import annotations

FIELDS = ("n", "wallMs", "selfMs", "cpuMs")
ROOT = "http.request"


def delta(ctx: dict) -> dict | None:
    """{span name: {n, wallMs, selfMs, cpuMs}} over the window, with the
    window's own length on the server's clock under "nowMs"; None where
    either end has no table."""
    a = (ctx.get("vars_before") or {}).get("spans")
    b = (ctx.get("vars_after") or {}).get("spans")
    if not a or not b:
        return None
    before = a.get("byName", {})
    out = {name: {f: e.get(f, 0) - before.get(name, {}).get(f, 0)
                  for f in FIELDS}
           for name, e in b.get("byName", {}).items()}
    out["nowMs"] = b.get("nowMs", 0.0) - a.get("nowMs", 0.0)
    return out


def names_of(d: dict, wanted: tuple) -> list:
    """The table's names among `wanted`; a wanted name that ends in "*"
    stands for every name with that prefix."""
    return [n for n in d if n != "nowMs" and any(
        n == w or (w.endswith("*") and n.startswith(w[:-1]))
        for w in wanted)]


def per_query(ctx: dict, field: str, wanted: tuple):
    """Summed `field` of the wanted spans over the window, a request:
    divided by the `http.request` spans that finished in it. None where
    no request finished or none of the spans did."""
    d = delta(ctx)
    if d is None:
        return None
    requests = d.get(ROOT, {}).get("n", 0)
    names = names_of(d, wanted)
    if requests <= 0 or not any(d[n]["n"] > 0 for n in names):
        return None
    return sum(d[n][field] for n in names) / requests
