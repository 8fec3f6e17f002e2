"""Host milliseconds a request spends in the HTTP layer and the parser: the
self time of the spans `http.request` (what no stage below it owns),
`http.read`, `http.admit`, `pql.parse`, `http.encode` and `http.write`
(/debug/vars `spans`, lib/spans.py), over the window's requests."""

from lib import spans

SPANS = ("http.request", "http.read", "http.admit", "pql.parse",
         "http.encode", "http.write")


def read(ctx):
    return spans.per_query(ctx, "selfMs", SPANS)
