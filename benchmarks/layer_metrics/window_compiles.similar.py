"""`window_compiles` in `chem-similarity.similar`, which reports no
`query_p95_ms`: the same reading, set against `query_p50_ms`."""

from lib import byfile

read = byfile.load("layer_metrics", "window_compiles").read
