"""`leaf_resolve_ms_per_query` in a cell that reports no `query_p95_ms`:
the same reading, set against `query_p50_ms`."""

from lib import byfile

read = byfile.load("layer_metrics", "leaf_resolve_ms_per_query").read
