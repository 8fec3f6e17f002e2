"""`recount_roofline` in `chem-similarity.similar`: the same reading,
the one pairs entry of 500,000 rows recounted once a request."""

from lib import byfile

read = byfile.load("layer_metrics", "recount_roofline").read
