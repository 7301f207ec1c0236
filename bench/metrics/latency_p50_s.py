"""Median of due time to completion over the requests due in the
window; a failed request counts as infinite."""
from bench.readers import latencies, percentile


def read(run):
    return percentile(latencies(run), 50)
