"""99th percentile of submission time minus due time: how late the load generator ran."""
from bench.readers import percentile


def read(run):
    p = percentile(run.gen_late_s, 99)
    return None if p is None else 1e3 * p
