"""Median over all bursts of the window, from due time to observed completion."""
from bench.readers import percentile


def read(run):
    p = percentile(run.latencies_s, 50)
    return None if p is None else 1e3 * p
