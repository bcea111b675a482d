"""Useful bytes completed over the whole window (payload only), in GB/s."""
from bench.readers import rate


def read(run):
    r = rate(run.bytes_done, run.window_s)
    return None if r is None else r / 1e9
