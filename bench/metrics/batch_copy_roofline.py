"""Kernels: 2 x page bytes moved at peak HBM bandwidth over the device time of every op of the batch_copy program."""
from bench.readers import roofline_pct


def read(run):
    return roofline_pct(run, "batch_copy")
