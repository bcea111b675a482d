"""Kernels: 2 x snapshot bytes moved at peak HBM bandwidth over the device time of every op of the copy_crc program."""
from bench.readers import roofline_pct


def read(run):
    return roofline_pct(run, "copy_crc")
