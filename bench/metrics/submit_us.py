"""Submission (core/device.py): lifecycle validate0 -> accept, mean per submitted descriptor."""
from bench.readers import mark_gap_us


def read(run):
    return mark_gap_us(run, "validate0", "accept")
