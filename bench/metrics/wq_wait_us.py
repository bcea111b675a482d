"""Queues and arbiter (core/queues.py, StreamEngine.kick): lifecycle accept -> dispatch, mean."""
from bench.readers import mark_gap_us


def read(run):
    return mark_gap_us(run, "accept", "dispatch")
