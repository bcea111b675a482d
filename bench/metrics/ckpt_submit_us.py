"""Checkpoint manager (checkpoint/manager.py): descriptors of every leaf of a save made and submitted, the program's ckpt.submit span, mean per save."""
from bench.runtime_spans import mean_us


def read(run):
    return mean_us(run, "ckpt.submit")
