"""Checkpoint manager (checkpoint/manager.py): the wait for every completion of a save and the CRCs' one transfer, the program's ckpt.gather span, mean per save."""
from bench.runtime_spans import mean_us


def read(run):
    return mean_us(run, "ckpt.gather")
