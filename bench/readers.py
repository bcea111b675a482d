"""Reductions that several metric readers share.  A reader returns None
where its run has nothing to read, and the metric is then left out."""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile of all ``values`` (linear interpolation
    between order statistics), or None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def rate(n: float, seconds: float) -> Optional[float]:
    """``n`` per second over the whole window."""
    return n / seconds if seconds > 0 else None


def mark_gap_us(run, m0: str, m1: str) -> Optional[float]:
    """Mean microseconds from lifecycle mark ``m0`` to ``m1`` over the
    program's traces of the window that carry both."""
    gaps = [t.marks[m1] - t.marks[m0] for t in run.traces
            if m0 in t.marks and m1 in t.marks]
    return 1e6 * float(np.mean(gaps)) if gaps else None


def idle_pct(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device."""
    if run.device is None or run.device["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.device["busy_s"] / run.device["window_s"])


def roofline_pct(run, program: str) -> Optional[float]:
    """Least time the chip's HBM needs for the kernel's bytes, over the
    device time of its whole jitted program in the traced window."""
    seconds = (run.device or {}).get("programs", {}).get(program)
    nbytes = run.kernel_bytes.get(program)
    if not seconds or not nbytes or "hbm_bytes_per_s" not in run.peaks:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / seconds
