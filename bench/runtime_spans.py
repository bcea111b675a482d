"""The program's own host spans (``repro.obs`` ``HostSpan``s: ``gc.*``,
``pe.*``, ``kvpool.*``), reached through the tracer behind the window's
lifecycle traces.  Each function returns None where the program records
no such spans, so a reader of them reports nothing there."""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np


def tracer(run):
    """The tracer behind ``run.traces``, where it keeps host spans."""
    for t in run.traces:
        tr = getattr(t, "tracer", None)
        if tr is not None and hasattr(tr, "host_spans"):
            return tr
    return None


def window(run) -> Optional[Tuple[float, float]]:
    """The window on the host clock: the extent of the bench's spans."""
    if not run.spans:
        return None
    return min(s[1] for s in run.spans), max(s[2] for s in run.spans)


def in_window(run, prefix: str) -> Optional[List]:
    """Host spans named ``prefix...`` that start inside the window."""
    tr, w = tracer(run), window(run)
    if tr is None or w is None:
        return None
    return [sp for sp in tr.host_spans(prefix) if w[0] <= sp.t0 <= w[1]]


def mean_us(run, prefix: str) -> Optional[float]:
    """Mean length of the window's ``prefix...`` spans, in microseconds."""
    spans = in_window(run, prefix)
    if not spans:
        return None
    return 1e6 * float(np.mean([sp.t1 - sp.t0 for sp in spans]))
