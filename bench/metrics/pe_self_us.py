"""PE dispatch: a PE slot's exec0 -> exec1 minus its pe.kernel:<op> children, the glue around the kernel calls, mean per descriptor."""
from collections import defaultdict

import numpy as np

from bench.runtime_spans import in_window


def read(run):
    kernels = in_window(run, "pe.kernel:")
    if not kernels:
        return None
    inner = defaultdict(float)
    for sp in kernels:
        inner[sp.desc_id] += sp.t1 - sp.t0
    own = [t.marks["exec1"] - t.marks["exec0"] - inner[t.desc_id] for t in run.traces
           if "exec0" in t.marks and "exec1" in t.marks]
    return 1e6 * float(np.mean(own)) if own else None
