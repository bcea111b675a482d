"""KV pool (serving/kv_pool.py): bench span around each swap_out / swap_in minus its descriptors validate0 -> observed, mean per swap."""
import numpy as np


def read(run):
    own = []
    for name, t0, t1 in run.spans:
        if name not in ("swap_out", "swap_in"):
            continue
        inner = sum(t.marks["observed"] - t.marks["validate0"] for t in run.traces
                    if "observed" in t.marks and t0 <= t.marks.get("validate0", -1.0) <= t1)
        own.append(t1 - t0 - inner)
    return 1e6 * float(np.mean(own)) if own else None
