"""Python runtime: time in garbage collections during the window, the sum of the program's gc.gen<N> spans."""
from bench.runtime_spans import in_window


def read(run):
    spans = in_window(run, "gc.")
    return None if spans is None else 1e3 * sum(sp.t1 - sp.t0 for sp in spans)
