"""PE dispatch: one call into a kernel wrapper on the PE worker, the program's pe.kernel:<op> span, mean."""
from bench.runtime_spans import mean_us


def read(run):
    return mean_us(run, "pe.kernel:")
