"""Device: share of the traced window with no operation running on the chip."""
from bench.readers import idle_pct


def read(run):
    return idle_pct(run)
