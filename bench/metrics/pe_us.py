"""PE dispatch (StreamEngine._launch, PE pool): lifecycle dispatch -> exec1, mean per descriptor."""
from bench.readers import mark_gap_us


def read(run):
    return mark_gap_us(run, "dispatch", "exec1")
