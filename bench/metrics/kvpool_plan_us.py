"""KV pool (serving/kv_pool.py): free-list pops and index uploads before a swap's copy, the program's kvpool.plan span, mean per swap."""
from bench.runtime_spans import mean_us


def read(run):
    return mean_us(run, "kvpool.plan")
