"""KV pool (serving/kv_pool.py): page-table and free-list update after a swap's copy, the program's kvpool.commit span, mean per swap."""
from bench.runtime_spans import mean_us


def read(run):
    return mean_us(run, "kvpool.commit")
