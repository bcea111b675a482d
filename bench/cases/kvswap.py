"""Checks of the KV swap cells: pages swapped as one ``ops.batch_copy`` a swap.

The control: pages are bfloat16 and are rounded to float8 e4m3, the KV
quantisation a later change could be tempted by.  The rounding is
``lax.reduce_precision``: the TPU compiler removes a bfloat16 -> float8 ->
bfloat16 convert pair as a no-op, so a cast round trip would not round.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import traffic
from bench.cases import Fault, altered_copy, half_copy, unchanged_copy
from bench.systems import kvswap as adapter
from repro.kernels import ops

GIB = 1 << 30
POOL_PAGES = 64  # pages of each pool at the test size


def small(cfg: Dict, mix: Dict) -> Tuple[Dict, Dict]:
    """2 layers of 1 KV head of head_dim 64 (4 KiB pages of block_size 16),
    ``POOL_PAGES``-page pools, at most 4 sessions of at most 160 tokens
    with at most 2 resident."""
    cfg = dict(cfg, num_hidden_layers=2, num_key_value_heads=1, head_dim=64)
    page = cfg["block_size"] * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    cfg.update(device_pool_gib=POOL_PAGES * page / GIB, swap_space_gib=POOL_PAGES * page / GIB)
    s = mix["sessions"]
    sessions = dict(s, count=min(s["count"], 4), resident=min(s["resident"], 2),
                    min_tokens=min(s["min_tokens"], 16), max_tokens=min(s["max_tokens"], 160))
    return cfg, dict(mix, sessions=sessions)


FAULTS = {
    "unchanged": Fault(ops={"batch_copy": unchanged_copy}),
    "altered": Fault(ops={"batch_copy": altered_copy}),
    "half": Fault(ops={"batch_copy": half_copy}),  # half the pages of each swap
}


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(1,))
def fp8_batch_copy(src_pool, dst_pool, src_idx, dst_idx, *, interpret=None):
    pages = jax.lax.reduce_precision(src_pool[src_idx], exponent_bits=4, mantissa_bits=3)
    return dst_pool.at[dst_idx].set(pages)


CONTROL = {"batch_copy": fp8_batch_copy}


def rehearse(cfg: Dict, mixes, report, shape) -> None:
    """The swap programs at the fewest and the most pages a swap of the
    cells moves, the pool fill of set-up and one block of the check.  The
    pools are sized from what this prints: the swap program's arguments
    plus its temporaries have to fit the chip's HBM beside what else the
    process holds."""
    page = (cfg["block_size"], 2 * cfg["num_key_value_heads"] * cfg["head_dim"])
    page_bytes = math.prod(page) * 2
    dev = int(cfg["device_pool_gib"] * GIB) // page_bytes
    host = int(cfg["swap_space_gib"] * GIB) // page_bytes
    counts = sorted({cfg["num_hidden_layers"] * -(-int(t) // cfg["block_size"])
                     for mix in mixes for t in traffic.session_tokens(mix, 0)})
    for n in (counts[0], counts[-1]):
        for name, (p, q) in (("swap_out", (dev, host)), ("swap_in", (host, dev))):
            report(f"batch_copy {name} bf16[{p}|{q},{page[0]},{page[1]}] x {n} pages",
                   ops.batch_copy, shape((p,) + page, jnp.bfloat16),
                   shape((q,) + page, jnp.bfloat16), shape((n,), jnp.int32),
                   shape((n,), jnp.int32), interpret=False)
    fill = jax.jit(adapter._fill, static_argnums=(3,))
    report(f"kv pool fill bf16[{dev},{page[0]},{page[1]}]", fill, shape((), jnp.uint32),
           shape((dev,), jnp.int32), shape((dev,), jnp.int32), page)
    report("kv check block", jax.jit(adapter._mismatched_pages),
           shape((dev,) + page, jnp.bfloat16), *[shape((adapter.CHECK_BLOCK,), jnp.int32)] * 3,
           shape((adapter.CHECK_BLOCK,), jnp.bool_), shape((), jnp.uint32))
