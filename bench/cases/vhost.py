"""Checks of the vhost cells: packets copied in bursts of ``Device.batch_async``.

A burst of mixed sizes runs one ``ops.memcpy`` a packet; a burst of one
size fuses into one ``ops.batch_copy`` of a 2-D pool.  So each kernel
fault replaces both.

The control: the configuration states no floating precision but a
bit-exact copy, so the step down is the contract's ladder applied to the
data: packets are bytes (8 bits) and go through 4 bits, keeping each
byte's high nibble.
"""
from __future__ import annotations

import functools
import itertools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.cases import Fault, altered_copy, bump_first, unchanged_copy
from bench.systems.vhost import REFUSED_LIMIT_PCT
from repro.core import QueueFull
from repro.core.descriptor import BatchDescriptor
from repro.core.device import Device
from repro.core.engine import StreamEngine
from repro.kernels import ops

RATE_PER_S = 40  # open-loop bursts a second a CPU test run keeps up with
REAL_MEMCPY = ops.memcpy
REAL_BATCH = StreamEngine._execute_batch
REAL_BATCH_ASYNC = Device.batch_async


def small(cfg: Dict, mix: Dict) -> Tuple[Dict, Dict]:
    """The cell's own sizes; an open loop at no more than ``RATE_PER_S``."""
    if mix["loop"] == "open":
        arrivals = dict(mix["arrivals"], rate_per_s=min(mix["arrivals"]["rate_per_s"], RATE_PER_S))
        mix = dict(mix, arrivals=arrivals)
    return cfg, mix


def unchanged_memcpy(x, *, interpret=None, n_pe=1):
    return jnp.zeros_like(x)


def altered_memcpy(x, *, interpret=None, n_pe=1):
    return bump_first(REAL_MEMCPY(x, interpret=interpret))


def half_batch(self, b, dst_tier="hbm"):
    """Half the packets of each burst."""
    kept = list(b.descriptors)[: len(b.descriptors) // 2]
    return REAL_BATCH(self, BatchDescriptor(descriptors=kept), dst_tier=dst_tier)


def refusing_batch_async():
    """``Device.batch_async`` that refuses every other burst with QueueFull."""
    n = itertools.count()

    def batch_async(self, descriptors, **kw):
        if next(n) % 2:
            raise QueueFull("planted", 0)
        return REAL_BATCH_ASYNC(self, descriptors, **kw)

    return batch_async


def _refused_shows(result, checks):
    assert result["failed"] > 0, result
    assert checks["refused_pct"][0] > checks["refused_pct"][1], checks


FAULTS = {
    "unchanged": Fault(ops={"batch_copy": unchanged_copy, "memcpy": unchanged_memcpy}),
    "altered": Fault(ops={"batch_copy": altered_copy, "memcpy": altered_memcpy}),
    "half": Fault(patch=lambda mp: mp.setattr(StreamEngine, "_execute_batch", half_batch)),
    "refused": Fault(patch=lambda mp: mp.setattr(Device, "batch_async", refusing_batch_async()),
                     check=_refused_shows),
}


@functools.partial(jax.jit, static_argnames=("interpret", "n_pe"))
def int4_memcpy(x, *, interpret=None, n_pe=1):
    return (x >> 4) << 4


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(1,))
def int4_batch_copy(src_pool, dst_pool, src_idx, dst_idx, *, interpret=None):
    return dst_pool.at[dst_idx].set((src_pool[src_idx] >> 4) << 4)


CONTROL = {"memcpy": int4_memcpy, "batch_copy": int4_batch_copy}


def rehearse(cfg: Dict, mixes, report, shape) -> None:
    for mix in mixes:
        burst, sizes = int(mix["burst"]), mix["sizes"]["values"]
        if burst > 1 and len(sizes) == 1:
            size = int(sizes[0])
            pool, idx = shape((burst, size), jnp.uint8), shape((burst,), jnp.int32)
            report(f"batch_copy u8[{burst},{size}] (fused {size} B burst)", ops.batch_copy,
                   pool, pool, idx, idx, interpret=False)
        else:
            for size in sizes:
                report(f"memcpy u8[{size}]", ops.memcpy, shape((size,), jnp.uint8), interpret=False)
