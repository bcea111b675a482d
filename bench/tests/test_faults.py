"""``correct`` comes out false when the timed path is broken underneath.

Each case drives a whole run on the CPU at a size a test run can hold
(the chip check is skipped; everything else is as in the cell) with one
fault planted in the program's path: a copy that leaves its destination
unchanged, a batch of which half is left out, an answer altered where it
is produced, bursts refused at admission, and the control of ``bench/control.py`` (the reference one
precision step down in the program's place).  A sound run of each cell
must come out correct.  One chip, so no exchange between chips to drop.
"""
import itertools

import jax
import jax.numpy as jnp
import pytest

from bench import control, run, spec, traffic
from repro.core import QueueFull
from repro.core.descriptor import BatchDescriptor
from repro.core.device import Device
from repro.core.engine import StreamEngine
from repro.kernels import ops

BM = spec.load_benchmark()
REAL_COPY = ops.batch_copy
REAL_MEMCPY = ops.memcpy
REAL_BATCH = StreamEngine._execute_batch
REAL_BATCH_ASYNC = Device.batch_async
PAGE = 16 * 128 * 2  # bytes of a page of the small KV geometry


def small(cell):
    cfg, mix = None, None
    if cell == "kvswap-dsmoe16b.long":
        cfg = spec.config(BM, "kv-offload-dsmoe16b")
        cfg.update(num_hidden_layers=2, num_key_value_heads=1, head_dim=64,
                   device_pool_gib=64 * PAGE / 2**30, swap_space_gib=64 * PAGE / 2**30)
        mix = traffic.load("sessions-long")
        mix["sessions"].update(count=4, resident=2, min_tokens=16, max_tokens=160)
    elif cell == "vhost-imix.steady":
        mix = traffic.load("imix-steady")
        mix["arrivals"]["rate_per_s"] = 40
    return cfg, mix


def _bump_first(x):
    """``x`` with one added to the bits of the first element of each row
    (an XOR would cancel out on a page that moves twice)."""
    u = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    bits = jax.lax.bitcast_convert_type(x, u)
    flat = bits.reshape(bits.shape[0], -1) if bits.ndim > 1 else bits[None]
    flat = flat.at[:, 0].set(flat[:, 0] + 1)
    return jax.lax.bitcast_convert_type(flat.reshape(bits.shape), x.dtype)


def unchanged_copy(src, dst, si, di, *, interpret=None):
    return dst


def half_copy(src, dst, si, di, *, interpret=None):
    h = si.shape[0] // 2
    return REAL_COPY(src, dst, si[:h], di[:h], interpret=interpret)


def altered_copy(src, dst, si, di, *, interpret=None):
    out = REAL_COPY(src, dst, si, di, interpret=interpret)
    return out.at[di].set(_bump_first(out[di]))


def unchanged_memcpy(x, *, interpret=None, n_pe=1):
    return jnp.zeros_like(x)


def altered_memcpy(x, *, interpret=None, n_pe=1):
    return _bump_first(REAL_MEMCPY(x, interpret=interpret))


def half_batch(self, b, dst_tier="hbm"):
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


FAULTS = {
    "unchanged": {"batch_copy": unchanged_copy, "memcpy": unchanged_memcpy},
    "altered": {"batch_copy": altered_copy, "memcpy": altered_memcpy},
}
CELLS = [w["name"] for w in BM["workloads"]]
VHOST = [c for c in CELLS if c.startswith("vhost")]


def _run(cell, seed=2**31 + 5):
    cfg, mix = small(cell)
    result, checks = run.run_cell(cell, seed, 0.4, False, cfg=cfg, mix=mix, require_tpu=False)
    assert result["attempted"] > 0
    return result, checks


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result, checks = _run(cell)
    assert result["correct"], checks
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    system = spec.config(BM, spec.cell(BM, cell)["config"])["system"]
    with control.replaced(control.CONTROLS[system]):
        result, checks = _run(cell)
    assert not result["correct"], checks


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault):
    with control.replaced(FAULTS[fault]):
        result, checks = _run(cell)
    assert not result["correct"], checks


@pytest.mark.parametrize("cell", VHOST + ["kvswap-dsmoe16b.long"])
def test_half_of_each_batch_left_out_is_not_correct(cell, monkeypatch):
    if cell in VHOST:  # half the packets of each burst
        monkeypatch.setattr(StreamEngine, "_execute_batch", half_batch)
        result, checks = _run(cell)
    else:  # half the pages of each swap
        with control.replaced({"batch_copy": half_copy}):
            result, checks = _run(cell)
    assert not result["correct"], checks


@pytest.mark.parametrize("cell", VHOST)
def test_refused_bursts_are_not_correct(cell, monkeypatch):
    monkeypatch.setattr(Device, "batch_async", refusing_batch_async())
    result, checks = _run(cell)
    assert result["failed"] > 0, result
    assert checks["refused_pct"][0] > checks["refused_pct"][1], checks
    assert not result["correct"], checks
