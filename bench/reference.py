"""Plain references for ``correct``: the contents every packet and every KV
page must hold, regenerated from the seed.  Nothing here imports the
program.

Contents are a counter-based hash of (seed, stream ids, position), so any
one packet or page can be regenerated alone, in NumPy on the host or in
``jax.numpy`` on the device, with the same bits.  KV pages are bfloat16
values with a bounded exponent: finite and normal, so no NaN payload can
be rewritten by a copy.
"""
from __future__ import annotations

import numpy as np


def seed_word(seed: int) -> int:
    """32 bits of a seed of any size."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1, np.uint32)[0])


def _mix(x, xp):
    """lowbias32 finaliser on uint32 arrays (wraps mod 2**32)."""
    x = x ^ (x >> 16)
    x = x * xp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * xp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hash4(sw, a, b, pos, xp=np):
    """uint32 hash of (seed word, a, b, pos); ``a``/``b`` broadcast against
    ``pos``."""
    u = xp.uint32
    with np.errstate(over="ignore"):  # uint32 products wrap by design
        x = _mix(xp.asarray(a).astype(u) ^ u(sw), xp)
        x = _mix(x ^ xp.asarray(b).astype(u), xp)
        return _mix(x + xp.asarray(pos).astype(u), xp)


def packet_bytes(sw: int, size: int, slot, xp=np):
    """The source packet ``slot`` (scalar or [n]) of ``size`` bytes: uint8
    of shape [size] or [n, size]."""
    slot = xp.asarray(slot)
    pos = xp.arange(size, dtype=xp.uint32)
    h = hash4(sw, size, slot[..., None], pos, xp)
    return (h & xp.uint32(0xFF)).astype(xp.uint8)


def page_bits(sw: int, session, page_no, page_elems: int, xp=np):
    """uint16 bfloat16 bit patterns of KV pages: ``session`` and ``page_no``
    are [n] arrays, the result is [n, page_elems].  Sign, a 4-bit exponent
    around 1.0 and a 7-bit mantissa: finite normal values only."""
    pos = xp.arange(page_elems, dtype=xp.uint32)
    h = hash4(sw ^ 0x5BD1E995, xp.asarray(session)[:, None],
              xp.asarray(page_no)[:, None], pos, xp)
    u = xp.uint32
    bits = (((h >> 15) & u(1)) << 15) | ((u(0x78) + ((h >> 7) & u(0xF))) << 7) | (h & u(0x7F))
    return bits.astype(xp.uint16)
