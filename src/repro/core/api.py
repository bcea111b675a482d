"""Transparent offload (DTO analogue).

The paper ships two software layers above raw descriptors:
  * DML — explicit C/C++ API with async offload and load balancing;
  * DTO — LD_PRELOAD interception of memcpy/memset/memcmp.

The DML-style facade lives in core/device.py: ``Device`` owns N engine
instances behind a pluggable SubmitPolicy and returns ``Future`` objects
from every submit; completion waiting is core/completion.py.  This module
keeps ``dto`` — the drop-in layer: jnp-compatible copy/fill/compare
functions that route through the active Device when one is installed, else
fall back to plain jnp.

The deprecated ``Stream`` / ``make_stream`` shims were REMOVED (they
lasted the promised one release): port to ``make_device`` and Futures —
see docs/api.md, "Migration: Stream -> Device".
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.device import Device, make_device

_REMOVED_SHIMS = ("Stream", "make_stream")


def __getattr__(name: str):
    if name in _REMOVED_SHIMS:
        raise AttributeError(
            f"repro.core.api.{name} was removed: the deprecated Stream shim "
            "API is gone. Use repro.core.make_device / Device — submissions "
            "return Future objects. Migration guide: docs/api.md, "
            "'Migration: Stream -> Device'."
        )
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# --------------------------------------------------------------------------- DTO
_active: threading.local = threading.local()


@contextlib.contextmanager
def dto_enabled(device: Optional[Device] = None, min_bytes: int = 8192):
    """Transparent offload: inside this context, dto.memcpy/memset/memcmp
    route through the engine for transfers >= min_bytes (the paper's
    CacheLib study offloads >= 8KB — 4.8% of calls, 96.4% of bytes)."""
    prev = getattr(_active, "ctx", None)
    _active.ctx = (device or make_device(), min_bytes)
    try:
        yield _active.ctx[0]
    finally:
        _active.ctx = prev


class dto:
    """memcpy/memset/memcmp interposers (synchronous, like the DTO library)."""

    @staticmethod
    def memcpy(src: jax.Array) -> jax.Array:
        ctx = getattr(_active, "ctx", None)
        if ctx and src.size * src.dtype.itemsize >= ctx[1]:
            return ctx[0].memcpy(src)
        return jnp.array(src)

    @staticmethod
    def memset(x: jax.Array, byte: int = 0) -> jax.Array:
        ctx = getattr(_active, "ctx", None)
        nbytes = x.size * x.dtype.itemsize
        if ctx and nbytes >= ctx[1]:
            word = int.from_bytes(bytes([byte]) * 4, "little")
            d = ctx[0]
            out = d.wait(d.fill_async(jnp.asarray([word], jnp.uint32), nbytes // 4))
            from repro.kernels.ops import from_words

            return from_words(out, x.shape, x.dtype)
        return jnp.full_like(x, 0 if byte == 0 else byte)

    @staticmethod
    def memcmp(a: jax.Array, b: jax.Array) -> bool:
        ctx = getattr(_active, "ctx", None)
        if ctx and a.size * a.dtype.itemsize >= ctx[1]:
            eq, _ = ctx[0].compare(a, b)
            return bool(eq)
        return bool(jnp.array_equal(a, b))
