"""jit'd public wrappers over the Pallas streaming kernels.

Handles: arbitrary input shapes/dtypes (word view + padding), the interpret
decision (CPU host -> interpreted; TPU -> compiled), block/PE parameter
selection, and the jnp stages that pair with each kernel (delta compaction,
CRC stream fold, first-diff reduce).

Every function has a bit-exact oracle in ref.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (
    batch_copy as _bc,
    compare as _cmp,
    crc32 as _crc,
    delta_apply as _da,
    delta_create as _dc,
    dualcast as _dual,
    fill as _fill,
    fused as _fused,
    memcpy as _mc,
)

LANES = 128


def _interpret_default() -> bool:
    """Kernels compile for the TPU and run in the Pallas interpreter on the
    CPU backend (tests); any other backend has no kernel path."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"no Pallas kernel path for the {backend!r} backend "
                       "(TPU compiles, CPU interprets)")


# --------------------------------------------------------------------------- word view
# For buffers of up to a few MiB the TPU compiler spends minutes (80 s for
# 1 MiB) on the relayout that a narrowing bitcast ([n] u32 -> [n, 4] u8 ->
# [4n]) or a bitcast of a padded byte buffer leaves behind.  So only whole,
# unpadded items are bit-cast to words; narrower items are shifted out of
# repeated words (_split), and partial words are assembled with shifts.
_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _nbytes(x: jax.Array) -> int:
    return x.size * x.dtype.itemsize


def _bitcast_to_u32(x: jax.Array) -> jax.Array:
    """u32 words of a 4-byte-multiple array (little-endian byte view)."""
    itemsize = x.dtype.itemsize
    flat = x.reshape(-1, 4 // itemsize) if itemsize < 4 else x.reshape(-1)
    return jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)


def _split(x: jax.Array, dtype) -> jax.Array:
    """Flat little-endian view of ``x`` (itemsize <= 4) as the same or a
    narrower ``dtype``."""
    src, dst = x.dtype.itemsize, jnp.dtype(dtype).itemsize
    u = jax.lax.bitcast_convert_type(x.reshape(-1), _UINT[src])
    k = src // dst
    if k > 1:
        lane = jnp.arange(u.size * k, dtype=jnp.uint32) & (k - 1)
        u = (jnp.repeat(u, k) >> (lane * 8 * dst).astype(u.dtype)).astype(_UINT[dst])
    return jax.lax.bitcast_convert_type(u, dtype)


def _word(by: jax.Array, first: int = 0) -> jax.Array:
    """[1] u32 holding the bytes ``by`` from byte position ``first`` on."""
    w = jnp.uint32(0)
    for k in range(by.shape[0]):
        w = w | (by[k].astype(jnp.uint32) << (8 * (first + k)))
    return w[None]


def _pack_bytes(by: jax.Array, front: int = 0) -> jax.Array:
    """u32 words of ``front`` zero bytes followed by the u8 bytes ``by``,
    zero-padded to a whole word."""
    n, f = by.shape[0], front % 4
    head = min(n, -f % 4)  # bytes that complete a partial first word
    body = (n - head) // 4 * 4
    parts = [jnp.zeros((front // 4,), jnp.uint32)]
    if head:
        parts.append(_word(by[:head], f))
    parts.append(_bitcast_to_u32(by[head:head + body]))
    if head + body < n:
        parts.append(_word(by[head + body:]))
    return jnp.concatenate(parts)


def _flat_words(x: jax.Array) -> jax.Array:
    """u32 word view of any array; a byte length that is not a multiple of
    4 gets its last word zero-padded."""
    if _nbytes(x) % 4 == 0:
        return _bitcast_to_u32(x)
    return _pack_bytes(_split(x, jnp.uint8))


def _block_rows(n_words: int, n_pe: int = 1, target: int = 64) -> Tuple[int, int]:
    """(rows, block_rows) of the [rows, 128] grid holding ``n_words``: one
    whole-array block when it fits in ``target`` rows, else blocks of a
    multiple of 8 rows (the TPU tile) with ``n_pe * block_rows | rows``."""
    rows = -(-n_words // LANES)
    if n_pe == 1 and rows <= target:
        return rows, rows
    rows = -(-rows // (8 * n_pe)) * (8 * n_pe)
    br = max(b for b in range(8, target + 1, 8) if rows % (b * n_pe) == 0)
    return rows, br


def to_words(x: jax.Array, n_pe: int = 1, target: int = 64) -> Tuple[jax.Array, int]:
    """Bit-cast any array to a zero-padded [rows, 128] uint32 word grid;
    returns the grid and its block rows (see _block_rows)."""
    flat = _flat_words(x)
    rows, br = _block_rows(flat.shape[0], n_pe, target)
    pad = rows * LANES - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.uint32)])
    return flat.reshape(rows, LANES), br


def from_words(words: jax.Array, shape: tuple, dtype) -> jax.Array:
    """Inverse of to_words: the leading bytes of ``words`` as ``dtype``."""
    itemsize = jnp.dtype(dtype).itemsize
    n = math.prod(shape)
    flat = words.reshape(-1)[: -(-n * itemsize // 4)]
    if itemsize > 4:
        return jax.lax.bitcast_convert_type(flat.reshape(-1, itemsize // 4), dtype).reshape(shape)
    return _split(flat, dtype)[:n].reshape(shape)


def _i32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x.astype(jnp.uint32), jnp.int32)


def _u32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _first_diff(first_blk: jax.Array, n_words: int):
    """(equal?, first-diff word index | -1) from a kernel's running-minimum
    block; indices past ``n_words`` are padding."""
    first = jnp.min(first_blk)
    real = first < n_words
    return ~real, jnp.where(real, first, -1)


# --------------------------------------------------------------------------- ops
@functools.partial(jax.jit, static_argnames=("n_pe", "interpret"))
def memcpy(x: jax.Array, *, n_pe: int = 1, interpret: Optional[bool] = None) -> jax.Array:
    interpret = _interpret_default() if interpret is None else interpret
    w, br = to_words(x, n_pe=n_pe)
    out = _mc.memcpy_words(w, block_rows=br, n_pe=n_pe, interpret=interpret)
    return from_words(out, x.shape, x.dtype)


@functools.partial(jax.jit, static_argnames=("n_words", "n_pe", "interpret"))
def fill(
    pattern: jax.Array, n_words: int, *, n_pe: int = 1, interpret: Optional[bool] = None
) -> jax.Array:
    """Fill ``n_words`` uint32 words with a repeating 1/2/4-word pattern."""
    interpret = _interpret_default() if interpret is None else interpret
    rows, br = _block_rows(n_words, n_pe)
    out = _fill.fill_words(rows, _i32(pattern), block_rows=br, n_pe=n_pe,
                           interpret=interpret)
    return _u32(out).reshape(-1)[:n_words]


def fill_like(x: jax.Array, pattern_words=(0,), **kw) -> jax.Array:
    """Engine-backed buffer (re)initialization — e.g. grad-accumulator zeroing."""
    pat = jnp.asarray(pattern_words, jnp.uint32)
    words = fill(pat, -(-_nbytes(x) // 4), **kw)
    return from_words(words, x.shape, x.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def compare(a: jax.Array, b: jax.Array, *, interpret: Optional[bool] = None):
    """(equal?, first-diff word index | -1) — DSA completion-record style."""
    interpret = _interpret_default() if interpret is None else interpret
    wa, br = to_words(a)
    wb, _ = to_words(b)
    first = _cmp.compare_words(wa, wb, block_rows=br, interpret=interpret)
    return _first_diff(first, -(-_nbytes(a) // 4))


@functools.partial(jax.jit, static_argnames=("interpret",))
def compare_pattern(a: jax.Array, pattern: jax.Array, *, interpret: Optional[bool] = None):
    interpret = _interpret_default() if interpret is None else interpret
    wa, br = to_words(a)
    # padding words may mismatch the pattern: _first_diff drops them
    first = _cmp.compare_pattern_words(wa, _i32(pattern), block_rows=br,
                                       interpret=interpret)
    return _first_diff(first, -(-_nbytes(a) // 4))


@functools.partial(jax.jit, static_argnames=("interpret",))
def dualcast(x: jax.Array, *, interpret: Optional[bool] = None):
    interpret = _interpret_default() if interpret is None else interpret
    w, br = to_words(x)
    d1, d2 = _dual.dualcast_words(w, block_rows=br, interpret=interpret)
    return from_words(d1, x.shape, x.dtype), from_words(d2, x.shape, x.dtype)


# --------------------------------------------------------------------------- crc32
def _crc_tiles(x: jax.Array) -> Tuple[jax.Array, int]:
    """[1, T, 8, 128] int32 tiles of ``x``'s bytes, zero-padded at the
    FRONT to whole tiles (leading zeros leave a zero-init CRC register
    unchanged), and the front pad in bytes."""
    nbytes = _nbytes(x)
    tile_bytes = 4 * _crc.TILE_WORDS
    pad = (-nbytes) % tile_bytes
    if nbytes % 4 == 0:
        flat = jnp.concatenate([jnp.zeros((pad // 4,), jnp.uint32), _bitcast_to_u32(x)])
    else:
        flat = _pack_bytes(_split(x, jnp.uint8), front=pad)
    return _i32(flat).reshape(1, -1, _crc.TILE_ROWS, LANES), pad


def _crc_value(q: jax.Array, nbytes: int) -> jax.Array:
    return _crc.fold_streams(_u32(q[0])) ^ jnp.uint32(_crc.zeros_crc(nbytes))


@functools.partial(jax.jit, static_argnames=("interpret",))
def crc32(x: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
    """zlib-compatible CRC32 of the little-endian byte view (u32 scalar)."""
    interpret = _interpret_default() if interpret is None else interpret
    if x.size == 0:
        return jnp.uint32(0)
    tiles, _ = _crc_tiles(x)
    q = _crc.crc_streams(tiles, gap=_crc.TILE_WORDS, interpret=interpret)
    return _crc_value(q, _nbytes(x))


# --------------------------------------------------------------------------- fused pairs
@functools.partial(jax.jit, static_argnames=("interpret",))
def copy_crc(x: jax.Array, *, interpret: Optional[bool] = None):
    """Fused memcpy + CRC32 in ONE kernel launch: returns ``(copy, crc)``
    where ``copy`` is bit-identical to ``memcpy(x)`` and ``crc`` matches
    ``crc32(x)`` (zlib-compatible u32 scalar).  One read pass feeds both
    the write stream and the checksum — vs two launches and two read
    passes unfused."""
    interpret = _interpret_default() if interpret is None else interpret
    if x.size == 0:
        return x, jnp.uint32(0)
    tiles, pad = _crc_tiles(x)
    q, dst = _crc.crc_streams(tiles, gap=_crc.TILE_WORDS, copy=True,
                              interpret=interpret)
    flat = _u32(dst).reshape(-1)
    if pad % 4:  # byte-granular x (itemsize 1 or 2) after a partial word
        copy = _split(flat, x.dtype)[pad // x.dtype.itemsize:].reshape(x.shape)
    else:
        copy = from_words(flat[pad // 4:], x.shape, x.dtype)
    return copy, _crc_value(q, _nbytes(x))


@functools.partial(jax.jit, static_argnames=("n_words", "interpret"))
def fill_verify(pattern: jax.Array, n_words: int, *,
                interpret: Optional[bool] = None):
    """Fused fill + compare_pattern in ONE kernel launch: returns
    ``(filled, (ok, first_bad_idx))`` where ``filled`` is bit-identical to
    ``fill(pattern, n_words)`` and the verification pair matches
    ``compare_pattern(filled, pattern)`` — computed in-kernel from the
    just-written tile (the DSA fill-then-verify integrity idiom)."""
    interpret = _interpret_default() if interpret is None else interpret
    rows, br = _block_rows(n_words)
    dst, first = _fused.fill_verify_words(rows, _i32(pattern), block_rows=br,
                                          interpret=interpret)
    return _u32(dst).reshape(-1)[:n_words], _first_diff(first, n_words)


# --------------------------------------------------------------------------- delta records
@functools.partial(jax.jit, static_argnames=("cap", "interpret"))
def delta_create(src: jax.Array, ref: jax.Array, *, cap: int = 1024,
                 interpret: Optional[bool] = None):
    """Fixed-capacity delta record (offsets, data, count, overflow?)."""
    interpret = _interpret_default() if interpret is None else interpret
    ws, br = to_words(src)
    wr, _ = to_words(ref)
    # padding words are zero in both operands, so they never differ
    diff = _dc.delta_mask_words(ws, wr, block_rows=br, interpret=interpret).reshape(-1) != 0
    count = diff.sum().astype(jnp.int32)
    (idx,) = jnp.nonzero(diff, size=cap, fill_value=-1)
    data = jnp.where(idx >= 0, ws.reshape(-1)[jnp.clip(idx, 0)], 0).astype(jnp.uint32)
    return idx.astype(jnp.int32), data, count, count > cap


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def delta_apply(ref: jax.Array, offsets: jax.Array, data: jax.Array, *,
                interpret: Optional[bool] = None, use_kernel: bool = True) -> jax.Array:
    interpret = _interpret_default() if interpret is None else interpret
    wr, br = to_words(ref, target=512)
    if use_kernel:
        # sort the record by offset (stable: a later duplicate still wins)
        # and tell each grid block which slice of it lands there; -1
        # padding sorts past every block
        key = jnp.where(offsets >= 0, offsets, np.iinfo(np.int32).max).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)
        starts = jnp.arange(wr.shape[0] // br + 1, dtype=jnp.int32) * (br * LANES)
        bounds = jnp.searchsorted(key[order], starts).astype(jnp.int32)
        out = _u32(_da.delta_apply_words(_i32(wr), bounds, key[order], _i32(data[order]),
                                         block_rows=br, interpret=interpret))
    else:
        flat = wr.reshape(-1)
        valid = offsets >= 0
        safe = jnp.clip(offsets, 0)
        flat = flat.at[safe].set(jnp.where(valid, data, flat[safe]))
        out = flat.reshape(wr.shape)
    return from_words(out, ref.shape, ref.dtype)


# --------------------------------------------------------------------------- batch copy (paged)
def batch_copy_path(pool) -> str:
    """Which kernel ``batch_copy`` runs for ``pool`` ([n_pages, *page]).

    ``"dma"``: a page of 2 or more dims is whole HBM tiles, so it is sliced
    off the leading dim and moved HBM -> HBM in the pool's own layout.
    ``"vector"``: a 1-D page is one row of a tiled 2-D array, which a DMA
    cannot slice on its own, so pages go through the u32 word view."""
    return "dma" if len(pool.shape) >= 3 else "vector"


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(1,))
def batch_copy(src_pool: jax.Array, dst_pool: jax.Array, src_idx: jax.Array,
               dst_idx: jax.Array, *, interpret: Optional[bool] = None) -> jax.Array:
    """Batch-descriptor page copy: dst_pool[dst_idx[i]] = src_pool[src_idx[i]],
    a later descriptor winning where two write one page.

    Pools are [n_pages, ...page_shape...] of any dtype.  The kernel follows
    ``batch_copy_path``: page DMAs on the pools as they are, or pages
    bit-cast to word tiles."""
    interpret = _interpret_default() if interpret is None else interpret
    src_idx = src_idx.astype(jnp.int32)
    dst_idx = dst_idx.astype(jnp.int32)
    if batch_copy_path(src_pool) == "dma":
        return _bc.batch_copy_dma(src_pool, dst_pool, src_idx, dst_idx, interpret=interpret)
    P = src_pool.shape[0]
    Q = dst_pool.shape[0]
    page_shape = src_pool.shape[1:]
    page_words = -(-math.prod(page_shape) * src_pool.dtype.itemsize // 4)
    rows = -(-page_words // LANES)

    def pool_words(pool, k):
        flat = jax.vmap(_flat_words)(pool)
        pad = rows * LANES - page_words
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((k, pad), jnp.uint32)], axis=1)
        return flat.reshape(k, rows, LANES)

    out = _bc.batch_copy_pages(pool_words(src_pool, P), pool_words(dst_pool, Q),
                               src_idx, dst_idx, interpret=interpret)
    pages = out.reshape(Q, -1)[:, :page_words]
    return jax.vmap(lambda w: from_words(w, page_shape, dst_pool.dtype))(pages)
