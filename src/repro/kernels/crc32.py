"""CRC Generation kernel (paper Table 1, "Move"/CRC32), TPU-adapted.

CRC is bit-serial by definition; the DSA computes it in streaming hardware.
The TPU adaptation rests on CRC's GF(2) linearity.  With a zero initial
register, each word's contribution to the final register depends only on
the word and on how many words follow it, and contributions XOR together:

    reg = XOR_p A_{N-p}(w_p)        A_m = "feed m zero words" (32x32 GF(2))

The kernel reads the buffer in its natural [rows, 128] word grid, one
(8, 128) tile at a time.  Each of the 1024 tile positions is a stream whose
consecutive words lie one tile apart, so every stream folds Horner-style
with one constant operator, vectorised across the whole tile:

    Q <- A_1024(Q) ^ tile

A_1024 is applied by masking its 32 columns with the bits of Q (shift, and,
xor on the VPU: no table gathers).  ``fold_streams`` then combines the 1024
stream states with A_{1024-k} for tile position k, and XORing in the CRC of
as many zero bytes (``zeros_crc``) supplies zlib's 0xFFFFFFFF init and final
xor.  Buffers are padded with zero words at the FRONT: leading zeros leave a
zero register untouched, so every byte length works without a tail pass.

DIF reuses the kernel with the operator A_1: the ops layer transposes its
blocks so that each lane holds one block's words (``crc_streams(gap=1)``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

POLY = 0xEDB88320  # reflected IEEE polynomial (zlib)
TILE_ROWS = 8
LANES = 128
TILE_WORDS = TILE_ROWS * LANES


@functools.lru_cache(maxsize=None)
def advance_columns() -> np.ndarray:
    """[TILE_WORDS + 1, 32] u32: row m holds the 32 columns of A_m, i.e.
    column b is the register ``1 << b`` after m zero words."""
    cols = np.zeros((TILE_WORDS + 1, 32), np.uint32)
    v = np.left_shift(np.uint32(1), np.arange(32, dtype=np.uint32))
    cols[0] = v
    for m in range(1, TILE_WORDS + 1):
        for _ in range(32):  # one zero bit per step
            v = (v >> np.uint32(1)) ^ (np.uint32(POLY) * (v & np.uint32(1)))
        cols[m] = v
    return cols


def zeros_crc(nbytes: int) -> int:
    """zlib.crc32 of ``nbytes`` zero bytes (computed by GF(2) squaring)."""
    mat = _ref.crc32_shift_matrix(nbytes).astype(np.uint64)
    return _ref._gf2_matrix_times(mat, 0xFFFFFFFF) ^ 0xFFFFFFFF


def gf2_apply(cols: jax.Array, v: jax.Array) -> jax.Array:
    """Apply a GF(2) operator given as [..., 32] u32 columns to u32 ``v``
    (broadcast over the leading axes); XOR-reduces the masked columns."""
    bits = (v[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    return jax.lax.reduce(jnp.where(bits != 0, cols, jnp.uint32(0)),
                          jnp.uint32(0), jax.lax.bitwise_xor, (v.ndim,))


def fold_streams(q: jax.Array) -> jax.Array:
    """Register of a whole buffer from its 1024 stream states ([8, 128]):
    stream k's last word sits 1024-k words from the end of the buffer."""
    fold = jnp.asarray(advance_columns()[TILE_WORDS:0:-1])  # row k = A_{1024-k}
    regs = gf2_apply(fold, q.reshape(-1).astype(jnp.uint32))
    return jax.lax.reduce(regs, jnp.uint32(0), jax.lax.bitwise_xor, (0,))


def _signed(c: int) -> int:
    return c - (1 << 32) if c >= 1 << 31 else c


def _apply_cols(q: jax.Array, cols) -> jax.Array:
    """In-kernel A(q) for int32 ``q``: XOR of the columns whose bit is set.
    ``(q << (31 - b)) >> 31`` is all ones exactly where bit b of q is set."""
    acc = jnp.zeros_like(q)
    for b, col in enumerate(cols):
        if col:
            acc = acc ^ (((q << (31 - b)) >> 31) & _signed(col))
    return acc


def _horner_kernel(data_ref, *refs, cols, tiles, copy):
    """Grid (group, step): fold ``tiles`` tiles of this group's streams into
    the resident state block; ``copy`` also streams the tiles to a second
    output (the fused copy+CRC)."""
    q_ref = refs[0]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        q_ref[...] = jnp.zeros(q_ref.shape, jnp.int32)

    if copy:
        refs[1][...] = data_ref[...]

    def body(t, q):
        return _apply_cols(q, cols) ^ data_ref[0, t]

    q_ref[0] = jax.lax.fori_loop(0, tiles, body, q_ref[0])


def crc_streams(data: jax.Array, *, gap: int, copy: bool = False,
                tiles_per_step: int = 64, interpret: bool = False):
    """Horner-fold ``data`` [G, T, S, L] int32 along T with operator A_gap.

    Returns the zero-init stream states [G, S, L] int32 (and, with
    ``copy``, a bit-identical copy of ``data``).  Stream (s, l) of group g
    sees the words data[g, :, s, l] in order; ``gap`` is how many buffer
    words separate two of them (1024 for a buffer's own tiles, 1 for
    transposed DIF blocks)."""
    G, T, S, L = data.shape
    tb = max(d for d in range(1, min(tiles_per_step, T) + 1) if T % d == 0)
    cols = tuple(int(c) for c in advance_columns()[gap])
    kernel = functools.partial(_horner_kernel, cols=cols, tiles=tb, copy=copy)
    tiles_spec = pl.BlockSpec((1, tb, S, L), lambda g, j: (g, j, 0, 0))
    state_spec = pl.BlockSpec((1, S, L), lambda g, j: (g, 0, 0))
    state_shape = jax.ShapeDtypeStruct((G, S, L), jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid=(G, T // tb),
        in_specs=[tiles_spec],
        out_specs=[state_spec, tiles_spec] if copy else state_spec,
        out_shape=[state_shape, jax.ShapeDtypeStruct(data.shape, data.dtype)]
        if copy else state_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(data)
    return tuple(out) if copy else out
