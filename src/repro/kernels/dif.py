"""Data Integrity Field (DIF) operations (paper Table 1, "Move").

DSA checks/inserts/strips an 8-byte DIF per 512/4096-byte block while moving
data.  TPU adaptation: blocks map to rows of a [n_blocks, block_words] word
grid; the per-block CRC reuses the CRC kernel with one block per lane (the
blocks are transposed so that each lane walks one block's words, all
blocks in one vector pass), and the tag framing is a pure reshape/concat.
Used for checkpoint-shard integrity framing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import crc32 as _crc
from repro.kernels import ops as _ops

LANES = 128


def _block_crcs(blocks: jax.Array, interpret: bool) -> jax.Array:
    """blocks [n_blocks, block_words] u32 -> per-block CRC32 [n_blocks] u32."""
    n, bw = blocks.shape
    sub = min(_crc.TILE_ROWS, -(-n // LANES))  # sublanes of blocks per group
    per = sub * LANES
    groups = -(-n // per)
    if groups * per > n:
        blocks = jnp.concatenate(
            [blocks, jnp.zeros((groups * per - n, bw), blocks.dtype)])
    lanes_of_blocks = _ops._i32(blocks).reshape(groups, sub, LANES, bw).transpose(0, 3, 1, 2)
    q = _crc.crc_streams(lanes_of_blocks, gap=1, interpret=interpret)
    # stream state -> register: the last word of a block is one word from its end
    regs = _crc.gf2_apply(jnp.asarray(_crc.advance_columns()[1]),
                          _ops._u32(q).reshape(-1)[:n])
    return regs ^ jnp.uint32(_crc.zeros_crc(4 * bw))


@functools.partial(jax.jit, static_argnames=("block_words", "ref_tag", "interpret"))
def dif_insert(words: jax.Array, *, block_words: int = 128, ref_tag: int = 0,
               interpret: Optional[bool] = None) -> jax.Array:
    """[n_blocks*block_words] u32 -> framed [n_blocks, block_words+2]."""
    interpret = _ops._interpret_default() if interpret is None else interpret
    blocks = words.reshape(-1, block_words)
    crcs = _block_crcs(blocks, interpret)
    n = blocks.shape[0]
    tags = (jnp.uint32(ref_tag) << 16) | (jnp.arange(n, dtype=jnp.uint32) & jnp.uint32(0xFFFF))
    return jnp.concatenate([blocks, crcs[:, None], tags[:, None]], axis=1)


@functools.partial(jax.jit, static_argnames=("block_words", "interpret"))
def dif_check(framed: jax.Array, *, block_words: int = 128,
              interpret: Optional[bool] = None) -> jax.Array:
    """framed [n_blocks, block_words+2] -> per-block ok mask [n_blocks]."""
    interpret = _ops._interpret_default() if interpret is None else interpret
    blocks = framed[:, :block_words]
    crcs = _block_crcs(blocks, interpret)
    return crcs == framed[:, block_words]


def dif_strip(framed: jax.Array, *, block_words: int = 128) -> jax.Array:
    return framed[:, :block_words].reshape(-1)


@functools.partial(jax.jit, static_argnames=("block_words", "ref_tag", "interpret"))
def dif_update(framed: jax.Array, *, block_words: int = 128, ref_tag: int = 0,
               interpret: Optional[bool] = None) -> jax.Array:
    """Recompute tags over (possibly modified) framed data."""
    return dif_insert(dif_strip(framed, block_words=block_words),
                      block_words=block_words, ref_tag=ref_tag, interpret=interpret)
