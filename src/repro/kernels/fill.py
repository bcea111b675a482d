"""Memory Fill kernel (paper Table 1, "Fill").

Fills a word buffer with a repeating 2- or 4-word pattern (the paper's
8/16-byte patterns).  ``nt=True`` models the non-allocating variant
(cache-control flag G3): on real TPU the difference is the destination
memory-space hint; the data path is identical.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def pattern_spec() -> pl.BlockSpec:
    """The [p] int32 pattern words live whole in SMEM (scalar reads)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def pattern_tile(pat_ref, shape) -> jax.Array:
    """The pattern laid over a (rows, 128) int32 tile without a gather.
    p divides the lane width, so lane l of every row holds pattern word
    l % p whatever the tile's offset; a select chain picks it."""
    p = pat_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1) & (p - 1)
    tile = jnp.full(shape, pat_ref[0], jnp.int32)
    for j in range(1, p):
        tile = jnp.where(lane == j, pat_ref[j], tile)
    return tile


def _fill_kernel(pat_ref, dst_ref):
    dst_ref[...] = pattern_tile(pat_ref, dst_ref.shape)


def fill_words(
    rows: int,
    pattern: jax.Array,  # [p] int32, p divides 128
    *,
    block_rows: int = 8,
    n_pe: int = 1,
    interpret: bool = False,
) -> jax.Array:
    """Returns the filled [rows, 128] int32 word grid."""
    assert rows % (block_rows * n_pe) == 0
    p = pattern.shape[0]
    assert LANES % p == 0, "pattern must divide the lane width"
    blocks_per_pe = rows // block_rows // n_pe
    return pl.pallas_call(
        _fill_kernel,
        grid=(n_pe, blocks_per_pe),
        in_specs=[pattern_spec()],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda pe, j, bpp=blocks_per_pe: (pe * bpp + j, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
        interpret=interpret,
    )(pattern)
