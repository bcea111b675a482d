"""Memory Compare / Compare Pattern kernels (paper Table 1, "Compare").

The grid walks the word grid in order and keeps one resident int32 block
of the lowest differing word index seen at each (row, lane) position of a
tile (``NO_DIFF`` where none).  The ops layer takes its minimum: the global
first-diff index, or NO_DIFF for equal buffers, which matches DSA's
completion-record semantics (status + first-diff offset).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.fill import pattern_spec, pattern_tile

LANES = 128
NO_DIFF = np.iinfo(np.int32).max


def fold_first_diff(first_ref, diff) -> None:
    """Lower ``first_ref`` (the block-shaped running minimum, resident
    across the sequential grid) with the word indices where ``diff``."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        first_ref[...] = jnp.full(first_ref.shape, NO_DIFF, jnp.int32)

    rows, lanes = diff.shape
    row = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    idx = (i * rows + row) * lanes + lane
    first_ref[...] = jnp.minimum(first_ref[...], jnp.where(diff, idx, NO_DIFF))


def first_diff_spec(block_rows: int) -> pl.BlockSpec:
    return pl.BlockSpec((block_rows, LANES), lambda i: (0, 0))


def _compare_kernel(a_ref, b_ref, first_ref):
    fold_first_diff(first_ref, a_ref[...] != b_ref[...])


def compare_words(
    a: jax.Array,  # [rows, 128] uint32
    b: jax.Array,
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Returns the [block_rows, 128] i32 running minimum of differing word
    indices (NO_DIFF where none)."""
    rows = a.shape[0]
    assert a.shape == b.shape and rows % block_rows == 0
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _compare_kernel,
        grid=(rows // block_rows,),
        in_specs=[spec, spec],
        out_specs=first_diff_spec(block_rows),
        out_shape=jax.ShapeDtypeStruct((block_rows, LANES), jnp.int32),
        interpret=interpret,
    )(a, b)


def _compare_pattern_kernel(a_ref, pat_ref, first_ref):
    a = jax.lax.bitcast_convert_type(a_ref[...], jnp.int32)
    fold_first_diff(first_ref, a != pattern_tile(pat_ref, a.shape))


def compare_pattern_words(
    a: jax.Array,  # [rows, 128] uint32
    pattern: jax.Array,  # [p] int32
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> jax.Array:
    rows = a.shape[0]
    p = pattern.shape[0]
    assert rows % block_rows == 0 and LANES % p == 0
    return pl.pallas_call(
        _compare_pattern_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)), pattern_spec()],
        out_specs=first_diff_spec(block_rows),
        out_shape=jax.ShapeDtypeStruct((block_rows, LANES), jnp.int32),
        interpret=interpret,
    )(a, pattern)
