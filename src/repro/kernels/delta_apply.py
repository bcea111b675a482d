"""Apply Delta Record kernel (paper Table 1, "Compare").

Scatters (offset, word) pairs into a copy of the reference buffer.  The grid
streams the reference block by block; the ops layer sorts the record by
offset and hands the kernel, via scalar prefetch (SMEM), the record slice
that lands in each block.  Each grid step copies its block and then walks
its slice serially, rewriting one lane per entry — delta records are small
by design (DSA caps them at 4KB), so the walk is latency- not
bandwidth-bound.  The ops layer also provides a vectorized jnp path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _delta_apply_kernel(bounds_ref, off_ref, data_ref, ref_ref, out_ref):
    i = pl.program_id(0)
    out_ref[...] = ref_ref[...]
    rows, lanes = out_ref.shape
    base = i * (rows * lanes)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def body(j, carry):
        local = off_ref[j] - base
        r = local // lanes
        row = out_ref[pl.ds(r, 1), :]
        out_ref[pl.ds(r, 1), :] = jnp.where(lane == local % lanes, data_ref[j], row)
        return carry

    jax.lax.fori_loop(bounds_ref[i], bounds_ref[i + 1], body, 0)


def delta_apply_words(
    ref: jax.Array,  # [rows, 128] int32
    bounds: jax.Array,  # [n_blocks + 1] i32: block b owns entries bounds[b]:bounds[b+1]
    offsets: jax.Array,  # [cap] i32, ascending
    data: jax.Array,  # [cap] i32
    *,
    block_rows: int,
    interpret: bool = False,
) -> jax.Array:
    n_blocks = ref.shape[0] // block_rows
    assert bounds.shape == (n_blocks + 1,)
    spec = pl.BlockSpec((block_rows, LANES), lambda i, b, o, d: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_blocks,),
        in_specs=[spec],
        out_specs=spec,
    )
    return pl.pallas_call(
        _delta_apply_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(ref.shape, ref.dtype),
        interpret=interpret,
    )(bounds, offsets, data, ref)
