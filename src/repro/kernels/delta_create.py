"""Create Delta Record kernel (paper Table 1, "Compare").

The DSA emits (offset, 8-byte data) pairs for differing granules.  TPU
adaptation: the kernel computes the vectorized word-granule diff mask (the
streaming part); the ops layer compacts the mask into the fixed-capacity
record with ``jnp.nonzero(size=cap)`` — the record capacity mirrors DSA's
max delta record size, with the same overflow status semantics in the
completion record.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _delta_mask_kernel(src_ref, ref_ref, mask_ref):
    mask_ref[...] = (src_ref[...] != ref_ref[...]).astype(jnp.int32)


def delta_mask_words(
    src: jax.Array,  # [rows, 128] uint32
    ref: jax.Array,
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Returns the [rows, 128] int32 mask: 1 where the words differ."""
    rows = src.shape[0]
    assert src.shape == ref.shape and rows % block_rows == 0
    spec = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _delta_mask_kernel,
        grid=(rows // block_rows,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(src.shape, jnp.int32),
        interpret=interpret,
    )(src, ref)
