"""Fused fill + verify — the hot-path op pair in ONE Pallas launch.

The paper's per-descriptor cost model (Fig. 2/3) says small-op throughput is
launch-bound: two descriptors that always travel together pay two launch
overheads and stream the data twice.  Two pairs are fused:

  copy_crc     memcpy + CRC32: the CRC kernel streams each tile to a second
               output as it folds it (``crc32.crc_streams(copy=True)``).
  fill_verify  fill + compare_pattern (here): each grid step writes the
               pattern tile and immediately reads it back into the running
               first-mismatch record — one launch instead of a 0.5x fill
               plus a 0.5x compare.

Both are bit-exact against the unfused pairs (tests/test_hotpath.py sweeps
sizes and payloads); the ops layer wraps them with the same word-grid
conventions as the unfused kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compare import first_diff_spec, fold_first_diff
from repro.kernels.fill import pattern_spec, pattern_tile

LANES = 128


def _fill_verify_kernel(pat_ref, dst_ref, first_ref):
    """Write the pattern tile, then read the destination back into the
    compare_pattern record — computed from the just-written memory."""
    expect = pattern_tile(pat_ref, dst_ref.shape)
    dst_ref[...] = expect
    fold_first_diff(first_ref, dst_ref[...] != expect)


def fill_verify_words(
    rows: int,
    pattern: jax.Array,  # [p] int32, p divides 128
    *,
    block_rows: int = 8,
    interpret: bool = False,
):
    """Returns (filled [rows, 128] i32, running first-mismatch block
    [block_rows, 128] i32 as compare.compare_words)."""
    assert rows % block_rows == 0
    p = pattern.shape[0]
    assert LANES % p == 0, "pattern must divide the lane width"
    return pl.pallas_call(
        _fill_verify_kernel,
        grid=(rows // block_rows,),
        in_specs=[pattern_spec()],
        out_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            first_diff_spec(block_rows),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((block_rows, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(pattern)
