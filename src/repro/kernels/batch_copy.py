"""Batch-descriptor page copy kernels (paper F2 — THE key DSA feature).

A batch descriptor delivers an array of work descriptors processed in one
submission.  TPU-native analogue: ONE pallas_call that walks a
scalar-prefetched descriptor table (src_page -> dst_page).  This amortizes a
single kernel launch over N page copies exactly as DSA amortizes one ENQCMD
over N descriptors — and it is the engine behind paged-KV-cache block moves
(serving) and incremental-checkpoint page flushes.

Two kernels, chosen by ``ops.batch_copy_path``:

- ``batch_copy_dma``: pools stay in HBM in their own dtype and layout, and
  each page moves HBM -> HBM on the DMA engine, ``DEPTH`` copies in flight.
  Needs a page of at least 2 dims, so that slicing one page off the leading
  dim never cuts an HBM tile.
- ``batch_copy_pages``: a grid over [pages, rows, 128] u32 word tiles, each
  step's block re-pointed through the BlockSpec index_map and staged in
  VMEM; for pools whose page is a single row.

The destination pool is donated (input_output_aliased), so untouched pages
keep their contents — matching DSA semantics of scattered writes into an
existing buffer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# DMAs in flight in batch_copy_dma.  On one v5e, swaps of 1,792-4,592
# pages of 128 KiB read the same share of HBM bandwidth at 4, 8 and 16
# (PERF.md, section 6); 8 keeps twice 4's bytes in flight for smaller
# pages, and each copy compares its destination with DEPTH others.
DEPTH = 8


def _batch_copy_kernel(src_idx_ref, dst_idx_ref, src_pool_ref, dst_in_ref, dst_pool_ref):
    del dst_in_ref  # aliased with the output; untouched pages persist
    dst_pool_ref[...] = src_pool_ref[...]


def batch_copy_pages(
    src_pool: jax.Array,  # [P, rows, 128]
    dst_pool: jax.Array,  # [Q, rows, 128] (donated)
    src_idx: jax.Array,  # [N] i32
    dst_idx: jax.Array,  # [N] i32
    *,
    interpret: bool = False,
) -> jax.Array:
    n = src_idx.shape[0]
    rows = src_pool.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda i, sidx, didx: (sidx[i], 0, 0)),
            pl.BlockSpec((1, rows, LANES), lambda i, sidx, didx: (didx[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, LANES), lambda i, sidx, didx: (didx[i], 0, 0)),
    )
    return pl.pallas_call(
        _batch_copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_pool.shape, dst_pool.dtype),
        input_output_aliases={3: 0},  # dst_pool arg (after 2 scalars + src) -> output
        interpret=interpret,
    )(src_idx, dst_idx, src_pool, dst_pool)


def _batch_copy_dma_kernel(src_idx_ref, dst_idx_ref, src_pool_ref, dst_in_ref,
                           dst_pool_ref, sems):
    """Copy i reuses semaphore i % DEPTH; copies [done, i) are in flight and
    finish in any order, so a copy into a page that one of them is writing
    first waits for all of them (a later descriptor wins)."""
    del dst_in_ref  # aliased with the output; untouched pages persist
    n = src_idx_ref.shape[0]

    def page_copy(i):
        return pltpu.make_async_copy(src_pool_ref.at[src_idx_ref[i]],
                                     dst_pool_ref.at[dst_idx_ref[i]], sems.at[i % DEPTH])

    def wait(j, carry):
        page_copy(j).wait()
        return carry

    def start(i, done):
        collides = False
        for k in range(1, DEPTH + 1):
            j = jnp.maximum(i - k, 0)  # the read is in bounds; i - k < done is masked
            collides = collides | ((i - k >= done) & (dst_idx_ref[j] == dst_idx_ref[i]))
        upto = jnp.where(collides, i, jnp.maximum(done, i - DEPTH + 1))
        jax.lax.fori_loop(done, upto, wait, 0)
        page_copy(i).start()
        return upto

    done = jax.lax.fori_loop(0, n, start, jnp.int32(0))
    jax.lax.fori_loop(done, n, wait, 0)


def batch_copy_dma(
    src_pool: jax.Array,  # [P, *page]
    dst_pool: jax.Array,  # [Q, *page] (donated)
    src_idx: jax.Array,  # [N] i32
    dst_idx: jax.Array,  # [N] i32
    *,
    interpret: bool = False,
) -> jax.Array:
    """dst_pool[dst_idx[i]] = src_pool[src_idx[i]] as N HBM -> HBM DMAs,
    at most DEPTH in flight, in the pools' own dtype and layout."""
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[any_spec, any_spec],
        out_specs=any_spec,
        scratch_shapes=[pltpu.SemaphoreType.DMA((DEPTH,))],
    )
    return pl.pallas_call(
        _batch_copy_dma_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(dst_pool.shape, dst_pool.dtype),
        input_output_aliases={3: 0},  # dst_pool arg (after 2 scalars + src) -> output
        interpret=interpret,
    )(src_idx, dst_idx, src_pool, dst_pool)
