"""Every descriptor kernel compiles for a TPU v5e chip.

The kernels run in the Pallas interpreter on the CPU backend everywhere
else in the suite, which accepts block shapes, gathers and memory accesses
that the TPU compiler refuses.  Here each op is lowered for one chip of a
described (not attached) v5e:2x2 topology and compiled by the installed TPU
compiler; nothing runs.  The compiled program must contain the Pallas
kernel (``tpu_custom_call``).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dif, ops

MiB = 1 << 20
KV_PAGE = (16, 512)  # tinyllama-1.1b: 16 tokens x K+V of 4 heads x 64


def _words(nbytes):
    return ((nbytes // 4,), jnp.uint32)


def _bytes(nbytes):
    return ((nbytes,), jnp.uint8)


PAT = ((2,), jnp.uint32)
# id -> (function of the arguments, argument (shape, dtype) list)
CASES = {
    "memcpy-1MiB": (lambda x: ops.memcpy(x, interpret=False), [_words(MiB)]),
    "dualcast-1MiB": (lambda x: ops.dualcast(x, interpret=False), [_words(MiB)]),
    "fill-1MiB": (lambda p: ops.fill(p, MiB // 4, interpret=False), [PAT]),
    "compare-1MiB": (lambda a, b: ops.compare(a, b, interpret=False),
                     [_words(MiB), _words(MiB)]),
    "compare_pattern-1MiB": (lambda a, p: ops.compare_pattern(a, p, interpret=False),
                             [_words(MiB), PAT]),
    "crc32-1MiB": (lambda x: ops.crc32(x, interpret=False), [_words(MiB)]),
    "copy_crc-1MiB": (lambda x: ops.copy_crc(x, interpret=False), [_words(MiB)]),
    "fill_verify-1MiB": (lambda p: ops.fill_verify(p, MiB // 4, interpret=False), [PAT]),
    "delta_create-1MiB": (lambda a, b: ops.delta_create(a, b, interpret=False),
                          [_words(MiB), _words(MiB)]),
    "delta_apply-1MiB": (lambda r, o, d: ops.delta_apply(r, o, d, interpret=False),
                         [_words(MiB), ((1024,), jnp.int32), ((1024,), jnp.uint32)]),
    "dif_insert-1MiB": (lambda x: dif.dif_insert(x, interpret=False), [_words(MiB)]),
    "dif_check-1MiB": (lambda f: dif.dif_check(f, interpret=False),
                       [((MiB // 512, 130), jnp.uint32)]),
    "batch_copy-1MiB": (lambda s, d, i, j: ops.batch_copy(s, d, i, j, interpret=False),
                        [((64, 4096), jnp.uint32), ((64, 4096), jnp.uint32),
                         ((8,), jnp.int32), ((8,), jnp.int32)]),
    "batch_copy-kv_page": (lambda s, d, i, j: ops.batch_copy(s, d, i, j, interpret=False),
                           [((4096,) + KV_PAGE, jnp.bfloat16), ((4096,) + KV_PAGE, jnp.bfloat16),
                            ((32,), jnp.int32), ((32,), jnp.int32)]),
}
for _n in (64, 1518):
    CASES[f"memcpy-{_n}B"] = (lambda x: ops.memcpy(x, interpret=False), [_bytes(_n)])
    CASES[f"crc32-{_n}B"] = (lambda x: ops.crc32(x, interpret=False), [_bytes(_n)])
    CASES[f"copy_crc-{_n}B"] = (lambda x: ops.copy_crc(x, interpret=False), [_bytes(_n)])
# 1 MiB of bytes: the word view of a u8 buffer (ops._split, ops._pack_bytes)
# must compile in seconds; a narrowing bitcast here took over a minute
for _op in ("memcpy", "dualcast", "copy_crc"):
    CASES[f"{_op}-1MiB-u8"] = (lambda x, f=getattr(ops, _op): f(x, interpret=False),
                               [_bytes(MiB)])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs on disk
        try:
            return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, arg_specs = CASES[case]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_batch_copy_kv_swap_moves_pages_in_place(one_chip):
    """The KV cell's largest swap (bf16[16384, 16, 4096] pools, 4,592 pages
    of 128 KiB) compiles to page DMAs on the pools as they are: no
    temporary of the size of a pool, as a relayout of both pools would
    need, and the output aliased to the donated destination."""
    pool = jax.ShapeDtypeStruct((16384, 16, 4096), jnp.bfloat16, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((4592,), jnp.int32, sharding=one_chip)
    compiled = ops.batch_copy.lower(pool, pool, idx, idx, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < MiB
    assert mem.alias_size_in_bytes == 16384 * 16 * 4096 * 2
