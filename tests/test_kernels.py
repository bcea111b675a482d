"""Per-kernel correctness: shape/dtype sweeps asserting bit-exact agreement
with the pure-jnp/zlib oracles in repro.kernels.ref."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dif, ops, ref

SHAPES = [(128,), (8, 128), (1000,), (64, 130), (3, 5, 7, 4)]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32, jnp.uint8]


def _rand(rng, shape, dtype):
    if dtype in (jnp.float32, jnp.bfloat16):
        return jnp.asarray(rng.normal(size=shape) * 3, dtype)
    if dtype == jnp.int32:
        return jnp.asarray(rng.integers(-(2**30), 2**30, shape), jnp.int32)
    return jnp.asarray(rng.integers(0, 255, shape), jnp.uint8)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_memcpy_matches_identity(rng, shape, dtype):
    nbytes = int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    if nbytes % 4:
        pytest.skip("non-word-multiple buffer")
    x = _rand(rng, shape, dtype)
    for n_pe in (1, 2, 4):
        y = ops.memcpy(x, n_pe=n_pe)
        assert y.shape == x.shape and y.dtype == x.dtype
        assert (np.asarray(y) == np.asarray(x)).all()


@pytest.mark.parametrize("n_words", [7, 128, 1000, 8192])
@pytest.mark.parametrize("plen", [1, 2, 4])
def test_fill_matches_ref(n_words, plen):
    pat = jnp.asarray(np.arange(1, plen + 1) * 0x01010101, jnp.uint32)
    out = ops.fill(pat, n_words)
    want = ref.fill_ref((n_words,), pat)
    assert (np.asarray(out) == np.asarray(want)).all()


@pytest.mark.parametrize("n", [256, 1000, 4096, 100_000])
def test_compare_finds_first_diff(rng, n):
    a = jnp.asarray(rng.integers(0, 2**31, n), jnp.uint32)
    eq, idx = ops.compare(a, a)
    assert bool(eq) and int(idx) == -1
    for pos in [0, n // 2, n - 1]:
        b = a.at[pos].add(1)
        eq, idx = ops.compare(a, b)
        weq, widx = ref.compare_ref(a, b)
        assert bool(eq) == bool(weq) and int(idx) == int(widx) == pos


def test_compare_pattern(rng):
    pat = jnp.asarray([0xAA55AA55, 0x12345678], jnp.uint32)
    buf = ref.fill_ref((2048,), pat)
    eq, idx = ops.compare_pattern(buf, pat)
    assert bool(eq)
    eq, idx = ops.compare_pattern(buf.at[99].add(1), pat)
    assert not bool(eq) and int(idx) == 99


@pytest.mark.parametrize("shape,dtype", [((512,), jnp.float32), ((33, 128), jnp.bfloat16)])
def test_dualcast(rng, shape, dtype):
    x = _rand(rng, shape, dtype)
    d1, d2 = ops.dualcast(x)
    assert (np.asarray(d1) == np.asarray(x)).all()
    assert (np.asarray(d2) == np.asarray(x)).all()


@pytest.mark.parametrize("n", [1, 2, 3, 17, 64, 256, 1000, 4096, 65536])
def test_crc32_matches_zlib(rng, n):
    x = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
    got = int(ops.crc32(x))
    want = zlib.crc32(np.asarray(x, dtype="<u4").tobytes()) & 0xFFFFFFFF
    assert got == want


def test_crc32_over_dtypes(rng):
    x = jnp.asarray(rng.normal(size=(123, 4)), jnp.float32)
    got = int(ops.crc32(x))
    want = zlib.crc32(np.asarray(x, dtype="<f4").tobytes()) & 0xFFFFFFFF
    assert got == want


@pytest.mark.parametrize("shape,dtype", [((64, 2048), np.float32), ((16, 1408), np.float32),
                                         ((24, 2816), np.int32), ((16, 11264), np.float32)])
def test_crc_of_a_leaf_read_in_place(rng, shape, dtype):
    """2-D 4-byte leaves of whole 8-row tiles, at the checkpoint cell's row
    widths (several tiles a grid step, lane chunks of 512 and of 128): the
    kernel reads them in their own layout, and the copy and both CRCs are
    bit-exact with zlib."""
    a = (rng.normal(size=shape).astype(dtype) if dtype == np.float32  # no NaN payloads
         else rng.integers(-(2**31), 2**31, size=shape).astype(dtype))
    x = jnp.asarray(a)
    assert ops._crc_in_place(x)
    want = zlib.crc32(a.tobytes()) & 0xFFFFFFFF
    copy, crc = ops.copy_crc(x)
    assert copy.dtype == x.dtype and copy.shape == x.shape
    assert np.asarray(copy).tobytes() == a.tobytes()
    assert int(crc) == want and int(ops.crc32(x)) == want


@pytest.mark.parametrize("n,k", [(512, 10), (4096, 100), (1024, 0)])
def test_delta_roundtrip(rng, n, k):
    base = jnp.asarray(rng.integers(0, 2**31, n), jnp.uint32)
    src = jnp.array(base)
    if k:
        pos = rng.choice(n, k, replace=False)
        src = src.at[pos].add(7)
    off, data, count, ovf = ops.delta_create(src, base, cap=max(k, 16))
    woff, wdata, wcount, wovf = ref.delta_create_ref(src, base, cap=max(k, 16))
    assert int(count) == int(wcount) == k and bool(ovf) == bool(wovf) is False
    out = ops.delta_apply(base, off, data)
    assert (np.asarray(out) == np.asarray(src)).all()
    out_jnp = ops.delta_apply(base, off, data, use_kernel=False)
    assert (np.asarray(out_jnp) == np.asarray(src)).all()


def test_delta_overflow_flag(rng):
    base = jnp.zeros(256, jnp.uint32)
    src = base + 1  # every word differs
    off, data, count, ovf = ops.delta_create(src, base, cap=16)
    assert bool(ovf) and int(count) == 256


# (dtype, P, page, Q, src_idx, dst_idx): pools [P, *page] -> [Q, *page]
BATCH_COPY_CASES = {
    "f32": (jnp.float32, 12, (8, 128), 12, [0, 3, 3, 11], [5, 2, 7, 0]),
    "bf16-P<Q": (jnp.bfloat16, 5, (16, 256), 9, [4, 0, 2], [8, 1, 3]),
    "f32-P>Q": (jnp.float32, 20, (8, 128), 7, [19, 0, 7, 13], [6, 2, 0, 5]),
    "n1": (jnp.bfloat16, 4, (16, 128), 6, [3], [5]),
    # a later descriptor wins where two write one page, in flight or not
    "dup-dst": (jnp.float32, 6, (8, 128), 6, [0, 1, 2, 3, 4, 5, 0, 1, 2, 3, 4, 5],
                [1, 1, 2, 1, 3, 3, 4, 5, 4, 0, 2, 2]),
    "dup-src-deep": (jnp.bfloat16, 8, (16, 128), 40, [i % 3 for i in range(30)],
                     list(range(30))),
    "4d-page": (jnp.float32, 6, (2, 8, 128), 6, [5, 4, 5], [0, 0, 3]),
    "vector-2d": (jnp.uint8, 32, (64,), 32, [7, 0, 31, 7], [1, 1, 30, 2]),
}


@pytest.mark.parametrize("case", list(BATCH_COPY_CASES))
def test_batch_copy_matches_ref(rng, case):
    dtype, P, page, Q, src_idx, dst_idx = BATCH_COPY_CASES[case]
    src_pool = _rand(rng, (P,) + page, dtype)
    dst_pool = _rand(rng, (Q,) + page, dtype)
    src_idx = jnp.asarray(src_idx, jnp.int32)
    dst_idx = jnp.asarray(dst_idx, jnp.int32)
    want = ref.batch_copy_ref(src_pool, dst_pool, src_idx, dst_idx)
    got = ops.batch_copy(src_pool, jnp.array(dst_pool), src_idx, dst_idx)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert (np.asarray(got).view(np.uint8) == np.asarray(want).view(np.uint8)).all()
    # untouched pages preserved
    untouched = sorted(set(range(Q)) - set(np.asarray(dst_idx)))
    assert (np.asarray(got)[untouched] == np.asarray(dst_pool)[untouched]).all()


def test_batch_copy_path():
    assert ops.batch_copy_path(jnp.zeros((32, 64), jnp.uint8)) == "vector"
    assert ops.batch_copy_path(jnp.zeros((4, 16, 64), jnp.bfloat16)) == "dma"
    assert ops.batch_copy_path(jnp.zeros((4, 2, 8, 128), jnp.float32)) == "dma"


@pytest.mark.parametrize("detect_races", [False, True])
def test_batch_copy_dma_in_flight(rng, detect_races):
    """The DMA kernel under the TPU interpreter's own semaphores and DMA
    queue: copies run when waited on, and with the race detector on no two
    copies in flight write one page.  Destinations repeat within and across
    the in-flight window."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import batch_copy as bc

    n = 3 * bc.DEPTH + 1
    src_pool = _rand(rng, (n, 8, 128), jnp.float32)
    dst_pool = _rand(rng, (7, 8, 128), jnp.float32)
    src_idx = jnp.arange(n, dtype=jnp.int32)
    dst_idx = jnp.asarray(rng.integers(0, 7, n), jnp.int32)
    params = pltpu.InterpretParams(detect_races=detect_races, vector_clock_size=256)
    got = bc.batch_copy_dma(src_pool, jnp.array(dst_pool), src_idx, dst_idx, interpret=params)
    want = ref.batch_copy_ref(src_pool, dst_pool, src_idx, dst_idx)
    assert (np.asarray(got) == np.asarray(want)).all()
    assert not (detect_races and ipc.races.races_found)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_batch_copy_dtypes(rng, dtype):
    pool = jnp.asarray(rng.normal(size=(4, 16, 64)), dtype)
    out = ops.batch_copy(pool, jnp.zeros_like(pool), jnp.asarray([1], jnp.int32),
                         jnp.asarray([2], jnp.int32))
    assert (np.asarray(out[2]) == np.asarray(pool[1])).all()


def test_dif_roundtrip_and_detection(rng):
    w = jnp.asarray(rng.integers(0, 2**32, 128 * 6, dtype=np.uint32))
    framed = dif.dif_insert(w)
    assert (np.asarray(framed) == np.asarray(ref.dif_insert_ref(w))).all()
    assert bool(np.asarray(dif.dif_check(framed)).all())
    corrupted = framed.at[2, 64].add(1)
    okm = np.asarray(dif.dif_check(corrupted))
    assert not okm[2] and okm.sum() == 5
    assert (np.asarray(dif.dif_strip(framed)) == np.asarray(w)).all()
    # update recomputes a valid frame after mutation
    fixed = dif.dif_update(corrupted)
    assert bool(np.asarray(dif.dif_check(fixed)).all())


@pytest.mark.parametrize("nbytes", [1, 3, 64, 1518, 4099])
def test_byte_granular_ops(rng, nbytes):
    """Byte lengths that are not word multiples (a 1518 B frame): copies
    are exact, CRCs match zlib, and compare reports the first differing
    word."""
    x = jnp.asarray(rng.integers(0, 256, nbytes), jnp.uint8)
    assert (np.asarray(ops.memcpy(x)) == np.asarray(x)).all()
    d1, d2 = ops.dualcast(x)
    assert (np.asarray(d1) == np.asarray(x)).all() and (np.asarray(d2) == np.asarray(x)).all()
    want = zlib.crc32(np.asarray(x).tobytes()) & 0xFFFFFFFF
    assert int(ops.crc32(x)) == want
    copy, crc = ops.copy_crc(x)
    assert copy.dtype == x.dtype and (np.asarray(copy) == np.asarray(x)).all()
    assert int(crc) == want
    pos = nbytes - 1
    eq, idx = ops.compare(x, x.at[pos].add(1))
    assert not bool(eq) and int(idx) == pos // 4
    eq, idx = ops.compare(x, x)
    assert bool(eq) and int(idx) == -1


def test_delta_apply_multi_block(rng):
    """A record spanning several grid blocks, in any order, with -1 padding
    interleaved: each entry lands in its own block; a later duplicate wins."""
    n = 150_000
    base = rng.integers(0, 2**32, n, dtype=np.uint32)
    off = rng.choice(n, 300, replace=False).astype(np.int32)
    off[::7] = -1
    off[-1] = off[0] = 70_001
    data = rng.integers(0, 2**32, off.shape[0], dtype=np.uint32)
    want = base.copy()
    for o, d in zip(off, data):
        if o >= 0:
            want[o] = d
    got = ops.delta_apply(jnp.asarray(base), jnp.asarray(off), jnp.asarray(data))
    assert (np.asarray(got) == want).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.uint16, jnp.int8])
def test_narrow_dtypes_any_length(rng, dtype):
    """2- and 1-byte items at lengths that end mid-word: the word view
    round-trips (memcpy, copy_crc) and the CRC covers exactly their bytes."""
    itemsize = jnp.dtype(dtype).itemsize
    for n in (1, 3, 513, 4097):
        a = rng.integers(0, 256, n * itemsize, dtype=np.uint8).view(jnp.dtype(dtype))
        x = jnp.asarray(a)
        want = zlib.crc32(a.tobytes()) & 0xFFFFFFFF
        copy, crc = ops.copy_crc(x)
        assert np.asarray(ops.memcpy(x)).tobytes() == a.tobytes()
        assert copy.dtype == x.dtype and np.asarray(copy).tobytes() == a.tobytes()
        assert int(crc) == want and int(ops.crc32(x)) == want
