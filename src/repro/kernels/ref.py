"""Pure-jnp / numpy oracles for every streaming kernel (Table 1 of the paper).

These define the SEMANTICS; the Pallas kernels must match them bit-exactly
(tests/test_kernels.py sweeps shapes x dtypes and asserts equality).

Buffers are modeled as 1-D uint32 word arrays (the TPU-native 4-byte lane
granule; the paper's DSA operates on bytes — we document the granule change
in DESIGN.md).  CRC32 matches zlib.crc32 over the little-endian byte view.
"""
from __future__ import annotations

import zlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------- CRC32 combine
_POLY = 0xEDB88320  # reflected IEEE


def _gf2_matrix_times(mat: np.ndarray, vec: int) -> int:
    s = 0
    i = 0
    while vec:
        if vec & 1:
            s ^= int(mat[i])
        vec >>= 1
        i += 1
    return s


def _gf2_matrix_square(mat: np.ndarray) -> np.ndarray:
    return np.array([_gf2_matrix_times(mat, int(m)) for m in mat], dtype=np.uint64)


def crc32_shift_matrix(length_bytes: int) -> np.ndarray:
    """Matrix advancing a CRC state over ``length_bytes`` zero bytes: [32] u32
    columns (column i = image of bit i)."""
    # one zero BIT, squared 3x -> one zero BYTE
    op = np.zeros(32, dtype=np.uint64)
    op[0] = np.uint64(_POLY)
    for i in range(1, 32):
        op[i] = np.uint64(1) << np.uint64(i - 1)
    for _ in range(3):
        op = _gf2_matrix_square(op)
    # binary exponentiation over bytes
    result = np.array([1 << i for i in range(32)], dtype=np.uint64)
    base = op
    n = length_bytes
    while n:
        if n & 1:
            result = np.array([_gf2_matrix_times(base, int(r)) for r in result], dtype=np.uint64)
        base = _gf2_matrix_square(base)
        n >>= 1
    return result.astype(np.uint32)


# --------------------------------------------------------------------------- oracles
def words_to_bytes(words: np.ndarray) -> bytes:
    return np.asarray(words, dtype="<u4").tobytes()


def memcpy_ref(src: jnp.ndarray) -> jnp.ndarray:
    return jnp.array(src)  # identity copy


def fill_ref(shape: Tuple[int, ...], pattern_words: jnp.ndarray) -> jnp.ndarray:
    """Fill a uint32 word buffer with a repeating pattern (2 or 4 words = the
    paper's 8/16-byte patterns)."""
    n = int(np.prod(shape))
    p = len(pattern_words)
    reps = -(-n // p)
    return jnp.tile(jnp.asarray(pattern_words, jnp.uint32), reps)[:n].reshape(shape)


def compare_ref(a: jnp.ndarray, b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(equal?, first-diff flat index or -1)."""
    diff = (a != b).reshape(-1)
    any_diff = diff.any()
    idx = jnp.argmax(diff)  # first True
    return ~any_diff, jnp.where(any_diff, idx, -1)


def compare_pattern_ref(a: jnp.ndarray, pattern_words: jnp.ndarray):
    expect = fill_ref(a.shape, pattern_words)
    return compare_ref(a, expect)


def dualcast_ref(src: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    return jnp.array(src), jnp.array(src)


def crc32_ref(words: jnp.ndarray) -> int:
    """zlib.crc32 of the little-endian byte view (ground truth)."""
    return zlib.crc32(words_to_bytes(np.asarray(words))) & 0xFFFFFFFF


def delta_create_ref(
    src: jnp.ndarray, ref: jnp.ndarray, cap: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Delta record vs a reference buffer, 1-word granules.

    Returns (offsets [cap] i32 (-1 pad), data [cap] u32, count, overflow?).
    """
    s = src.reshape(-1)
    r = ref.reshape(-1)
    diff = s != r
    count = diff.sum()
    (idx,) = jnp.nonzero(diff, size=cap, fill_value=-1)
    data = jnp.where(idx >= 0, s[jnp.clip(idx, 0)], 0)
    return idx.astype(jnp.int32), data.astype(jnp.uint32), count.astype(jnp.int32), count > cap


def delta_apply_ref(ref: jnp.ndarray, offsets: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    flat = ref.reshape(-1)
    valid = offsets >= 0
    flat = flat.at[jnp.clip(offsets, 0)].set(
        jnp.where(valid, data, flat[jnp.clip(offsets, 0)])
    )
    return flat.reshape(ref.shape)


def dif_insert_ref(words: jnp.ndarray, block_words: int = 128, ref_tag: int = 0) -> jnp.ndarray:
    """Append an 8-byte DIF (2 words: crc32, ref_tag|block#) per data block
    (block_words*4 bytes = 512B for 128).  Output [n_blocks, block_words+2]."""
    w = np.asarray(words).reshape(-1, block_words)
    out = np.zeros((w.shape[0], block_words + 2), dtype=np.uint32)
    out[:, :block_words] = w
    for i in range(w.shape[0]):
        out[i, block_words] = zlib.crc32(words_to_bytes(w[i])) & 0xFFFFFFFF
        out[i, block_words + 1] = (ref_tag << 16) | (i & 0xFFFF)
    return jnp.asarray(out)


def dif_check_ref(framed: jnp.ndarray, block_words: int = 128) -> jnp.ndarray:
    f = np.asarray(framed).reshape(-1, block_words + 2)
    ok = np.zeros(f.shape[0], dtype=bool)
    for i in range(f.shape[0]):
        ok[i] = (zlib.crc32(words_to_bytes(f[i, :block_words])) & 0xFFFFFFFF) == int(
            f[i, block_words]
        )
    return jnp.asarray(ok)


def dif_strip_ref(framed: jnp.ndarray, block_words: int = 128) -> jnp.ndarray:
    f = np.asarray(framed).reshape(-1, block_words + 2)
    return jnp.asarray(f[:, :block_words].reshape(-1))


def batch_copy_ref(
    src_pool: jnp.ndarray, dst_pool: jnp.ndarray, src_idx: jnp.ndarray, dst_idx: jnp.ndarray
) -> jnp.ndarray:
    """Copy pages src_pool[src_idx[i]] -> dst_pool[dst_idx[i]] (later
    descriptors win on collision, matching sequential DSA semantics)."""
    out = jnp.array(dst_pool)
    for i in range(src_idx.shape[0]):
        out = out.at[dst_idx[i]].set(src_pool[src_idx[i]])
    return out
