"""The content generators agree between NumPy and the device, and the KV
page regenerator matches a small PagedKVPool swap round trip."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.systems import kvswap
from repro.core import make_device
from repro.serving.kv_pool import PagedKVPool

SW = reference.seed_word(2**31 + 99)


def test_seed_word_takes_any_seed():
    assert reference.seed_word(2**40 + 1) != reference.seed_word(1)
    assert 0 <= reference.seed_word(2**33) < 2**32


def test_packets_agree_between_numpy_and_jax():
    host = reference.packet_bytes(SW, 594, np.arange(5))
    dev = np.asarray(reference.packet_bytes(jnp.uint32(SW), 594, jnp.arange(5), xp=jnp))
    np.testing.assert_array_equal(host, dev)
    assert host.shape == (5, 594) and host.dtype == np.uint8
    np.testing.assert_array_equal(reference.packet_bytes(SW, 594, 3), host[3])
    assert len({bytes(r) for r in host}) == 5


def test_pages_are_finite_normal_bfloat16_on_both_sides():
    host = reference.page_bits(SW, np.array([0, 3]), np.array([7, 7]), 4096)
    dev = np.asarray(reference.page_bits(jnp.uint32(SW), jnp.array([0, 3]), jnp.array([7, 7]),
                                         4096, xp=jnp))
    np.testing.assert_array_equal(host, dev)
    vals = np.asarray(jax.lax.bitcast_convert_type(jnp.asarray(host), jnp.bfloat16), np.float32)
    assert np.all(np.isfinite(vals)) and np.all(np.abs(vals) >= 2.0 ** -7)
    assert not np.array_equal(host[0], host[1])


def test_regenerated_pages_survive_a_pool_swap_round_trip():
    block, kv_dim = 16, 128
    kv = PagedKVPool(8, 8, block, kv_dim, dtype=jnp.bfloat16, device=make_device())
    assert kv.alloc(0, 3, "device") and kv.alloc(1, 2, "host")
    for tier, n in (("device", 8), ("host", 8)):
        session = np.full(n, -1, np.int32)
        page_no = np.zeros(n, np.int32)
        for s, entries in kv.page_table.items():
            for p, (t, _node, idx) in enumerate(entries):
                if t == tier:
                    session[idx], page_no[idx] = s, p
        pool = kvswap._fill(jnp.uint32(SW), jnp.asarray(session), jnp.asarray(page_no), (block, kv_dim))
        if tier == "device":
            kv._set_device_pool(0, pool)
        else:
            kv._set_host_pool(pool)
    assert kv.swap_out(0) and kv.swap_in(1) and kv.swap_in(0) and kv.swap_out(1)
    for s, n in ((0, 3), (1, 2)):
        got = np.asarray(jax.lax.bitcast_convert_type(kv.read_pages(s), jnp.uint16))
        want = reference.page_bits(SW, np.full(n, s), np.arange(n), block * kv_dim)
        np.testing.assert_array_equal(got.reshape(n, -1), want)
