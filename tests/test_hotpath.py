"""Submit/complete hot path: fused submission (submit_many / SubmitRing),
kick() slot reuse, fused Pallas pairs (copy_crc / fill_verify), the DSA106
unbatched-submit-loop lint, and the CRC operator tables."""
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import desclint
from repro.analysis.apilint import lint_source
from repro.core import OpType, Status, WorkDescriptor, make_device
from repro.core.device import QueueFull
from repro.core.queues import WorkQueue
from repro.kernels import ops


def _bufs(rng, n=8, words=256):
    return [jnp.asarray(rng.integers(0, 2**32, words, dtype=np.uint32))
            for _ in range(n)]


def _copies(bufs):
    return [WorkDescriptor(op=OpType.MEMCPY, src=b) for b in bufs]


# --------------------------------------------------------------------------- fused kernels
def test_copy_crc_parity(rng):
    """copy_crc == (memcpy, crc32) bit-for-bit, including sizes that don't
    tile the 128-lane grid and multi-chunk splits."""
    for n in (4, 100, 512, 1000, 4096, 16384):
        x = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
        copy, crc = ops.copy_crc(x)
        assert np.array_equal(np.asarray(copy), np.asarray(x))
        ref = zlib.crc32(np.asarray(x).tobytes()) & 0xFFFFFFFF
        assert int(crc) == ref
        assert int(crc) == int(ops.crc32(x))


def test_copy_crc_non_u32_payload(rng):
    x = jnp.asarray(rng.standard_normal((16, 128)), jnp.float32)
    copy, crc = ops.copy_crc(x)
    assert copy.shape == x.shape and copy.dtype == x.dtype
    assert np.array_equal(np.asarray(copy), np.asarray(x))
    assert int(crc) == (zlib.crc32(np.asarray(x).tobytes()) & 0xFFFFFFFF)


def test_fill_verify_parity():
    """fill_verify == (fill, compare_pattern): same filled words and the
    all-clear verification record, across pattern widths and ragged sizes."""
    for n_words in (8, 128, 300, 1024, 5000):
        for width in (1, 2, 4):
            pat = jnp.asarray(
                [0xDEADBEEF, 0x12345678, 0xA5A5A5A5, 0x0F0F0F0F][:width],
                jnp.uint32)
            filled, (ok, idx) = ops.fill_verify(pat, n_words)
            ref = ops.fill(pat, n_words)
            assert np.array_equal(np.asarray(filled), np.asarray(ref))
            assert bool(ok) and int(idx) == -1


# --------------------------------------------------------------------------- WQ burst enqueue
def test_wq_submit_many_all_or_nothing():
    q = WorkQueue("swq", mode="shared", size=4)
    descs = _copies([jnp.zeros((8, 128), jnp.float32)] * 3)
    assert q.submit_many(descs) == Status.PENDING
    assert len(q) == 3
    # 3 + 2 > 4: the whole burst bounces, nothing is partially enqueued
    assert q.submit_many(descs[:2]) == Status.RETRY
    assert len(q) == 3
    assert q.submit_many(descs[:1]) == Status.PENDING


def test_wq_submit_many_owner_enforced():
    q = WorkQueue("dwq", mode="dedicated", size=8, owner="t0")
    descs = _copies([jnp.zeros((8, 128), jnp.float32)] * 2)
    assert q.submit_many(descs, producer="t0") == Status.PENDING
    with pytest.raises(PermissionError):
        q.submit_many(descs, producer="t1")  # dsalint: disable=DSA101 — raw WQ submit returns Status


# --------------------------------------------------------------------------- device.submit_many
def test_submit_many_equivalent_to_singles(rng):
    """A fused burst is observably identical to N single submits: same
    results, same WQ/engine byte totals, same per-descriptor trace spans."""
    bufs = _bufs(rng)
    d1 = make_device(wq_mode="shared", trace=1.0)
    d2 = make_device(wq_mode="shared", trace=1.0)

    futs1 = [d1.submit(desc) for desc in _copies(bufs)]  # dsalint: disable=DSA106 — the unbatched reference leg
    d1.wait_all(futs1)
    futs2 = d2.submit_many(_copies(bufs))
    d2.wait_all(futs2)

    for f1, f2 in zip(futs1, futs2):
        assert np.array_equal(np.asarray(f1.result()), np.asarray(f2.result()))
    c1 = d1.engines[0].counters_snapshot()
    c2 = d2.engines[0].counters_snapshot()
    assert c1["bytes"] == c2["bytes"]
    assert c1["completed"] == c2["completed"] == len(bufs)
    assert c2["submitted"] == len(bufs)
    assert c2["fused_batches"] == 1 and c2["fused_descs"] == len(bufs)
    assert c1["fused_batches"] == 0

    wq1 = d1.engines[0].wq(0, 0).stats
    wq2 = d2.engines[0].wq(0, 0).stats
    assert wq1["bytes_submitted"] == wq2["bytes_submitted"]

    marks1 = sorted(frozenset(t.marks) for t in d1.tracer.traces())
    marks2 = sorted(frozenset(t.marks) for t in d2.tracer.traces())
    assert marks1 == marks2  # same lifecycle span structure per descriptor


def test_submit_many_amortizes_enqcmd(rng):
    """On a shared WQ the ENQCMD round trip is charged once per fused
    doorbell: a b8 burst models 7/8 of the per-descriptor ENQCMD away."""
    bufs = _bufs(rng)
    d1 = make_device(wq_mode="shared")
    d2 = make_device(wq_mode="shared")
    futs1 = d1.wait_all([d1.submit(x) for x in _copies(bufs)])  # dsalint: disable=DSA106 — the unbatched reference leg
    futs2 = d2.wait_all(d2.submit_many(_copies(bufs)))
    m1 = sum(f.record.modeled_time_us for f in futs1)
    m2 = sum(f.record.modeled_time_us for f in futs2)
    enq_us = d2.engines[0].model.enqcmd_overhead_s * 1e6
    saved = enq_us * (len(bufs) - 1)
    assert m1 - m2 == pytest.approx(saved, rel=1e-6)


def test_submit_many_dedicated_no_enqcmd_delta(rng):
    """Dedicated WQs (posted MOVDIR64B) never charged ENQCMD, so fusion
    must not change the modeled time there."""
    bufs = _bufs(rng)
    d1 = make_device(wq_mode="dedicated")
    d2 = make_device(wq_mode="dedicated")
    futs1 = d1.wait_all([d1.submit(x) for x in _copies(bufs)])  # dsalint: disable=DSA106 — the unbatched reference leg
    futs2 = d2.wait_all(d2.submit_many(_copies(bufs)))
    m1 = sum(f.record.modeled_time_us for f in futs1)
    m2 = sum(f.record.modeled_time_us for f in futs2)
    assert m1 == pytest.approx(m2, rel=1e-9)


def test_submit_many_failed_fence_fails_all(rng):
    d = make_device()
    bad = d.promise()
    bad.set_error("upstream exploded")
    futs = d.submit_many(_copies(_bufs(rng, n=3)), after=[bad])
    assert len(futs) == 3
    assert all(f.status == Status.ERROR for f in futs)


def test_submit_many_pending_fence_defers_then_runs(rng):
    d = make_device()
    gate = d.promise()
    bufs = _bufs(rng, n=3)
    futs = d.submit_many(_copies(bufs), after=[gate])
    assert not any(f.done() for f in futs)
    gate.set_result(None)
    d.wait_all(futs)
    for f, b in zip(futs, bufs):
        assert f.status == Status.SUCCESS
        assert np.array_equal(np.asarray(f.result()), np.asarray(b))


def test_submit_many_queue_full_raises(rng):
    """A burst that can never fit bounces off every backoff attempt and
    surfaces as QueueFull — not a partial enqueue."""
    d = make_device(wq_size=2, max_retries=1, backoff_base_s=1e-5)
    gate = d.promise()  # hold the WQ full so retries can't drain it
    held = d.submit_many(_copies(_bufs(rng, n=2)), after=[gate])
    with pytest.raises(QueueFull):
        d.submit_many(_copies(_bufs(rng, n=4)), chunk=4)  # dsalint: disable=DSA101 — raises QueueFull
    gate.set_result(None)
    d.wait_all(held)


# --------------------------------------------------------------------------- slot reuse
def test_kick_reuses_slot_objects(rng):
    """The free-slot ring recycles the same _PESlot objects forever —
    inventory is conserved and nothing is reallocated per dispatch."""
    d = make_device()
    eng = d.engines[0]
    inventory = {id(s) for slots in eng._slots.values() for s in slots}
    for _ in range(3):
        d.wait_all(d.submit_many(_copies(_bufs(rng))))
    now = {id(s) for g in eng.config.groups
           for s in eng._free[g.name] + eng._active[g.name]}
    assert now == inventory
    # after the waits everything is retired back onto the free ring
    for g in eng.config.groups:
        assert not eng._active[g.name]
        assert len(eng._free[g.name]) == len(eng._slots[g.name])


# --------------------------------------------------------------------------- submit ring
def test_submit_ring_defers_until_kick(rng):
    d = make_device(wq_mode="shared")
    ring = d.submit_ring(depth=64)
    bufs = _bufs(rng)
    futs = [ring.add(desc) for desc in _copies(bufs)]
    assert len(ring) == len(bufs)
    assert not any(f.done() for f in futs)
    d.wait_all(futs)  # WaitPolicy pumps device.kick() -> ring flush
    assert len(ring) == 0
    for f, b in zip(futs, bufs):
        assert np.array_equal(np.asarray(f.result()), np.asarray(b))
    assert d.engines[0].counters_snapshot()["fused_descs"] == len(bufs)
    assert ring.stats["doorbells"] == 1


def test_submit_ring_auto_flush_at_depth(rng):
    d = make_device()
    ring = d.submit_ring(depth=4)
    futs = [ring.add(desc) for desc in _copies(_bufs(rng, n=4))]
    assert len(ring) == 0  # hit depth -> flushed without an explicit kick
    d.wait_all(futs)
    assert all(f.status == Status.SUCCESS for f in futs)


def test_submit_ring_context_manager_drains(rng):
    d = make_device()
    bufs = _bufs(rng, n=3)
    with d.submit_ring(depth=16) as ring:
        futs = [ring.add(desc) for desc in _copies(bufs)]
    d.wait_all(futs)
    for f, b in zip(futs, bufs):
        assert np.array_equal(np.asarray(f.result()), np.asarray(b))


# --------------------------------------------------------------------------- fused ops e2e
def test_copy_crc_async_device_path(rng):
    d = make_device()
    x = _bufs(rng, n=1, words=1000)[0]
    copy, crc = d.copy_crc_async(x).result()
    assert np.array_equal(np.asarray(copy), np.asarray(x))
    assert int(crc) == (zlib.crc32(np.asarray(x).tobytes()) & 0xFFFFFFFF)


def test_fill_verify_async_device_path():
    d = make_device()
    filled, (ok, idx) = d.fill_verify_async((0xABCD1234,), 1000).result()
    assert bool(ok) and int(idx) == -1
    assert filled.shape[0] == 1000
    assert int(filled[0]) == 0xABCD1234


def test_fused_ops_pass_desclint_strict(rng):
    d = make_device(validate="strict")
    f1 = d.copy_crc_async(_bufs(rng, n=1)[0])
    f2 = d.fill_verify_async((0x5A5A5A5A, 0xA5A5A5A5), 512)
    d.wait_all([f1, f2])
    assert f1.status == Status.SUCCESS and f2.status == Status.SUCCESS


# --------------------------------------------------------------------------- desclint
def test_desclint_copy_crc_missing_src():
    diags = desclint.check_descriptor(WorkDescriptor(op=OpType.COPY_CRC))
    assert any(x.code == "DESC101" for x in diags)


def test_desclint_fill_verify_contract():
    diags = desclint.check_descriptor(
        WorkDescriptor(op=OpType.FILL_VERIFY, n_words=64))
    assert any(x.code == "DESC101" and "pattern" in x.message for x in diags)
    diags = desclint.check_descriptor(
        WorkDescriptor(op=OpType.FILL_VERIFY,
                       pattern=jnp.asarray([1], jnp.uint32), n_words=0))
    assert any(x.code == "DESC101" and "n_words" in x.message for x in diags)
    ok = desclint.check_descriptor(
        WorkDescriptor(op=OpType.FILL_VERIFY,
                       pattern=jnp.asarray([1], jnp.uint32), n_words=64))
    assert ok == []


# --------------------------------------------------------------------------- DSA106 lint
def test_dsa106_flags_unbatched_loop():
    out = lint_source("for d in descs:\n    futs.append(dev.submit(d))\n")
    assert any(v.code == "DSA106" for v in out)


def test_dsa106_exemptions():
    clean = (
        # batched entry point in a loop is already amortized
        "for burst in bursts:\n    futs += dev.submit_many(burst)\n"
        # conditional submit: not a homogeneous fan-out
        "for d in descs:\n    if d.hot:\n        futs.append(dev.submit(d))\n"
        # retry wrapper: breaks out on success
        "for attempt in range(3):\n"
        "    f = dev.submit(d)\n"
        "    if f is not None:\n        break\n"
    )
    assert [v for v in lint_source(clean) if v.code == "DSA106"] == []


def test_dsa106_suppression():
    src = "for d in descs:\n    futs.append(dev.submit(d))  # dsalint: disable=DSA106\n"
    assert [v for v in lint_source(src) if v.code == "DSA106"] == []


# --------------------------------------------------------------------------- CRC operator tables
@pytest.mark.parametrize("nbytes", [0, 1, 3, 4, 64, 1518, 4096, 1 << 20])
def test_crc_zeros_crc_matches_zlib(nbytes):
    from repro.kernels.crc32 import zeros_crc

    assert zeros_crc(nbytes) == zlib.crc32(bytes(nbytes)) & 0xFFFFFFFF


def test_crc_advance_columns_are_zero_word_feeds():
    """Row m of the operator table is the register after m zero words:
    A_1 matches zlib continuing over 4 zero bytes, and A_{m+n} = A_m A_n."""
    from repro.kernels.crc32 import advance_columns

    cols = advance_columns()
    for b in (0, 7, 31):
        reg = 1 << b
        # zlib's running value is the register xor 0xFFFFFFFF on both ends
        want = zlib.crc32(bytes(4), reg ^ 0xFFFFFFFF) ^ 0xFFFFFFFF
        assert int(cols[1][b]) == want

    def apply(m, v):
        out = 0
        for b in range(32):
            if (v >> b) & 1:
                out ^= int(cols[m][b])
        return out

    for b in range(32):
        assert apply(1000, int(cols[24][b])) == int(cols[1024][b])
