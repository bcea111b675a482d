"""Engine behaviour: queues, arbitration, async completion, batch fusion,
DTO, and QoS semantics from the paper (§3.2-3.4)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BatchDescriptor,
    DeviceConfig,
    OpType,
    Status,
    StreamEngine,
    WorkDescriptor,
    WorkQueue,
    dto,
    dto_enabled,
    make_device,
)


def test_swq_retry_when_full():
    q = WorkQueue("swq", mode="shared", size=2)
    d = lambda: WorkDescriptor(op=OpType.MEMCPY, src=jnp.zeros((8, 128), jnp.float32))
    assert q.submit(d()) == Status.PENDING
    assert q.submit(d()) == Status.PENDING
    assert q.submit(d()) == Status.RETRY  # ENQCMD retry
    assert q.pop() is not None
    assert q.submit(d()) == Status.PENDING


def test_dwq_owner_enforced():
    q = WorkQueue("dwq", mode="dedicated", size=4, owner="thread0")
    d = WorkDescriptor(op=OpType.MEMCPY, src=jnp.zeros((8, 128), jnp.float32))
    assert q.submit(d, producer="thread0") == Status.PENDING
    with pytest.raises(PermissionError):
        q.submit(d, producer="thread1")  # dsalint: disable=DSA101 — raw WQ submit returns Status


def test_async_submit_wait(rng):
    d = make_device()
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    fut = d.memcpy_async(x)
    out = fut.wait()
    assert np.allclose(np.asarray(out), np.asarray(x))
    assert fut.status == Status.SUCCESS
    assert fut.record.bytes_processed == x.size * 4
    assert fut.record.modeled_time_us > 0
    assert fut.op == "memcpy"


def test_engine_error_reported():
    d = make_device()
    bad = WorkDescriptor(op=OpType.DELTA_APPLY, src=None, src_idx=None, src2=None)
    fut = d.submit(bad)
    d.drain()
    assert fut.status == Status.ERROR and fut.error
    with pytest.raises(RuntimeError):
        fut.result()


def test_batch_fusion_equals_individual(rng):
    s = make_device()
    xs = [jnp.asarray(rng.normal(size=(8, 128)), jnp.float32) for _ in range(5)]
    descs = [WorkDescriptor(op=OpType.MEMCPY, src=x) for x in xs]
    outs = s.batch_async(descs).result()
    assert len(outs) == 5
    for o, x in zip(outs, xs):
        assert np.allclose(np.asarray(o), np.asarray(x))


def test_batch_copy_path_counters(rng):
    """A 3-D page pool's batch copy counts on the DMA path; a fused burst of
    1-D packets (a 2-D pool) on the vector path."""
    s = make_device()
    eng = s.engines[0]
    pool = jnp.asarray(rng.normal(size=(4, 8, 128)), jnp.float32)
    idx = jnp.asarray([1, 3], jnp.int32)
    out = s.batch_copy_async(pool, jnp.zeros_like(pool), idx, idx).result()
    assert (np.asarray(out)[[1, 3]] == np.asarray(pool)[[1, 3]]).all()
    assert (eng.counters["batch_copy_dma"], eng.counters["batch_copy_vector"]) == (1, 0)
    packets = [jnp.asarray(rng.integers(0, 255, 64), jnp.uint8) for _ in range(4)]
    outs = s.batch_async([WorkDescriptor(op=OpType.MEMCPY, src=p) for p in packets]).result()
    assert all((np.asarray(o) == np.asarray(p)).all() for o, p in zip(outs, packets))
    assert (eng.counters["batch_copy_dma"], eng.counters["batch_copy_vector"]) == (1, 1)


def test_mixed_batch(rng):
    s = make_device()
    x = jnp.asarray(rng.integers(0, 2**31, 1024), jnp.uint32)
    descs = [
        WorkDescriptor(op=OpType.MEMCPY, src=x),
        WorkDescriptor(op=OpType.CRC32, src=x),
        WorkDescriptor(op=OpType.COMPARE, src=x, src2=x),
    ]
    outs = s.batch_async(descs).result()
    assert np.allclose(np.asarray(outs[0]), np.asarray(x))
    import zlib

    assert int(outs[1]) == zlib.crc32(np.asarray(x, "<u4").tobytes()) & 0xFFFFFFFF
    eq, idx = outs[2]
    assert bool(eq)


def test_priority_arbitration():
    """High-priority WQ is serviced preferentially; starvation guard still
    services the low-priority queue (paper F3)."""
    cfg = DeviceConfig.default(n_groups=1, wqs_per_group=2, pes_per_group=1, wq_size=64)
    eng = StreamEngine(cfg)
    eng.wq(0, 0).priority = 0
    eng.wq(0, 1).priority = 10
    x = jnp.zeros((8, 128), jnp.float32)
    lo = [WorkDescriptor(op=OpType.MEMCPY, src=x) for _ in range(6)]
    hi = [WorkDescriptor(op=OpType.MEMCPY, src=x) for _ in range(6)]
    for d in lo:
        eng.wq(0, 0).submit(d)  # dsalint: disable=DSA101,DSA106 — raw WQ submit returns Status
    for d in hi:
        eng.wq(0, 1).submit(d)  # dsalint: disable=DSA101,DSA106 — raw WQ submit returns Status
    eng.drain()
    assert eng.wq(0, 1).stats["dispatched"] == 6
    assert eng.wq(0, 0).stats["dispatched"] == 6  # no starvation


def test_multi_instance_round_robin(rng):
    s = make_device(n_instances=3, policy="round_robin")
    x = jnp.zeros((8, 128), jnp.float32)
    for _ in range(6):
        s.memcpy_async(x).wait()  # dsalint: disable=DSA106 — per-descriptor path under test
    used = [e for e in s.engines if any(w.stats["submitted"] for g in e.config.groups for w in g.wqs)]
    assert len(used) == 3  # load balanced


def test_dto_threshold(rng):
    s = make_device()
    small = jnp.zeros((4,), jnp.float32)  # 16B < threshold
    big = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    with dto_enabled(s, min_bytes=1024):
        assert np.allclose(np.asarray(dto.memcpy(small)), 0)
        assert np.allclose(np.asarray(dto.memcpy(big)), np.asarray(big))
        assert dto.memcmp(big, big)
        z = dto.memset(big, 0)
        assert (np.asarray(z) == 0).all()
    submitted = sum(w.stats["submitted"] for e in s.engines for g in e.config.groups for w in g.wqs)
    assert submitted >= 3  # big ops offloaded; small stayed on "core"


def test_completion_record_timing_fields(rng):
    s = make_device(trace=True)
    x = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    fut = s.memcpy_async(x)
    fut.wait()
    s.drain()
    assert fut.record.modeled_time_us > 0
    m = fut.trace.marks
    assert m["dispatch"] <= m["exec0"] <= m["exec1"] <= m["resolved"]


def test_stream_shim_removed_with_pointer():
    """The deprecated Stream/make_stream shims are gone after their one
    grace release; residual imports fail with a migration-guide pointer."""
    import repro.core
    import repro.core.api

    for module in (repro.core, repro.core.api):
        for name in ("Stream", "make_stream"):
            with pytest.raises(AttributeError, match="docs/api.md"):
                getattr(module, name)
    # the from-import form fails too (the import machinery rewraps the
    # AttributeError, so the pointer text is only on the attribute path)
    with pytest.raises(ImportError):
        from repro.core import make_stream  # noqa: F401


def test_batch_fusion_respects_flags(rng):
    """Mixed cache hints in a copy batch must NOT take the fused path with
    shared flags — results still match the per-descriptor semantics."""
    from repro.core import CacheHint

    s = make_device()
    xs = [jnp.asarray(rng.normal(size=(8, 128)), jnp.float32) for _ in range(4)]
    descs = [
        WorkDescriptor(
            op=OpType.MEMCPY, src=x,
            cache_hint=CacheHint.TO_CACHE if i % 2 else CacheHint.TO_MEMORY,
        )
        for i, x in enumerate(xs)
    ]
    outs = s.batch_async(descs).result()
    assert len(outs) == 4
    for o, x in zip(outs, xs):
        assert np.allclose(np.asarray(o), np.asarray(x))
