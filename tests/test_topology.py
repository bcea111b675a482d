"""Topology-aware fabric: link-model monotonicity (paper §4 / Fig. 13),
fabric construction, the ``numa_local`` policy's prefer-then-degrade
behaviour, buffer-locality stamping, per-node telemetry rollups, and the
NUMA-sharded KV pool's no-leak swap contract."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Device,
    Link,
    Node,
    NumaLocalPolicy,
    OpType,
    QueueFull,
    Topology,
    WorkDescriptor,
    make_device,
)
from repro.core.perfmodel import DEFAULT_MODEL as MODEL
from repro.core.telemetry import Telemetry
from repro.serving.kv_pool import PagedKVPool

SIZES = [256, 4096, 65536, 1 << 20, 16 << 20]
REMOTE_PLACEMENTS = [(0, 1, 0), (0, 0, 1), (1, 0, 0), (0, 1, 1)]


def _desc(shape=(8, 128), **kw):
    return WorkDescriptor(op=OpType.MEMCPY, src=jnp.zeros(shape, jnp.float32), **kw)


# --------------------------------------------------------------------------- topology model
def test_topology_validation():
    with pytest.raises(ValueError):
        Topology([])
    with pytest.raises(ValueError):
        Topology([Node(0), Node(2)])  # ids must be dense
    with pytest.raises(ValueError):
        Node(-1)
    with pytest.raises(ValueError):
        Node(0, n_engines=0)
    with pytest.raises(ValueError):
        Link(bw=0)
    with pytest.raises(ValueError):
        Link(lat_s=-1e-6)
    with pytest.raises(ValueError):
        Topology.symmetric(0)


def test_hop_arithmetic():
    topo = Topology.symmetric(2)
    assert topo.hops(0, 0, 0) == 0
    assert topo.hops(0, 1, 0) == 1  # remote source
    assert topo.hops(0, 0, 1) == 1  # remote destination
    assert topo.hops(1, 0, 0) == 2  # engine remote from both buffers
    assert topo.hops(0, 1, 1) == 2
    assert topo.link_charge(0, 0, 0) == {}
    charge = topo.link_charge(1, 0, 0)
    assert charge["link_hops"] == 2 and charge["link"] is topo.link
    # a single-node topology never charges the link
    assert Topology.single_node().link_charge(0, 0, 0) == {}


def test_cross_node_op_time_monotonic():
    """The paper's locality guideline: ANY cross-node placement is slower
    than all-local, at EVERY transfer size, and more hops cost more."""
    topo = Topology.symmetric(2)
    for size in SIZES:
        local = MODEL.op_time(size)
        one_hop = MODEL.op_time(size, **topo.link_charge(0, 1, 0))
        two_hop = MODEL.op_time(size, **topo.link_charge(1, 0, 0))
        for e, s, d in REMOTE_PLACEMENTS:
            assert MODEL.op_time(size, **topo.link_charge(e, s, d)) > local
        assert two_hop > one_hop > local


def test_engine_nodes_layout():
    topo = Topology([Node(0, n_engines=2), Node(1, n_engines=3)])
    assert topo.engine_nodes() == [0, 0, 1, 1, 1]
    assert topo.n_nodes == 2 and topo.node(1).n_engines == 3


# --------------------------------------------------------------------------- fabric device
def test_fabric_builds_engines_per_node():
    d = make_device(topology=Topology.symmetric(2, engines_per_node=2))
    assert [(e.name, e.node_id) for e in d.engines] == [
        ("n0dsa0", 0), ("n0dsa1", 0), ("n1dsa0", 1), ("n1dsa1", 1)]
    assert [e.name for e in d.engines_on(1)] == ["n1dsa0", "n1dsa1"]
    # the flat default keeps the legacy shape: one node, dsa{i} names
    flat = make_device(n_instances=2)
    assert flat.topology.n_nodes == 1
    assert [e.name for e in flat.engines] == ["dsa0", "dsa1"]


def test_registry_and_node_hint():
    d = make_device(topology=Topology.symmetric(2))
    x = jnp.ones((16, 128), jnp.float32)
    assert d.home(x) is None
    d.register(x, 1)
    assert d.home(x) == 1
    with pytest.raises(ValueError):
        d.register(x, 2)  # out of range for a 2-node fabric
    fut = d.memcpy_async(x)
    fut.result()
    assert fut.record.src_node == 1
    # node= hint stamps operands the registry doesn't know
    y = jnp.ones((16, 128), jnp.float32)
    fut2 = d.memcpy_async(y, node=0)
    fut2.result()
    assert fut2.record.src_node == 0 and fut2.record.dst_node == 0


def test_record_attribution_and_link_charge():
    d = make_device(topology=Topology.symmetric(2), policy="numa_local")
    x = jnp.ones((64, 128), jnp.float32)
    d.register(x, 1)
    # engine placed at the destination's home; the remote source costs 1 hop
    fut = d.submit(WorkDescriptor(op=OpType.MEMCPY, src=x, dst_node=0))
    fut.result()
    assert fut.engine.node_id == 0
    assert fut.record.engine_node == 0
    assert fut.record.src_node == 1 and fut.record.dst_node == 0
    assert fut.record.link_hops == 1
    # modeled time carries the link charge: same submission fully local
    local = d.memcpy_async(x)  # home node 1, engine follows -> 0 hops
    local.result()
    assert local.record.link_hops == 0
    assert fut.record.modeled_time_us > local.record.modeled_time_us


def test_single_node_never_charges_link():
    d = make_device(n_instances=2)
    x = jnp.ones((32, 128), jnp.float32)
    fut = d.memcpy_async(x, node=0)
    fut.result()
    assert fut.record.link_hops == 0 and fut.record.engine_node == 0


# --------------------------------------------------------------------------- numa_local policy
def test_numa_local_picks_home_node_when_free():
    d = make_device(topology=Topology.symmetric(2, engines_per_node=2),
                    policy="numa_local")
    for node in (0, 1, 1, 0):
        fut = d.memcpy_async(jnp.ones((8, 128), jnp.float32), node=node)  # dsalint: disable=DSA106 — per-descriptor path under test
        assert fut.engine.node_id == node
        fut.result()


def test_numa_local_degrades_when_saturated():
    d = make_device(topology=Topology.symmetric(2),
                    policy="numa_local", wqs_per_group=1, wq_size=2)
    home = d.engines_on(1)[0]
    # stuff the home node's only WQ without kicking: occupancy hits 1.0
    while home.wq(0, 0).submit(_desc()).name != "RETRY":
        pass
    policy = NumaLocalPolicy()
    picked = policy.select(d.engines, _desc(src_node=1), None)
    assert picked.node_id == 0  # graceful degrade: remote beats stalled
    # and with a free home engine it goes home again
    assert policy.select(d.engines, _desc(src_node=0), None).node_id == 0


def test_numa_local_composes_with_inner_policy():
    policy = NumaLocalPolicy(inner="sticky")
    d = make_device(topology=Topology.symmetric(2, engines_per_node=2),
                    policy=policy)
    picks = {d.policy.select(d.engines, _desc(src_node=1), f"p{i}").name
             for i in range(4)}
    assert all(n.startswith("n1") for n in picks)  # home node respected
    one = [d.policy.select(d.engines, _desc(src_node=1), "p0").name
           for _ in range(3)]
    assert len(set(one)) == 1  # sticky affinity inside the node


# --------------------------------------------------------------------------- telemetry rollups
def test_per_node_rollups_sum_to_device_totals():
    d = make_device(topology=Topology.symmetric(2), policy="numa_local")
    tel = Telemetry(d)
    x0 = jnp.ones((64, 128), jnp.float32)
    x1 = jnp.ones((64, 128), jnp.float32)
    d.register(x0, 0)
    d.register(x1, 1)
    futs = [d.memcpy_async(x0), d.memcpy_async(x1)]  # local on each node
    futs.append(d.submit(WorkDescriptor(op=OpType.MEMCPY, src=x1, dst_node=0)))
    d.wait_all(futs)
    d.drain()
    snap = tel.snapshot()
    assert set(snap["nodes"]) == {0, 1}
    local_b = sum(n["local_bytes"] for n in snap["nodes"].values())
    cross_b = sum(n["cross_bytes"] for n in snap["nodes"].values())
    assert local_b > 0 and cross_b > 0
    engine_total = sum(c["bytes"] for e in snap["engines"].values()
                       for c in e["ops"].values())
    assert local_b + cross_b == engine_total
    ops_total = sum(c["count"] for e in snap["engines"].values()
                    for c in e["ops"].values())
    node_ops = sum(n["local_ops"] + n["cross_ops"]
                   for n in snap["nodes"].values())
    assert node_ops == ops_total
    occ = [n["link_occupancy"] for n in snap["nodes"].values()]
    assert all(o >= 0.0 for o in occ) and max(occ) > 0.0
    assert "node" in tel.report() or cross_b == 0


# --------------------------------------------------------------------------- sharded KV pool
def test_kv_pool_shards_and_spills_across_nodes():
    d = make_device(topology=Topology.symmetric(2), policy="numa_local")
    pool = PagedKVPool(n_device_pages=6, n_host_pages=8, page_tokens=8,
                       kv_dim=32, device=d)
    assert pool.free_device_pages(0) == 3 and pool.free_device_pages(1) == 3
    assert pool.alloc(1, 5)  # must spill: no single shard holds 5
    nodes = {n for t, n, _ in pool.page_table[1] if t == "device"}
    assert nodes == {0, 1}
    for i in range(5):
        pool.write_page(1, i, jnp.ones((8, 32)) * (i + 1))
    before = np.asarray(pool.read_pages(1))
    assert pool.swap_out(1)  # one batch descriptor per source node
    assert pool.stats.batch_copies == 2
    assert pool.stats.device_pages_used == 0
    assert pool.swap_in(1, node=1) is False  # node 1 alone can't hold 5
    assert pool.swap_in(1)
    assert (np.asarray(pool.read_pages(1)) == before).all()
    assert pool.stats.cross_node_swaps > 0  # host tier lives on node 0
    pool.free(1)
    assert pool.free_device_pages() == 6


def test_kv_pool_multinode_swap_out_charges_link():
    """The node-1 -> host@node-0 leg of a multi-node swap-out must keep its
    link charge even though the chained host pool is a fresh intermediate
    array (regression: unregistered intermediates resolved engine-local)."""
    d = make_device(topology=Topology.symmetric(2), policy="numa_local")
    tel = Telemetry(d)
    pool = PagedKVPool(n_device_pages=4, n_host_pages=8, page_tokens=8,
                       kv_dim=32, device=d)
    assert pool.alloc(1, 2, node=0)
    assert pool.alloc(1, 2, node=1)
    assert pool.swap_out(1)
    d.drain()
    snap = tel.snapshot()
    assert sum(n["cross_bytes"] for n in snap["nodes"].values()) > 0


def test_kv_pool_rejects_bad_node_pin():
    pool = PagedKVPool(n_device_pages=4, n_host_pages=4, page_tokens=4,
                       kv_dim=8, topology=Topology.symmetric(2))
    with pytest.raises(ValueError):
        pool.alloc(1, 1, node=2)
    with pytest.raises(ValueError):
        pool.alloc(1, 1, node=-1)  # would alias node 1 via negative indexing
    assert pool.alloc(1, 2, node=1)
    assert pool.swap_out(1)
    with pytest.raises(ValueError):
        pool.swap_in(1, node=-1)
    assert pool.free_device_pages() == 4  # the rejects moved no state
    assert pool.stats.host_pages_used == 2
    assert pool.swap_in(1, node=0)


def test_server_rejects_device_and_topology():
    from repro.serving.pipeline import VhostStyleServer

    with pytest.raises(ValueError):
        VhostStyleServer(None, None, device=make_device(),
                         topology=Topology.symmetric(2))


def test_kv_pool_engine_failure_falls_back_to_sync():
    """An engine that refuses the swap (QueueFull after backoff) fails it:
    the error surfaces from swap_out, and the page table and free lists
    are exactly as before — no silent synchronous re-copy."""
    class BoomDevice:
        topology = Topology.symmetric(2)

        def register(self, arr, node):
            return arr

        def batch_copy_async(self, *a, **kw):
            raise QueueFull("dsa0", 3)

    pool = PagedKVPool(n_device_pages=4, n_host_pages=4, page_tokens=4,
                       kv_dim=8, device=BoomDevice())
    assert pool.alloc(1, 2)
    pool.write_page(1, 0, jnp.ones((4, 8)))
    before = np.asarray(pool.read_pages(1))
    entries_before = list(pool.page_table[1])
    free_dev_before = pool.free_device_pages()
    free_host_before = list(pool._free_host)
    with pytest.raises(QueueFull):
        pool.swap_out(1)
    assert pool.page_table[1] == entries_before
    assert pool.free_device_pages() == free_dev_before
    assert pool._free_host == free_host_before
    assert pool.stats.swaps_out == 0
    assert (np.asarray(pool.read_pages(1)) == before).all()


def test_kv_pool_failed_swap_restores_free_lists(monkeypatch):
    pool = PagedKVPool(n_device_pages=4, n_host_pages=4, page_tokens=4, kv_dim=8)
    assert pool.alloc(1, 2)
    assert pool.swap_out(1)
    free_dev_before = pool.free_device_pages()
    free_host_before = len(pool._free_host)
    entries_before = list(pool.page_table[1])
    import repro.serving.kv_pool as kvmod

    def boom(*a, **kw):
        raise RuntimeError("kernel down")

    monkeypatch.setattr(kvmod.kops, "batch_copy", boom)
    with pytest.raises(RuntimeError):
        pool.swap_in(1)
    # the pops were restored: no leaked pages, no torn page table
    assert pool.free_device_pages() == free_dev_before
    assert len(pool._free_host) == free_host_before
    assert pool.page_table[1] == entries_before
    assert pool.stats.swaps_in == 0
