"""Checkpoint manager: full/delta round trips, CRC corruption fallback,
replica (dualcast) recovery, elastic restore, async overlap; the device
stage's snapshots, resumes and backpressure against bench/ckpt_reference.py."""
import json
import shutil
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointConfig, CheckpointManager


def _tree(rng, scale=1.0):
    return {
        "params": {
            "w": jnp.asarray(rng.normal(size=(64, 32)) * scale, jnp.float32),
            "b": jnp.asarray(rng.normal(size=(32,)) * scale, jnp.bfloat16),
        },
        "step_count": jnp.asarray(3, jnp.int32),
    }


def test_full_roundtrip(tmp_path, rng):
    m = CheckpointManager(CheckpointConfig(directory=str(tmp_path), async_save=False))
    t = _tree(rng)
    m.save(1, t)
    step, restored = m.restore(treedef_like=t)
    assert step == 1
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        assert (np.asarray(a) == np.asarray(b)).all()
        assert np.asarray(a).dtype == np.asarray(b).dtype


def test_delta_saves_space_and_roundtrips(tmp_path, rng):
    m = CheckpointManager(
        CheckpointConfig(directory=str(tmp_path), async_save=False, full_every=100)
    )
    t = _tree(rng)
    m.save(1, t)  # full
    # small change -> delta save
    t2 = jax.tree.map(lambda x: x, t)
    t2["params"]["w"] = t["params"]["w"].at[0, 0].add(1.0)
    m.save(2, t2)
    assert m.stats["delta_leaves"] >= 1
    assert m.stats["bytes_saved_by_delta"] > 0
    step, restored = m.restore(treedef_like=t)
    assert step == 2
    assert np.allclose(np.asarray(restored["params"]["w"]), np.asarray(t2["params"]["w"]))


def test_delta_overflow_falls_back_to_full(tmp_path, rng):
    m = CheckpointManager(
        CheckpointConfig(directory=str(tmp_path), async_save=False, full_every=100,
                         delta_cap_frac=0.01)
    )
    t = _tree(rng)
    m.save(1, t)
    t2 = jax.tree.map(lambda x: x + 1, t)  # everything changes
    m.save(2, t2)
    assert m.stats["delta_overflows"] >= 1
    _, restored = m.restore(treedef_like=t)
    assert np.allclose(np.asarray(restored["params"]["w"]), np.asarray(t2["params"]["w"]))


def test_crc_detects_corruption_and_falls_back(tmp_path, rng):
    m = CheckpointManager(CheckpointConfig(directory=str(tmp_path), async_save=False))
    t = _tree(rng)
    m.save(1, t)
    m.save(2, jax.tree.map(lambda x: x + 1, t), force_full=True)
    # corrupt the newest save
    target = next((tmp_path / "step_00000002").glob("params__w.bin"))
    raw = bytearray(target.read_bytes())
    raw[10] ^= 0xFF
    target.write_bytes(bytes(raw))
    step, restored = m.restore(treedef_like=t)
    assert step == 1  # fell back past the corrupt save
    assert np.allclose(np.asarray(restored["params"]["w"]), np.asarray(t["params"]["w"]))


def test_replica_recovers_corruption(tmp_path, rng):
    m = CheckpointManager(
        CheckpointConfig(directory=str(tmp_path / "ck"), async_save=False, replicas=2)
    )
    t = _tree(rng)
    m.save(1, t)
    target = next((tmp_path / "ck" / "step_00000001").glob("params__w.bin"))
    raw = bytearray(target.read_bytes())
    raw[0] ^= 0xFF
    target.write_bytes(bytes(raw))
    step, restored = m.restore(treedef_like=t)  # dualcast replica saves the day
    assert step == 1
    assert np.allclose(np.asarray(restored["params"]["w"]), np.asarray(t["params"]["w"]))


def test_async_save_overlaps(tmp_path, rng):
    m = CheckpointManager(CheckpointConfig(directory=str(tmp_path), async_save=True))
    t = _tree(rng)
    m.save(1, t)  # returns immediately
    m.save(2, jax.tree.map(lambda x: x + 1, t))  # waits for save 1 internally
    m.wait()
    assert m.all_steps() == [1, 2]


def test_elastic_restore_resharding(tmp_path, rng):
    """Save on one device layout, restore with explicit shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    m = CheckpointManager(CheckpointConfig(directory=str(tmp_path), async_save=False))
    t = _tree(rng)
    m.save(1, t)
    sh = jax.tree.map(lambda x: NamedSharding(mesh, P()), t)
    step, restored = m.restore(shardings=sh, treedef_like=t)
    w = restored["params"]["w"]
    assert isinstance(w, jax.Array) and w.sharding == NamedSharding(mesh, P())
    assert np.allclose(np.asarray(w), np.asarray(t["params"]["w"]))


def test_kernel_crc_impl_equivalent(tmp_path, rng):
    """crc_impl='kernel' (on-device Pallas CRC) agrees with zlib on save."""
    t = {"w": jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)}
    m1 = CheckpointManager(
        CheckpointConfig(directory=str(tmp_path / "a"), async_save=False, crc_impl="kernel")
    )
    m1.save(1, t)
    man = json.loads((tmp_path / "a" / "step_00000001" / "manifest.json").read_text())
    import zlib

    want = zlib.crc32(np.asarray(t["w"]).tobytes()) & 0xFFFFFFFF
    assert man["leaves"]["w"]["crc"] == want


def test_kernel_crc_routes_through_device(tmp_path, rng):
    """With a Device attached, kernel CRCs are engine descriptors: they agree
    with zlib AND show up in the device's submission telemetry."""
    from repro.core import make_device

    d = make_device()
    t = {"w": jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)}
    m = CheckpointManager(
        CheckpointConfig(directory=str(tmp_path / "dev"), async_save=False,
                         crc_impl="kernel"),
        device=d,
    )
    m.save(1, t)
    man = json.loads((tmp_path / "dev" / "step_00000001" / "manifest.json").read_text())
    import zlib

    want = zlib.crc32(np.asarray(t["w"]).tobytes()) & 0xFFFFFFFF
    assert man["leaves"]["w"]["crc"] == want
    # the save path reads each leaf out anyway, so the CRC rides the fused
    # copy+CRC descriptor (one launch instead of a copy pass plus a CRC pass)
    assert d.policy_stats["decisions_by_op"].get("dsa0/copy_crc", 0) >= 1


# ----------------------------------------------------------------- device stage
def _device_state(rng):
    """A seeded random state of arrays on one chip: leaves the CRC kernel
    reads in place (whole 8-row tiles) and flattened ones, float32 and
    bfloat16, with names that sort apart from tree order."""
    return {
        "params": {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
                   "wide": jnp.asarray(rng.normal(size=(16, 256)), jnp.float32),
                   "norm": jnp.asarray(rng.normal(size=(24,)), jnp.float32)},
        "exp_avg": {"w": jnp.asarray(rng.normal(size=(64, 32)), jnp.float32),
                    "b": jnp.asarray(rng.normal(size=(3,)), jnp.bfloat16)},
    }


def _host_leaves(tree):
    from repro.checkpoint.manager import _tree_flatten_with_names

    return {k: np.asarray(v) for k, v in _tree_flatten_with_names(tree)}


def test_device_stage_save_matches_the_plain_reference(rng):
    """Given a device and device arrays, a save snapshots every leaf as one
    COPY_CRC descriptor: the copies on the device and the manifest's CRCs
    match the plain reference's save leaf for leaf and CRC for CRC, and a
    resume returns what the reference's resume does."""
    from bench import ckpt_reference
    from repro.core import make_device

    d = make_device()
    m = CheckpointManager(CheckpointConfig(directory=None), device=d)
    state = _device_state(rng)
    m.save(3, state)
    copies, crcs = ckpt_reference.save(_host_leaves(state))
    snap = m.snapshot
    assert snap.step == 3 and list(snap.leaves) == list(copies)
    for k, want in copies.items():
        got = np.asarray(snap.leaves[k])
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), k
        assert snap.crcs[k] == crcs[k], k
    assert d.policy_stats["decisions_by_op"]["dsa0/copy_crc"] == len(copies)
    assert m.stats["snapshot_leaves"] == len(copies)
    assert m.stats["snapshot_bytes"] == sum(v.nbytes for v in copies.values())
    step, good, refused = m.resume()
    want_good, want_refused = ckpt_reference.resume(copies, crcs)
    assert step == 3 and refused == want_refused == []
    assert {k: np.asarray(v).tobytes() for k, v in good.items()} == {
        k: v.tobytes() for k, v in want_good.items()}


def test_resume_refuses_a_leaf_corrupted_after_the_save(rng):
    from bench import ckpt_reference
    from repro.core import make_device

    m = CheckpointManager(CheckpointConfig(directory=None), device=make_device())
    state = _device_state(rng)
    m.save(1, state)
    copies, crcs = ckpt_reference.save(_host_leaves(state))
    victim = "params/wide"
    m.snapshot.leaves[victim] = m.snapshot.leaves[victim].at[5, 7].add(1.0)
    copies[victim] = np.asarray(m.snapshot.leaves[victim])
    step, good, refused = m.resume()
    assert refused == ckpt_reference.resume(copies, crcs)[1] == [victim]
    assert m.stats["resume_refused"] == 1
    assert set(good) == set(copies) - {victim}
    for k, v in good.items():
        assert np.asarray(v).tobytes() == copies[k].tobytes(), k


def test_resume_refuses_a_leaf_whose_copy_is_gone(rng):
    """A snapshot leaf deleted after the save (as a donated step deletes a
    copy that is really the state's own buffer) is refused, not read."""
    from repro.core import make_device

    m = CheckpointManager(CheckpointConfig(directory=None), device=make_device())
    state = _device_state(rng)
    m.save(2, state)
    m.snapshot.leaves["params/w"].delete()
    step, good, refused = m.resume()
    assert step == 2 and refused == ["params/w"]
    assert set(good) == set(_host_leaves(state)) - {"params/w"}


def test_save_of_more_leaves_than_wq_slots_loses_none(rng):
    """40 leaves against one WQ of 4 slots: refused doorbells wait in the
    submit ring and land later (backpressure), and no leaf is lost."""
    from repro.core import make_device

    d = make_device(wqs_per_group=1, wq_size=4)
    m = CheckpointManager(CheckpointConfig(directory=None), device=d)
    state = {f"l{i:02d}": jnp.asarray(rng.normal(size=(8, 128)), jnp.float32) for i in range(40)}
    m.save(7, state)
    assert m.stats["queue_full_retries"] > 0
    assert len(m.snapshot.leaves) == 40
    for k, v in state.items():
        want = np.asarray(v)
        assert np.asarray(m.snapshot.leaves[k]).tobytes() == want.tobytes()
        assert m.snapshot.crcs[k] == zlib.crc32(want.tobytes())


def test_failed_save_is_never_claimed(rng, monkeypatch):
    """A leaf whose copy fails fails the save: SaveFailed, no snapshot for
    a resume to use, the failure counted; the next save is whole again."""
    from repro.checkpoint.manager import SaveFailed
    from repro.core import make_device
    from repro.kernels import ops

    m = CheckpointManager(CheckpointConfig(directory=None), device=make_device())
    state = _device_state(rng)
    m.save(1, state)
    real = ops.copy_crc

    def failing(x, **kw):
        if x.shape == (24,):
            raise RuntimeError("planted copy failure")
        return real(x, **kw)

    monkeypatch.setattr(ops, "copy_crc", failing)
    with pytest.raises(SaveFailed, match="planted copy failure"):
        m.save(2, state)
    assert m.snapshot is None and m.stats["failed_saves"] == 1
    with pytest.raises(IOError):
        m.resume()
    monkeypatch.setattr(ops, "copy_crc", real)
    m.save(3, state)
    assert m.resume()[0] == 3 and m.resume()[2] == []


def test_device_stage_persists_with_its_crcs(tmp_path, rng):
    """With a directory, the files are written from the snapshot and the
    manifest carries the snapshot's CRCs: no second CRC pass runs.  The
    snapshot was a staging copy: once written it is not kept in HBM."""
    from repro.core import make_device

    d = make_device()
    m = CheckpointManager(CheckpointConfig(directory=str(tmp_path), async_save=False,
                                           crc_impl="kernel"), device=d)
    state = _device_state(rng)
    m.save(4, state)
    man = json.loads((tmp_path / "step_00000004" / "manifest.json").read_text())
    want = _host_leaves(state)
    assert {k: e["crc"] for k, e in man["leaves"].items()} == {
        k: zlib.crc32(v.tobytes()) for k, v in want.items()}
    assert d.policy_stats["decisions_by_op"]["dsa0/copy_crc"] == len(want)
    assert "dsa0/crc32" not in d.policy_stats["decisions_by_op"]
    assert m.snapshot is None
    with pytest.raises(IOError):
        m.resume()
    step, restored = m.restore()
    assert step == 4
    for k, v in want.items():
        assert restored[k].tobytes() == v.tobytes(), k


@pytest.mark.parametrize("nbytes", [6, 4096, 4102])
def test_kernel_crc_of_host_bytes_matches_zlib(tmp_path, nbytes):
    """Host leaves CRC'd by the kernel (crc_impl='kernel'): a length that is
    not a whole number of words is not zero-padded into the CRC."""
    leaf = np.arange(nbytes, dtype=np.uint8)
    m = CheckpointManager(CheckpointConfig(directory=str(tmp_path), async_save=False,
                                           crc_impl="kernel"))
    m.save(1, {"x": leaf})
    man = json.loads((tmp_path / "step_00000001" / "manifest.json").read_text())
    assert man["leaves"]["x"]["crc"] == zlib.crc32(leaf.tobytes())
    assert m.restore()[1]["x"].tobytes() == leaf.tobytes()
