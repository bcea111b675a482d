"""Asynchronous incremental checkpointing — the paper's Delta Record + CRC +
Dualcast ops as a fault-tolerance subsystem (DESIGN.md §7).

Layout (one directory per save):

  <dir>/step_00000010/            full snapshot
      manifest.json               {step, kind, leaves: {key: {mode, shape,
                                   dtype, crc, nbytes, base_step}}}
      <key>.bin                   raw little-endian bytes
  <dir>/step_00000012/            delta save (vs. the last full snapshot)
      manifest.json
      <key>.delta.npz             offsets[int32] + data[uint32] word granules

Semantics mirror DSA:
  * Create Delta Record with a capacity cap — when a leaf's delta overflows
    (> delta_cap_frac of its words), the completion status is OVERFLOW and
    the manager falls back to a full copy of that leaf (exactly how software
    must handle DSA's delta overflow status).
  * CRC32 per shard file, verified on restore; torn/corrupt saves are
    detected and the manager falls back to the previous valid manifest.
  * replicas=2 fans each shard out twice (Dualcast) for rack-failure
    tolerance.
  * Saves run on a background thread, overlapped with the next train step
    (G2: async always); ``wait()`` joins the in-flight save.

Device stage (in-memory checkpoint, as ByteCheckpoint and GEMINI keep
one): given a ``device`` and a state whose leaves are arrays on one chip,
``save`` first snapshots every leaf as one COPY_CRC descriptor through
``Device.submit_ring``.  The copies stay on the device as the in-memory
checkpoint, and every CRC comes to the host in one transfer after one
``wait_all``; ``save`` returns only then.  The file path above then
persists from the snapshot and reuses its CRCs.  With a ``directory``
the snapshot is a staging copy: the writer reads each leaf out to the
host and drops it, so HBM holds a second copy of the state only from the
save until its files are written.  With ``directory=None`` the snapshot
is the checkpoint and stays until the next save.  ``resume`` checks every
leaf of the snapshot with a CRC32 descriptor and refuses a leaf whose CRC
differs from the manifest's, or whose copy is gone.  A save that fails
raises ``SaveFailed`` and leaves no snapshot: the previous one is
released as the save begins, since the state and two snapshots of it need
not fit the chip together.  So unlike ByteCheckpoint and GEMINI, which
keep the last complete checkpoint, a failed save leaves nothing in memory
to resume from; with a directory the last complete files remain.

Elastic restore: checkpoints store *logical* arrays (no device layout), so
restore onto any mesh re-shards via ``jax.device_put`` with the target
shardings (tested in tests/test_checkpoint.py).
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import threading
import time
import zlib
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)
from repro.core.descriptor import OpType, Status, WorkDescriptor

SUBMIT_CHUNK = 16  # descriptors a doorbell of the device stage carries at most


class SaveFailed(RuntimeError):
    """A save whose device stage did not complete: no manifest claims it."""


def _tree_flatten_with_names(tree) -> List[Tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        name = "/".join(
            str(p.key) if hasattr(p, "key") else str(getattr(p, "idx", p)) for p in path
        )
        out.append((name or "leaf", leaf))
    return out


def _np(leaf) -> np.ndarray:
    return np.asarray(jax.device_get(leaf))


def _on_one_chip(leaf) -> bool:
    return isinstance(leaf, jax.Array) and len(leaf.devices()) == 1


def _host_words(data: bytes) -> jax.Array:
    """``data`` uploaded for a CRC: u32 words, or bytes where the length is
    not a whole number of words (zero padding would change the CRC)."""
    if len(data) % 4:
        return jnp.asarray(np.frombuffer(data, np.uint8))
    return jnp.asarray(np.frombuffer(data, "<u4"))


def _u32_view(a: np.ndarray) -> np.ndarray:
    b = a.tobytes()
    pad = (-len(b)) % 4
    if pad:
        b = b + b"\0" * pad
    return np.frombuffer(b, dtype="<u4").copy()


@dataclasses.dataclass
class Snapshot:
    """The in-memory checkpoint of one save: each leaf's copy on the device
    and its zlib CRC32 (the manifest), by leaf name in tree order."""

    step: int
    leaves: Dict[str, jax.Array]
    crcs: Dict[str, int]


@dataclasses.dataclass
class CheckpointConfig:
    directory: Optional[str]  # None: in-memory snapshots only (device stage)
    full_every: int = 4  # every k-th save is a full snapshot
    delta_cap_frac: float = 0.25  # overflow threshold (fraction of words)
    replicas: int = 1  # 2 => dualcast to <dir>-replica
    verify_crc: bool = True
    async_save: bool = True
    keep: int = 8  # retained saves
    crc_impl: str = "zlib"  # "zlib" (host) | "kernel" (on-device Pallas CRC)


class CheckpointManager:
    def __init__(self, config: CheckpointConfig, device=None):
        self.cfg = config
        self.dir = None if config.directory is None else Path(config.directory)
        if self.dir is not None:
            self.dir.mkdir(parents=True, exist_ok=True)
        self.replica_dir = (Path(str(self.dir) + "-replica")
                            if self.dir is not None and config.replicas > 1 else None)
        if self.replica_dir:
            self.replica_dir.mkdir(parents=True, exist_ok=True)
        self.device = device
        self._thread: Optional[threading.Thread] = None
        self._save_count = 0
        self._base: Optional[Dict[str, np.ndarray]] = None  # last full snapshot (u32 views)
        self._base_step: Optional[int] = None
        self.snapshot: Optional[Snapshot] = None  # the device stage's last save
        self.stats = {"full_leaves": 0, "delta_leaves": 0, "delta_overflows": 0,
                      "bytes_written": 0, "bytes_saved_by_delta": 0,
                      # device stage: leaves and bytes snapshotted, doorbells
                      # a full WQ refused (kept in the ring and landed
                      # later), saves that failed, leaves a resume refused
                      "snapshot_leaves": 0, "snapshot_bytes": 0,
                      "queue_full_retries": 0, "failed_saves": 0,
                      "resume_refused": 0}

    # ------------------------------------------------------------------ device stage
    def _record(self, name: str, t0: float) -> float:
        t1 = time.perf_counter()
        tracer = getattr(self.device, "tracer", None)
        if tracer is not None:
            tracer.record(name, t0, t1)
        return t1

    def _submit_all(self, op: OpType, arrays: Sequence[jax.Array]) -> Tuple[Any, List[Any]]:
        """One ``op`` descriptor per array through a submit ring: a doorbell
        a full WQ refuses stays in the ring and lands on a later kick (every
        wait pumps one), so no descriptor is lost to ``QueueFull``.  Returns
        the ring, which ``_gather`` keeps alive until all have landed (the
        device holds rings weakly), and the futures."""
        # a doorbell lands whole or not at all: half the smallest WQ, so the
        # next one can land while the PEs drain the last
        wq = min(w.size for e in self.device.engines for g in e.config.groups for w in g.wqs)
        ring = self.device.submit_ring(depth=max(len(arrays), 1),
                                       chunk=min(SUBMIT_CHUNK, max(wq // 2, 1)),
                                       producer="checkpoint")
        futs = [ring.add(WorkDescriptor(op=op, src=a)) for a in arrays]
        ring.flush()
        return ring, futs

    def _gather(self, ring: Any, futs: List[Any]) -> None:
        self.device.wait_all(futs)
        self.stats["queue_full_retries"] += ring.stats["retries"]

    @staticmethod
    def _to_host(crcs: Sequence[jax.Array]) -> List[int]:
        """Device CRC scalars to ints in one transfer."""
        return [int(c) for c in np.asarray(jnp.stack(crcs))] if crcs else []

    def _device_crcs(self, arrays: Sequence[jax.Array]) -> List[int]:
        """CRC32 of each array: CRC32 descriptors through the device (the
        kernel directly without one), gathered once, one transfer."""
        if self.device is None:
            from repro.kernels import ops as kops

            return self._to_host([kops.crc32(a) for a in arrays])
        ring, futs = self._submit_all(OpType.CRC32, arrays)
        self._gather(ring, futs)
        return self._to_host([f.result() for f in futs])

    def _snapshot(self, step: int, named: List[Tuple[str, jax.Array]]) -> Snapshot:
        """The device stage of a save: spans ``ckpt.submit`` (descriptors of
        every leaf made and submitted) and ``ckpt.gather`` (every completion
        and the CRCs' transfer)."""
        t0 = time.perf_counter()
        old, self.snapshot = self.snapshot, None  # overwritten from here on
        if old is not None:
            for leaf in old.leaves.values():
                leaf.delete()
        try:
            ring, futs = self._submit_all(OpType.COPY_CRC, [v for _, v in named])
            t1 = self._record("ckpt.submit", t0)
            self._gather(ring, futs)
            failed = [(k, f.record.error) for (k, _), f in zip(named, futs)
                      if f.record.status == Status.ERROR]
            if failed:
                for f in futs:
                    if f.record.status != Status.ERROR:
                        f.result()[0].delete()
                raise SaveFailed(f"step {step}: {len(failed)} leaves failed their copy "
                                 f"and CRC, the first {failed[0][0]}: {failed[0][1]}")
            results = [f.result() for f in futs]
            crcs = self._to_host([r[1] for r in results])
            self._record("ckpt.gather", t1)
        except Exception:
            self.stats["failed_saves"] += 1
            raise
        snap = Snapshot(step, {k: r[0] for (k, _), r in zip(named, results)},
                        {k: c for (k, _), c in zip(named, crcs)})
        self.stats["snapshot_leaves"] += len(named)
        self.stats["snapshot_bytes"] += sum(int(v.nbytes) for _, v in named)
        self.snapshot = snap
        return snap

    def resume(self) -> Tuple[int, Dict[str, jax.Array], List[str]]:
        """Check the in-memory snapshot: one CRC32 descriptor a leaf, gathered
        once (span ``ckpt.verify``).  Returns (step, {name: leaf} of every
        leaf whose CRC matches the manifest, names of the leaves refused: a
        CRC that differs, or a copy deleted since the save).  The leaves are
        the snapshot's own arrays, valid until the next save.  Raises
        IOError when no save completed since the last one began."""
        snap = self.snapshot
        if snap is None or self.device is None:
            raise IOError("no in-memory snapshot to resume from")
        t0 = time.perf_counter()
        kept = [k for k, v in snap.leaves.items() if not v.is_deleted()]
        crcs = dict(zip(kept, self._device_crcs([snap.leaves[k] for k in kept])))
        refused = [k for k in snap.leaves if crcs.get(k) != snap.crcs[k]]
        self.stats["resume_refused"] += len(refused)
        self._record("ckpt.verify", t0)
        good = {k: snap.leaves[k] for k in kept if crcs[k] == snap.crcs[k]}
        return snap.step, good, refused

    # ------------------------------------------------------------------ crc
    def _crc(self, data: bytes) -> int:
        if self.cfg.crc_impl == "kernel":
            return self._device_crcs([_host_words(data)])[0]
        return zlib.crc32(data) & 0xFFFFFFFF

    # ------------------------------------------------------------------ save
    def save(self, step: int, tree, *, force_full: bool = False):
        """Save ``tree`` at ``step``.  With a device and every leaf an array
        on one chip, the device stage snapshots it first (raising
        ``SaveFailed`` if it cannot) and the files, if any, are written from
        the snapshot with its CRCs, releasing it; otherwise the leaves are
        read out to the host and CRC'd there (``crc_impl``)."""
        self.wait()  # one in-flight save at a time
        named = _tree_flatten_with_names(tree)
        if self.device is not None and named and all(_on_one_chip(v) for _, v in named):
            snap = self._snapshot(step, named)
            if self.dir is None:
                return
            # a staging copy: the writer holds its only references, reads it
            # out and drops it leaf by leaf; no resume is made from memory
            self.snapshot = None
            crcs: Optional[Dict[str, int]] = snap.crcs
            source = list(snap.leaves.items())
            del snap
        elif self.dir is None:
            raise ValueError("a manager without a directory keeps in-memory snapshots "
                             "only: give it a device and a state of arrays on one chip")
        else:
            crcs = None
            source = [(k, _np(v)) for k, v in named]
        is_full = force_full or self._base is None or (self._save_count % self.cfg.full_every == 0)
        self._save_count += 1

        def read_out():
            # each leaf leaves ``source`` as it is read, so a staging copy's
            # HBM is freed as soon as its bytes are on the host
            while source:
                k, v = source.pop(0)
                yield k, _np(v)

        def work():
            self._write(step, read_out(), is_full, crcs)

        if self.cfg.async_save:
            self._thread = threading.Thread(target=work, daemon=True)
            self._thread.start()
        else:
            work()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _write(self, step: int, leaves, is_full: bool,
               crcs: Optional[Dict[str, int]] = None):
        """Write one save's files; ``crcs`` are the leaves' CRCs where the
        device stage took them already."""
        tmp = self.dir / f".tmp_step_{step:08d}"
        final = self.dir / f"step_{step:08d}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        manifest: Dict[str, Any] = {
            "step": step,
            "kind": "full" if is_full else "delta",
            "base_step": None if is_full else self._base_step,
            "leaves": {},
        }
        new_base: Dict[str, np.ndarray] = {}
        # kernel CRCs of host bytes are taken together at the end, through
        # the device where there is one, and come back in one transfer
        pending: List[Tuple[Dict[str, Any], str, bytes]] = []

        def put_crc(entry: Dict[str, Any], field: str, data: bytes, key: Optional[str] = None):
            if crcs is not None and key is not None:
                entry[field] = crcs[key]
            elif self.cfg.crc_impl == "kernel":
                pending.append((entry, field, data))
            else:
                entry[field] = zlib.crc32(data) & 0xFFFFFFFF

        for key, arr in leaves:
            fn = key.replace("/", "__")
            words = _u32_view(arr)
            entry: Dict[str, Any] = {
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "nbytes": int(arr.nbytes),
            }
            if is_full or key not in (self._base or {}):
                data = arr.tobytes()
                (tmp / f"{fn}.bin").write_bytes(data)
                entry["mode"] = "full"
                put_crc(entry, "crc", data, key)
                self.stats["full_leaves"] += 1
                self.stats["bytes_written"] += len(data)
                new_base[key] = words
            else:
                base = self._base[key]
                cap = max(int(len(words) * self.cfg.delta_cap_frac), 16)
                diff = np.nonzero(words != base)[0]
                if len(diff) == 0:
                    entry["mode"] = "same"
                    put_crc(entry, "crc", arr.tobytes(), key)
                    self.stats["bytes_saved_by_delta"] += arr.nbytes
                elif len(diff) > cap:
                    # DSA delta-overflow status -> fall back to full copy
                    data = arr.tobytes()
                    (tmp / f"{fn}.bin").write_bytes(data)
                    entry["mode"] = "full"
                    put_crc(entry, "crc", data, key)
                    self.stats["delta_overflows"] += 1
                    self.stats["bytes_written"] += len(data)
                else:
                    offs = diff.astype(np.int32)
                    vals = words[diff]
                    payload = offs.tobytes() + vals.tobytes()
                    np.savez(tmp / f"{fn}.delta.npz", offsets=offs, data=vals)
                    entry["mode"] = "delta"
                    entry["count"] = int(len(diff))
                    put_crc(entry, "crc", arr.tobytes(), key)  # crc of FINAL contents
                    put_crc(entry, "payload_crc", payload)
                    self.stats["delta_leaves"] += 1
                    self.stats["bytes_written"] += len(payload)
                    self.stats["bytes_saved_by_delta"] += arr.nbytes - len(payload)
            manifest["leaves"][key] = entry
        if pending:
            values = self._device_crcs([_host_words(d) for _, _, d in pending])
            for (entry, field, _), value in zip(pending, values):
                entry[field] = value
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)  # atomic publish
        if self.replica_dir is not None:  # dualcast fan-out
            rep = self.replica_dir / final.name
            if rep.exists():
                shutil.rmtree(rep)
            shutil.copytree(final, rep)
        if is_full:
            self._base = new_base
            self._base_step = step
        self._gc()

    def _gc(self):
        steps = self.all_steps()
        # never drop the full snapshots that live deltas depend on
        needed = set()
        for s in steps[-self.cfg.keep:]:
            m = self._manifest(s)
            if m and m.get("base_step") is not None:
                needed.add(m["base_step"])
        for s in steps[: -self.cfg.keep]:
            if s not in needed:
                shutil.rmtree(self.dir / f"step_{s:08d}", ignore_errors=True)

    # ------------------------------------------------------------------ restore
    def all_steps(self) -> List[int]:
        if self.dir is None:
            return []
        return sorted(
            int(p.name.split("_")[1]) for p in self.dir.glob("step_*") if p.is_dir()
        )

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def _manifest(self, step: int, directory: Optional[Path] = None) -> Optional[dict]:
        p = (directory or self.dir) / f"step_{step:08d}" / "manifest.json"
        if not p.exists():
            return None
        try:
            return json.loads(p.read_text())
        except json.JSONDecodeError:
            return None

    def _load_leaf_full(self, step: int, key: str, entry: dict, directory: Path) -> np.ndarray:
        fn = key.replace("/", "__")
        data = (directory / f"step_{step:08d}" / f"{fn}.bin").read_bytes()
        if self.cfg.verify_crc and self._crc(data) != entry["crc"]:
            raise IOError(f"CRC mismatch for {key} at step {step}")
        return np.frombuffer(data, dtype=entry["dtype"]).reshape(entry["shape"]).copy()

    def restore(self, step: Optional[int] = None, *, shardings=None, treedef_like=None):
        """Returns (step, tree-of-numpy | tree-of-jax.Array if shardings given).

        Falls back step-by-step past CRC-corrupt saves (replica dir tried
        first when configured)."""
        self.wait()
        candidates = self.all_steps()
        if step is not None:
            candidates = [s for s in candidates if s <= step]
        for s in reversed(candidates):
            try:
                tree = self._restore_step(s)
                if shardings is not None:
                    named = dict(_tree_flatten_with_names(shardings))
                    tree = {
                        k: jax.device_put(v, named[k]) if k in named else v
                        for k, v in tree.items()
                    }
                if treedef_like is not None:
                    tree = self._unflatten_like(treedef_like, tree)
                return s, tree
            except (IOError, FileNotFoundError, KeyError) as e:
                print(f"[checkpoint] step {s} unusable ({e}); falling back")
        raise FileNotFoundError(f"no restorable checkpoint in {self.dir}")

    def _restore_step(self, step: int) -> Dict[str, np.ndarray]:
        for directory in filter(None, [self.dir, self.replica_dir]):
            m = self._manifest(step, directory)
            if m is None:
                continue
            try:
                return self._materialize(m, step, directory)
            except IOError:
                continue  # try replica
        raise IOError(f"step {step}: no valid manifest/replica")

    def _materialize(self, manifest: dict, step: int, directory: Path) -> Dict[str, np.ndarray]:
        base_step = manifest.get("base_step")
        base_manifest = self._manifest(base_step, directory) if base_step is not None else None
        out: Dict[str, np.ndarray] = {}
        for key, entry in manifest["leaves"].items():
            mode = entry["mode"]
            if mode == "full":
                out[key] = self._load_leaf_full(step, key, entry, directory)
            elif mode in ("same", "delta"):
                if base_manifest is None:
                    raise IOError(f"delta save {step} missing base {base_step}")
                arr = self._load_leaf_full(base_step, key, base_manifest["leaves"][key], directory)
                if mode == "delta":
                    fn = key.replace("/", "__")
                    z = np.load(directory / f"step_{step:08d}" / f"{fn}.delta.npz")
                    words = _u32_view(arr)
                    words[z["offsets"]] = z["data"]  # Apply Delta Record
                    arr = (
                        np.frombuffer(words.tobytes()[: entry["nbytes"]], dtype=entry["dtype"])
                        .reshape(entry["shape"]).copy()
                    )
                if self.cfg.verify_crc and self._crc(arr.tobytes()) != entry["crc"]:
                    raise IOError(f"CRC mismatch after delta-apply for {key} at {step}")
                out[key] = arr
            else:
                raise IOError(f"unknown mode {mode}")
        return out

    @staticmethod
    def _unflatten_like(like, named: Dict[str, np.ndarray]):
        names = [k for k, _ in _tree_flatten_with_names(like)]
        leaves = [named[k] for k in names]
        treedef = jax.tree_util.tree_structure(like)
        return jax.tree_util.tree_unflatten(treedef, leaves)
