"""A training job's in-memory checkpoints: the driven entry is
``CheckpointManager.save`` on a manager built with ``device=make_device()``
and no directory, so every save is one COPY_CRC descriptor a leaf, its
copies kept on the device as the snapshot and its CRCs in the manifest.

The state is the chip's share of ``ckpt_reference.state_leaves(cfg)``,
float32 on the device.  Each iteration of the closed loop runs one
stand-in training step, a jitted program that rewrites every leaf from
(seed, leaf, step) in the old leaf's buffer (donated), awaits it, then
saves the state; the sample is the save's stall, from the call to its
return.  Leaf ``l`` at step ``k`` holds ``ckpt_reference.leaf_bits(seed,
l, k)``.  After the window one more donated step runs, as the window's
next would, so that a snapshot sharing the state's buffers is lost as it
would be in training; then every leaf of the last snapshot, every CRC of
the last manifest and a seeded sample of the earlier manifests' CRCs, and
a resume from the last snapshot are compared with it.
"""
from __future__ import annotations

import math
import time
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import ckpt_reference, reference, traffic
from bench.systems import ckpt_bytes
from repro.checkpoint.manager import CheckpointConfig, CheckpointManager, SaveFailed
from repro.core import make_device
from repro.obs.trace import TraceConfig

FAILED_SAVE_S = 60.0  # the stall a failed save counts as


def _leaf(sw, leaf, step, shape):
    bits = ckpt_reference.leaf_bits(sw, leaf, step, math.prod(shape), xp=jnp)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).reshape(shape)


def _train_step(state, sw, step):
    """The stand-in training step: every word of every leaf changes, written
    where the leaf was (``state`` is donated)."""
    return [_leaf(sw, jnp.uint32(i), step, x.shape) for i, x in enumerate(state)]


def _mismatched(leaf, sw, leaf_id, step):
    """Words of ``leaf`` that differ from the reference's leaf at ``step``."""
    want = ckpt_reference.leaf_bits(sw, leaf_id, step, leaf.size, xp=jnp)
    return jnp.sum(jax.lax.bitcast_convert_type(leaf, jnp.uint32).reshape(-1) != want)


def _reference_leaf(sw, leaf_id, step, n):
    return ckpt_reference.leaf_bits(sw, leaf_id, step, n, xp=jnp)


# keep_unused: the old state is not read, but it has to be an argument for
# its donated buffers to hold the new leaves (else state + snapshot + new
# state would not fit the chip)
train_step = jax.jit(_train_step, donate_argnums=(0,), keep_unused=True)
mismatched = jax.jit(_mismatched)
reference_leaf = jax.jit(_reference_leaf, static_argnums=(3,))


class System:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, traced: bool):
        if cfg["dtype"] != "float32":
            raise ValueError(f"the state is float32 here, not {cfg['dtype']}")
        if cfg["snapshots_kept"] != 1:
            raise ValueError("the manager keeps one snapshot, released as the next save begins")
        self.seed = seed
        self.sw = reference.seed_word(seed)
        self.sample = int(mix["checks"]["earlier_manifests_sampled"])
        leaves = ckpt_reference.state_leaves(cfg)
        self.names = [f"{g}/{n}" for g, n, _ in leaves]
        self.shapes = [tuple(s) for _, _, s in leaves]
        self.state_bytes = ckpt_reference.state_bytes(cfg)
        self.dev = make_device(
            trace=TraceConfig(rate=1.0, capacity=1 << 16) if traced else None)
        self.tracer = self.dev.tracer
        self.mgr = CheckpointManager(CheckpointConfig(directory=None), device=self.dev)
        self.manifests: List[Tuple[int, Dict[str, int]]] = []
        sw = jnp.uint32(self.sw)
        self.step = 0
        # the first state, step 0, written into zeroed leaves by the step program
        self.state = train_step([jnp.zeros(s, jnp.float32) for s in self.shapes],
                                sw, jnp.uint32(0))
        # the step above and this save compile every program the window runs
        self._save()

    def _tree(self) -> Dict[str, Dict[str, jax.Array]]:
        tree: Dict[str, Dict[str, jax.Array]] = {}
        for name, leaf in zip(self.names, self.state):
            group, tensor = name.split("/", 1)
            tree.setdefault(group, {})[tensor] = leaf
        return tree

    def _save(self) -> None:
        self.mgr.save(self.step, self._tree())
        self.manifests.append((self.step, dict(self.mgr.snapshot.crcs)))

    def window(self, seconds: float, spans, run) -> None:
        sw = jnp.uint32(self.sw)
        saves = attempts = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.step += 1
            with spans.span("step"):
                self.state = train_step(self.state, sw, jnp.uint32(self.step))
                jax.block_until_ready(self.state)
            attempts += 1
            t = time.perf_counter()
            try:
                with spans.span("save"):
                    self._save()
            except SaveFailed:
                run.latencies_s.append(FAILED_SAVE_S)
                continue
            run.latencies_s.append(time.perf_counter() - t)
            saves += 1
        run.window_s = time.perf_counter() - t0
        run.attempted = attempts
        run.failed = attempts - saves
        run.bytes_done = saves * self.state_bytes
        run.kernel_bytes["copy_crc"] = ckpt_bytes.copy_crc(saves * self.state_bytes)

    # ------------------------------------------------------------------ checks
    def _bad_leaves(self, leaves: Dict[str, jax.Array], step: int) -> int:
        """Leaves of ``leaves`` missing, deleted or not bit-exact with the
        reference's state at ``step``, compared on the device, one transfer."""
        sw = jnp.uint32(self.sw)
        live = [(i, n) for i, n in enumerate(self.names)
                if n in leaves and not leaves[n].is_deleted()]
        counts = [mismatched(leaves[n], sw, jnp.uint32(i), jnp.uint32(step)) for i, n in live]
        bad = len(self.names) - len(live)
        return bad + int(np.count_nonzero(np.asarray(jnp.stack(counts)))) if counts else bad

    def _bad_crcs(self) -> int:
        """CRCs of the last manifest, and of ``self.sample`` seeded leaves of
        each earlier one, that are missing or differ from zlib of the
        reference's bytes (made on the device, CRC'd on the host)."""
        if not self.manifests:
            return len(self.names)
        pick = traffic.rng(self.seed, "ckpt.manifests")
        sw = jnp.uint32(self.sw)
        bad = 0
        for k, (step, crcs) in enumerate(self.manifests):
            last = k == len(self.manifests) - 1
            ids = (range(len(self.names)) if last
                   else pick.choice(len(self.names), self.sample, replace=False))
            for i in ids:
                want = np.asarray(reference_leaf(sw, jnp.uint32(i), jnp.uint32(step),
                                      math.prod(self.shapes[i])))
                bad += crcs.get(self.names[i]) != zlib.crc32(memoryview(want))
        return bad

    def check(self) -> Dict[str, Tuple[float, float]]:
        self.step += 1
        self.state = train_step(self.state, jnp.uint32(self.sw), jnp.uint32(self.step))
        jax.block_until_ready(self.state)
        snap = self.mgr.snapshot
        last = self.manifests[-1][0] if self.manifests else -1
        if snap is None or snap.step != last:
            bad_leaves = resume_errors = len(self.names)
        else:
            bad_leaves = self._bad_leaves(snap.leaves, snap.step)
            step, good, _refused = self.mgr.resume()  # a refused leaf is missing from good
            resume_errors = self._bad_leaves(good, step) + (step != snap.step)
        return {"bad_leaves": (bad_leaves, 0), "bad_crcs": (self._bad_crcs(), 0),
                "resume_errors": (resume_errors, 0)}
