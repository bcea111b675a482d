"""Checks of the checkpoint cells: every save one ``ops.copy_crc`` a leaf.

The control: the snapshot's copy is rounded to bfloat16 precision, the
"save bf16 instead of the fp32 masters" shortcut, with the CRC of what it
kept.  The rounding is ``lax.reduce_precision``: a float32 -> bfloat16 ->
float32 convert pair may be removed as a no-op by the compiler.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import ckpt_reference
from bench.cases import Fault, bump_first
from bench.systems import ckpt as adapter
from repro.checkpoint import manager
from repro.kernels import ops

REAL_COPY_CRC = ops.copy_crc
REAL_CRC32 = ops.crc32
# the published structure (463 leaves) at widths a CPU test run holds
SMALL_WIDTHS = {"hidden_size": 64, "num_attention_heads": 2, "qk_nope_head_dim": 8,
                "qk_rope_head_dim": 8, "kv_lora_rank": 16, "v_head_dim": 8,
                "intermediate_size": 64, "moe_intermediate_size": 16, "vocab_size": 64}


def small(cfg: Dict, mix: Dict) -> Tuple[Dict, Dict]:
    """Every leaf of the cell's structure (its depth, experts held, shared
    experts and router width) at the widths of ``SMALL_WIDTHS``."""
    return dict(cfg, **SMALL_WIDTHS), dict(mix)


def altered_copy_crc(x, *, interpret=None):
    copy, crc = REAL_COPY_CRC(x, interpret=interpret)
    return bump_first(copy.reshape(1, -1)).reshape(x.shape), crc


def wrong_crc(x, *, interpret=None):
    copy, crc = REAL_COPY_CRC(x, interpret=interpret)
    return copy, crc + jnp.uint32(1)


def no_copy(x, *, interpret=None):
    """The CRC taken in place and the state's own array kept as the copy:
    the next donated step takes the snapshot with it."""
    return x, REAL_CRC32(x, interpret=interpret)


def _leave_one_out(monkeypatch):
    flatten = manager._tree_flatten_with_names
    monkeypatch.setattr(manager, "_tree_flatten_with_names", lambda tree: flatten(tree)[:-1])


FAULTS = {
    "altered": Fault(ops={"copy_crc": altered_copy_crc}),  # the snapshot differs from the state
    "wrong_crc": Fault(ops={"copy_crc": wrong_crc}),
    "left_out": Fault(patch=_leave_one_out),  # a leaf left out of every save
    "no_copy": Fault(ops={"copy_crc": no_copy}),  # the snapshot is the state itself
}


@functools.partial(jax.jit, static_argnames=("interpret",))
def bf16_copy_crc(x, *, interpret=None):
    copy = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return copy, REAL_CRC32(copy, interpret=interpret)


CONTROL = {"copy_crc": bf16_copy_crc}


def rehearse(cfg: Dict, mixes, report, shape) -> None:
    """``copy_crc`` (a save) and ``crc32`` (a resume) at every leaf shape,
    the stand-in training step over the whole state, and the check's
    programs at the largest leaf.  The state and its snapshot take
    2 x ``ckpt_reference.state_bytes``; each program's temporaries have to
    fit beside them."""
    leaves = ckpt_reference.state_leaves(cfg)
    shapes = sorted({tuple(s) for _, _, s in leaves}, key=math.prod)
    for s in shapes:
        f32 = shape(s, jnp.float32)
        report(f"copy_crc f32{list(s)}", ops.copy_crc, f32, interpret=False)
        report(f"crc32 f32{list(s)}", ops.crc32, f32, interpret=False)
    u32 = shape((), jnp.uint32)
    report(f"train step, {len(leaves)} leaves", adapter.train_step,
           [shape(tuple(s), jnp.float32) for _, _, s in leaves], u32, u32)
    big = shapes[-1]
    report(f"check leaf f32{list(big)}", adapter.mismatched,
           shape(big, jnp.float32), u32, u32, u32)
    report(f"reference leaf f32{list(big)}", adapter.reference_leaf,
           u32, u32, u32, math.prod(big))
