"""Flash attention (fwd) — Pallas TPU kernel.

Motivation (EXPERIMENTS.md §Perf): the baseline pure-JAX chunked attention
materializes every [q_blk x kv_blk] score block through HBM at XLA fusion
granularity; the dry-run roofline shows this score traffic DOMINATING the
memory term for train/prefill cells.  This kernel keeps scores, softmax
state, and the output accumulator in VMEM scratch — per-tile HBM traffic
drops to the q/k/v reads + o write.

Layout: q [BH, Sq, hd], k/v [BKV, Skv, hd] (GQA: kv row = (bh // H) * KV +
(bh % H) // G resolved in the BlockSpec index_map).  Grid (BH, n_q, n_kv)
with the kv axis innermost (sequential on TPU) accumulating into VMEM
scratch; causal/window masking is positional, supporting meta-token prefixes
(hymba) via ``n_meta``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ops import _interpret_default

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal,
            window, n_meta, q_blk, kv_blk, n_kv):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [q_blk, hd]
    k = k_ref[0].astype(jnp.float32)  # [kv_blk, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [q_blk, kv_blk]

    q_pos = qi * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 0)
    k_pos = ki * kv_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)
    mask = jnp.ones((q_blk, kv_blk), dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        in_win = (q_pos - k_pos) < window
        if n_meta > 0:
            in_win |= k_pos < n_meta
        mask &= in_win
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[0]
    ).astype(jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, Sq, H, hd]
    k: jax.Array,  # [B, Skv, KV, hd]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    n_meta: int = 0,
    scale: Optional[float] = None,
    q_blk: int = 512,
    kv_blk: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Drop-in replacement for models.layers.attention (fwd)."""
    if interpret is None:
        interpret = _interpret_default()
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    while Sq % q_blk:
        q_blk //= 2
    while Skv % kv_blk:
        kv_blk //= 2
    n_q, n_kv = Sq // q_blk, Skv // kv_blk

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, Skv, hd)

    def kv_row(bh, qi, ki):
        return ((bh // H) * KV + (bh % H) // G, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window, n_meta=n_meta,
            q_blk=q_blk, kv_blk=kv_blk, n_kv=n_kv,
        ),
        grid=(B * H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, q_blk, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, kv_blk, hd), kv_row),
            pl.BlockSpec((1, kv_blk, hd), kv_row),
        ],
        out_specs=pl.BlockSpec((1, q_blk, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
