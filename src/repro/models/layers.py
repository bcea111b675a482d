"""Shared neural-net layers: norms, rotary embeddings (incl. M-RoPE), gated
MLPs, and memory-efficient attention.

Everything here is a pure function over explicit parameter pytrees — no
framework modules.  Attention comes in two forms:

* ``attention``       — training/prefill, online-softmax chunked over KV blocks
                        (flash-attention schedule in pure JAX; the quadratic
                        score matrix never materializes for long sequences).
* ``decode_attention`` — single-token decode against a (full or ring-buffer)
                        KV cache with explicit per-sequence length masks.

Block sizes are static python ints, so causal/window block skipping is
resolved at trace time (no dynamic control flow).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.annotate import ann

NEG_INF = -1e30


# --------------------------------------------------------------------------- norms
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight + bias).astype(dt)


# --------------------------------------------------------------------------- rope
def rope_cos_sin(
    positions: jax.Array, head_dim: int, theta: float, dtype=jnp.float32
) -> Tuple[jax.Array, jax.Array]:
    """positions [...,] -> cos/sin [..., head_dim//2]."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [B, S, H, hd]; cos/sin [B, S, hd//2] (broadcast over heads)."""
    dt = x.dtype
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(dt)


def mrope_cos_sin(
    positions_thw: jax.Array,
    head_dim: int,
    theta: float,
    sections: Tuple[int, int, int],
) -> Tuple[jax.Array, jax.Array]:
    """M-RoPE (Qwen2-VL): positions_thw [3, B, S] -> cos/sin [B, S, hd//2].

    The hd//2 frequency slots are partitioned into (t, h, w) sections; each
    section rotates by its own position stream.  Text tokens set t=h=w.
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang_all = positions_thw.astype(jnp.float32)[..., None] * freqs  # [3, B, S, half]
    pieces = []
    start = 0
    for i, sec in enumerate(sections):
        pieces.append(ang_all[i, ..., start : start + sec])
        start += sec
    ang = jnp.concatenate(pieces, axis=-1)  # [B, S, half]
    return jnp.cos(ang), jnp.sin(ang)


# --------------------------------------------------------------------------- mlp
def gated_mlp(x: jax.Array, p: dict, act: str = "silu", tp_comm: str = "auto") -> jax.Array:
    """SwiGLU/GeGLU MLP.  p = {w1 [D,F], w3 [D,F], w2 [F,D]}.

    tp_comm="manual_bf16": run the whole TP block in shard_map with an
    explicit bf16 cast on the row-parallel partial sums — GSPMD otherwise
    all-reduces the f32 matmul ACCUMULATOR, doubling wire bytes
    (EXPERIMENTS.md §Perf cell A iter 2)."""
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    if tp_comm == "manual_bf16":
        out = _tp_block_manual(x, p, fn)
        if out is not None:
            return out
    h = fn(x @ p["w1"]) * (x @ p["w3"])
    h = ann(h, "batch", None, "mlp")
    return h @ p["w2"]


def _tp_block_manual(x, p, fn):
    """Megatron-style column+row parallel MLP with bf16 wire; returns None
    when the mesh/rules context is absent or the FF dim isn't model-sharded."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.annotate import _current

    ctx = _current()
    if ctx is None:
        return None
    mesh, rules = ctx
    w1_spec = rules.spec(p["w1"].shape, (None, "mlp"))
    if w1_spec[1] is None:
        return None
    x_spec = rules.spec(x.shape, ("batch", None, None))

    def local(x_l, w1_l, w3_l, w2_l):
        h = fn(x_l @ w1_l) * (x_l @ w3_l)
        part = (h @ w2_l).astype(x_l.dtype)  # cast BEFORE the wire
        return jax.lax.psum(part, w1_spec[1])

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, w1_spec, w1_spec, P(w1_spec[1], None)),
        out_specs=x_spec, check_vma=False,
    )(x, p["w1"], p["w3"], p["w2"])


def row_parallel_out(o_flat: jax.Array, wo: jax.Array, tp_comm: str = "auto") -> jax.Array:
    """Attention output projection [B,S,H*hd] @ [H*hd,D], row-parallel with
    bf16-wire psum when tp_comm="manual_bf16" (same rationale as gated_mlp)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.annotate import _current

    ctx = _current()
    if tp_comm != "manual_bf16" or ctx is None:
        return o_flat @ wo
    mesh, rules = ctx
    wo_spec = rules.spec(wo.shape, ("qkv_flat", None))
    if wo_spec[0] is None:
        return o_flat @ wo
    o_spec = rules.spec(o_flat.shape, ("batch", None, "qkv_flat"))
    if o_spec[2] is None:
        return o_flat @ wo
    out_spec = P(o_spec[0], None, None)

    def local(o_l, w_l):
        part = (o_l @ w_l).astype(o_l.dtype)
        return jax.lax.psum(part, wo_spec[0])

    return jax.shard_map(local, mesh=mesh, in_specs=(o_spec, wo_spec),
                         out_specs=out_spec, check_vma=False)(o_flat, wo)


# --------------------------------------------------------------------------- attention
def _pick_block(seq: int, target: int = 512) -> int:
    """Largest divisor of ``seq`` that is <= target (prefers multiples of 128)."""
    best = 1
    for b in range(1, min(seq, target) + 1):
        if seq % b == 0:
            best = b
    return best


def _mask_block(
    q_pos: jax.Array,
    k_pos: jax.Array,
    *,
    causal: bool,
    window: int,
    n_meta: int,
) -> jax.Array:
    """[q_blk, kv_blk] boolean mask."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= kp <= qp
    if window > 0:
        in_window = (qp - kp) < window
        if n_meta > 0:
            in_window |= kp < n_meta  # meta tokens are always attendable
        m &= in_window
    return m


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    n_meta: int = 0,
    q_offset: int = 0,
    scale: Optional[float] = None,
    max_block: int = 512,
) -> jax.Array:
    """Chunked online-softmax attention (training / prefill).

    q [B, Sq, H, hd]; k, v [B, Skv, KV, hd] with H % KV == 0 (GQA).
    Returns [B, Sq, H, hd].  ``q_offset`` is the absolute position of q[0]
    relative to k[0] (used by enc-dec / prefix setups; 0 for self-attn).
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qg = q.reshape(B, Sq, KV, G, hd)

    # Small sequences: one dense block.
    if Sq * Skv <= 1024 * 1024:
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32))
        s *= scale
        mask = _mask_block(
            jnp.arange(Sq) + q_offset, jnp.arange(Skv), causal=causal, window=window, n_meta=n_meta
        )
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v)
        return o.reshape(B, Sq, H, hd)

    q_blk = _pick_block(Sq, max_block)
    kv_blk = _pick_block(Skv, max_block)
    n_q = Sq // q_blk

    def kv_step(carry, kv_i, qb, q_pos):
        m, l, acc = carry
        k_b = jax.lax.dynamic_slice_in_dim(k, kv_i * kv_blk, kv_blk, axis=1)
        v_b = jax.lax.dynamic_slice_in_dim(v, kv_i * kv_blk, kv_blk, axis=1)
        k_pos = kv_i * kv_blk + jnp.arange(kv_blk)
        # operands stay bf16 on the wire; the MXU accumulates in f32
        # (preferred_element_type) — halves attention HBM traffic vs
        # materializing f32 copies (EXPERIMENTS.md §Perf cell A iter 1)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qb, k_b, preferred_element_type=jnp.float32)
        s *= scale
        mask = _mask_block(q_pos, k_pos, causal=causal, window=window, n_meta=n_meta)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p.astype(v.dtype), v_b, preferred_element_type=jnp.float32
        )
        return (m_new, l_new, acc_new), None

    outs = []
    for qi in range(n_q):
        qb = jax.lax.dynamic_slice_in_dim(qg, qi * q_blk, q_blk, axis=1)
        q_pos = qi * q_blk + jnp.arange(q_blk) + q_offset
        q_end = (qi + 1) * q_blk - 1 + q_offset
        q_start = qi * q_blk + q_offset
        # static block skipping: causal upper bound and window lower bound
        kv_hi = min((q_end // kv_blk) + 1, Skv // kv_blk) if causal else Skv // kv_blk
        kv_lo = 0
        if window > 0:
            kv_lo = max(0, (q_start - window + 1) // kv_blk)
        n_meta_blocks = (n_meta + kv_blk - 1) // kv_blk if n_meta > 0 else 0
        idxs = list(range(min(n_meta_blocks, kv_lo))) + list(range(kv_lo, kv_hi))
        m0 = jnp.full((B, KV, G, q_blk), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_blk), dtype=jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_blk, hd), dtype=jnp.float32)

        step = jax.checkpoint(lambda c, i: kv_step(c, i, qb, q_pos))
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.asarray(idxs, dtype=jnp.int32))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(o.transpose(0, 3, 1, 2, 4).reshape(B, q_blk, H, hd).astype(v.dtype))
    return jnp.concatenate(outs, axis=1)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    valid_mask: jax.Array,
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token attention against a cache.

    q [B, H, hd]; k_cache/v_cache [B, S, KV, hd]; valid_mask [B, S] bool.
    """
    B, H, hd = q.shape
    _, S, KV, _ = k_cache.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg.astype(jnp.float32), k_cache.astype(jnp.float32))
    s *= scale
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(jnp.float32))
    return o.reshape(B, H, hd).astype(v_cache.dtype)


# --------------------------------------------------------------------------- flash wrapper
def _flash_call(q, k, v, causal, window, n_meta):
    """Flash kernel, shard_map'd when a mesh context is active.

    Standard TPU deployment: the kernel runs per-device on its local
    (batch x head) shard; KV stays as-sharded/replicated (GQA KV heads are
    replicated whenever KV % tp != 0, so every q-head shard has its K/V).
    Falls back to a direct call when dims don't divide.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.annotate import _current
    from repro.kernels.flash_attention import flash_attention

    ctx = _current()
    kernel = functools.partial(
        flash_attention, causal=causal, window=window, n_meta=n_meta
    )
    if ctx is None:
        return kernel(q, k, v)
    mesh, rules = ctx
    q_spec = rules.spec(q.shape, ("batch", None, "heads", None))
    kv_spec = rules.spec(k.shape, ("batch", None, "kv_heads", None))
    # local shapes must keep GQA consistent: if KV ends up sharded but heads
    # replicated (or group mismatch), fall back to the direct call
    def _size(entry):
        return rules.axis_size(entry)

    h_shard = _size(q_spec[2])
    kv_shard = _size(kv_spec[2])
    if kv_shard not in (1, h_shard):
        return kernel(q, k, v)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec, check_vma=False,
    )(q, k, v)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6)
)
def _flash_fwd_ref_bwd(q, k, v, causal, window, n_meta, q_offset):
    return _flash_call(q, k, v, causal, window, n_meta)


def _ffrb_fwd(q, k, v, causal, window, n_meta, q_offset):
    out = _flash_fwd_ref_bwd(q, k, v, causal, window, n_meta, q_offset)
    return out, (q, k, v)


def _ffrb_bwd(causal, window, n_meta, q_offset, res, g):
    q, k, v = res
    # reference bwd: recompute via the chunked-attention path and AD it.
    # (fwd + remat replays use the VMEM-resident kernel; only the true bwd
    # pays the chunked-path HBM traffic — see EXPERIMENTS.md §Perf.)
    _, vjp = jax.vjp(
        lambda q, k, v: attention(
            q, k, v, causal=causal, window=window, n_meta=n_meta, q_offset=q_offset
        ),
        q, k, v,
    )
    return vjp(g)


_flash_fwd_ref_bwd.defvjp(_ffrb_fwd, _ffrb_bwd)


def attention_trainable(
    q, k, v, *, causal: bool = True, window: int = 0, n_meta: int = 0,
    q_offset: int = 0, impl: str = "chunked",
):
    """Attention with a selectable implementation: "chunked" (pure JAX,
    baseline) or "flash" (Pallas kernel fwd, reference bwd)."""
    if impl == "flash":
        return _flash_fwd_ref_bwd(q, k, v, causal, window, n_meta, q_offset)
    return attention(q, k, v, causal=causal, window=window, n_meta=n_meta, q_offset=q_offset)


# --------------------------------------------------------------------------- qkv projection helpers
def project_qkv(x: jax.Array, p: dict, cfg, *, qk_norm_p: Optional[dict] = None):
    """x [B,S,D] -> q [B,S,H,hd], k,v [B,S,KV,hd] (+ optional per-head RMS qk-norm)."""
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    if qk_norm_p is not None:
        q = rms_norm(q, qk_norm_p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, qk_norm_p["k_norm"], cfg.norm_eps)
    q = ann(q, "batch", None, "heads", None)
    k = ann(k, "batch", None, "kv_heads", None)
    v = ann(v, "batch", None, "kv_heads", None)
    return q, k, v


def unembed(x: jax.Array, table: jax.Array, transpose: bool) -> jax.Array:
    """Logits head.  table is [V, D] if transpose (tied) else [D, V]."""
    w = table.T if transpose else table
    return (x @ w.astype(x.dtype)).astype(jnp.float32)


def cross_entropy(logits: jax.Array, labels: jax.Array, mask: jax.Array) -> jax.Array:
    """Mean CE over masked positions.  logits [B,S,V] f32, labels [B,S] i32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
