"""Mixture-of-experts block: top-k router, shared experts, and two dispatch
strategies:

* ``dense``  — one-hot einsum dispatch (GSPMD-friendly baseline; experts are
               expert-parallel over the ``model`` axis, tokens all-gather).
* ``a2a``    — shard_map all-to-all dispatch (the beyond-paper optimized path;
               see EXPERIMENTS.md §Perf).

Router follows deepseek-moe (softmax gate over routed experts, top-k with
normalized weights, aux load-balancing loss) and degenerates to switch-style
top-1 for llama4-maverick.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.distributed.annotate import ann


def router_topk(
    x: jax.Array, w_router: jax.Array, cfg: MoEConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [T, D] -> (weights [T, k], idx [T, k], aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.top_k > 1:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss.
    E = w_router.shape[-1]
    me = probs.mean(axis=0)  # mean router prob per expert
    onehot = jax.nn.one_hot(idx[:, 0], E)
    ce = onehot.mean(axis=0)  # fraction of tokens (by top-1) per expert
    aux = (me * ce).sum() * E * cfg.aux_loss_coef
    return weights, idx, aux


def _expert_ffn(h: jax.Array, w1, w3, w2, act) -> jax.Array:
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    return (fn(h @ w1) * (h @ w3)) @ w2


def moe_block(
    x: jax.Array,
    p: dict,
    cfg: MoEConfig,
    act: str = "silu",
    dispatch: str = "dense",
    mesh=None,
) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] -> (y [B, S, D], aux_loss).

    p = {router [D,E], w1/w3 [E,D,F], w2 [E,F,D],
         shared_w1/shared_w3 [D, F*ns], shared_w2 [F*ns, D] (if shared)}
    """
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    weights, idx, aux = router_topk(xt, p["router"], cfg)

    if dispatch == "a2a" and mesh is not None and "model" in mesh.axis_names:
        y = _moe_a2a(xt, weights, idx, p, cfg, act, mesh)
    else:
        y = _moe_dense(xt, weights, idx, p, cfg, act)

    if cfg.num_shared_experts > 0:
        fn = jax.nn.silu if act == "silu" else jax.nn.gelu
        sh = fn(xt @ p["shared_w1"]) * (xt @ p["shared_w3"])
        sh = ann(sh, "batch", "mlp")
        y = y + sh @ p["shared_w2"]
    return y.reshape(B, S, D), aux


def _moe_dense(xt, weights, idx, p, cfg: MoEConfig, act) -> jax.Array:
    """Capacity-based scatter/gather dispatch (GSPMD baseline).

    Tokens are scattered into per-expert buckets [E, C, D] (C from the
    capacity factor), expert FFNs run as one grouped einsum with the
    expert dim sharded over "model" (EP), and results gather back.
    Overflow tokens beyond capacity are dropped (standard switch behavior).
    """
    T, D = xt.shape
    E, k = cfg.num_experts, cfg.top_k
    cap = max(int(cfg.capacity_factor * k * T / E), 1)

    flat_e = idx.reshape(-1)  # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based position within expert
    pos = pos.sum(-1) - 1  # [T*k]
    keep = pos < cap
    pos_c = jnp.where(keep, pos, 0)
    src_tok = jnp.repeat(jnp.arange(T), k)

    buckets = jnp.zeros((E, cap, D), dtype=xt.dtype)
    buckets = buckets.at[flat_e, pos_c].add(jnp.where(keep[:, None], xt[src_tok], 0))
    buckets = ann(buckets, "expert", None, None)

    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    hh = fn(jnp.einsum("ecd,edf->ecf", buckets, p["w1"])) * jnp.einsum(
        "ecd,edf->ecf", buckets, p["w3"]
    )
    hh = ann(hh, "expert", None, "mlp")
    out = jnp.einsum("ecf,efd->ecd", hh, p["w2"])  # [E, cap, D]
    out = ann(out, "expert", None, None)

    gathered = out[flat_e, pos_c]  # [T*k, D]
    gathered = jnp.where(keep[:, None], gathered, 0)
    wflat = weights.reshape(-1, 1).astype(gathered.dtype)
    y = jnp.zeros_like(xt).at[src_tok].add(gathered * wflat)
    return y


def _moe_a2a(xt, weights, idx, p, cfg: MoEConfig, act, mesh) -> jax.Array:
    """shard_map expert-parallel dispatch (the beyond-paper optimized path;
    EXPERIMENTS.md §Perf cell B).

    Tokens are sharded over the data axes and REPLICATED over "model";
    experts are sharded over "model".  Each model rank therefore already
    holds every token of its data shard: it builds buckets for its LOCAL
    expert group only, runs those experts, scatters partial outputs back to
    token positions, and a single activation-sized psum over "model"
    combines the groups.  Collective bytes scale with tokens_local x D —
    never with the full [T, D] batch (dense-dispatch baseline) and never
    with expert weights (FSDP gathers)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.annotate import _current

    E = cfg.num_experts
    tp = mesh.shape["model"]
    e_local = E // tp

    # resolve shardings from the active rules so the shard_map keeps every
    # weight dim exactly where the param sharding put it (no hidden gathers):
    # tokens follow the "batch" rule; expert FF may be TP'd over data (the
    # llama4 decode scheme — see EXPERIMENTS.md §Perf cell C).
    ctx = _current()
    if ctx is not None:
        _, rules = ctx
        tok_spec = rules.spec(xt.shape, ("batch", None))
        w1_spec = rules.spec(p["w1"].shape[-3:], ("expert", "fsdp", "expert_ff"))
        w2_spec = rules.spec(p["w2"].shape[-3:], ("expert", "expert_ff", "fsdp"))
        # the local einsums contract the full d_model: an FSDP shard on D
        # must be gathered at the shard_map boundary (that cost is why the
        # optimized llama4 serving config disables fsdp in favor of
        # expert_ff TP — EXPERIMENTS.md §Perf cell C)
        w1_spec = P(w1_spec[0], None, w1_spec[2])
        w2_spec = P(w2_spec[0], w2_spec[1], None)
    else:
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        tok_spec = P(data_axes if data_axes else None, None)
        w1_spec = P("model", None, None)
        w2_spec = P("model", None, None)

    def _axes(entry):
        return () if entry is None else ((entry,) if isinstance(entry, str) else tuple(entry))

    tok_axes = _axes(tok_spec[0])
    ff_axes = _axes(w1_spec[2])  # axes sharding the expert FF dim (TP-within-expert)
    if set(ff_axes) & set(tok_axes):
        # FF-TP over an axis that also shards tokens would mix different
        # tokens' partial sums.  Replicate the tokens over those axes
        # instead (cheap at decode batch sizes — this is the llama4 serving
        # scheme: activations move, weights stay; EXPERIMENTS.md §Perf C).
        tok_spec = P(None, None)
        tok_axes = ()
    n_tok_shards = 1
    for a in tok_axes:
        n_tok_shards *= mesh.shape[a]
    t_local = max(xt.shape[0] // n_tok_shards, 1)
    cap = max(int(cfg.capacity_factor * cfg.top_k * t_local / E) + 1, 1)

    def local_fn(xt_l, weights_l, idx_l, w1, w3, w2):
        # xt_l [t_local, D]; w1/w3 [e_local, D, F_local]; w2 [e_local, F_local, D]
        m = jax.lax.axis_index("model")
        tl = xt_l.shape[0]
        flat_e = idx_l.reshape(-1)  # [tl*k] global expert ids
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # slot in expert bucket
        local_e = flat_e - m * e_local
        mine = (local_e >= 0) & (local_e < e_local) & (pos < cap)
        le_c = jnp.clip(local_e, 0, e_local - 1)
        pos_c = jnp.where(mine, pos, 0)
        src_tok = jnp.repeat(jnp.arange(tl), cfg.top_k)
        buckets = jnp.zeros((e_local, cap, xt_l.shape[1]), dtype=xt_l.dtype)
        buckets = buckets.at[le_c, pos_c].add(jnp.where(mine[:, None], xt_l[src_tok], 0))
        fn = jax.nn.silu if act == "silu" else jax.nn.gelu
        hh = fn(jnp.einsum("ecd,edf->ecf", buckets, w1)) * jnp.einsum(
            "ecd,edf->ecf", buckets, w3
        )
        o = jnp.einsum("ecf,efd->ecd", hh, w2)  # [e_local, cap, D] (partial if FF TP'd)
        if ff_axes:
            o = jax.lax.psum(o, ff_axes)  # TP-within-expert partial sums
        gathered = jnp.where(mine[:, None], o[le_c, pos_c], 0)
        wflat = weights_l.reshape(-1, 1).astype(gathered.dtype)
        y_partial = jnp.zeros_like(xt_l).at[src_tok].add(gathered * wflat)
        return jax.lax.psum(y_partial, "model")

    flat_spec = P(tok_spec[0], None)  # routing weights / indices [T, k]
    y = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(tok_spec, flat_spec, flat_spec, w1_spec, w1_spec, w2_spec),
        out_specs=tok_spec,
        check_vma=False,
    )(xt, weights, idx, p["w1"], p["w3"], p["w2"])
    return y


def init_moe_params(rng, cfg: MoEConfig, d_model: int, dtype) -> dict:
    E, F = cfg.num_experts, cfg.d_ff_expert
    k = jax.random.split(rng, 6)
    s_in = d_model ** -0.5
    s_out = F ** -0.5
    p = {
        "router": (jax.random.normal(k[0], (d_model, E)) * s_in).astype(jnp.float32),
        "w1": (jax.random.normal(k[1], (E, d_model, F)) * s_in).astype(dtype),
        "w3": (jax.random.normal(k[2], (E, d_model, F)) * s_in).astype(dtype),
        "w2": (jax.random.normal(k[3], (E, F, d_model)) * s_out).astype(dtype),
    }
    if cfg.num_shared_experts > 0:
        Fs = F * cfg.num_shared_experts
        p["shared_w1"] = (jax.random.normal(k[4], (d_model, Fs)) * s_in).astype(dtype)
        p["shared_w3"] = (jax.random.normal(k[5], (d_model, Fs)) * s_in).astype(dtype)
        p["shared_w2"] = (jax.random.normal(k[0], (Fs, d_model)) * Fs ** -0.5).astype(dtype)
    return p
