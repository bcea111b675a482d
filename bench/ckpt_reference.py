"""Plain reference of the checkpoint cells: the training state one chip
holds, the contents of every leaf at every step, and a save and a resume
done the plain way.  Nothing here imports the program.

Leaf ``l`` at step ``k`` holds finite, normal float32 values whose bits
are ``bench/reference.hash4`` of (seed, l, k, position), so any leaf of any
step can be regenerated alone, in NumPy on the host or in ``jax.numpy`` on
the device, with the same bits.  A leaf's CRC is ``zlib.crc32`` of its
little-endian bytes.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, List, Mapping, Tuple

import numpy as np

from bench.reference import hash4

GROUPS = ("params", "buffers", "exp_avg", "exp_avg_sq")


def state_leaves(cfg: Mapping) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """(group, tensor name, shape) of every leaf of the chip's share, in the
    order that numbers them: the trainable tensors as the published
    checkpoint names them (``params``), the router's score-correction
    biases (``buffers``, which have no moments), then AdamW's ``exp_avg``
    and ``exp_avg_sq`` of each trainable tensor."""
    h = int(cfg["hidden_size"])
    heads = int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    lora, v_dim = int(cfg["kv_lora_rank"]), int(cfg["v_head_dim"])
    moe_w = int(cfg["moe_intermediate_size"])
    router = int(cfg["published"]["n_routed_experts"])
    params: List[Tuple[str, Tuple[int, ...]]] = [
        ("model.embed_tokens.weight", (int(cfg["vocab_size"]), h))]
    buffers: List[Tuple[str, Tuple[int, ...]]] = []
    for i in range(int(cfg["num_hidden_layers"])):
        p = f"model.layers.{i}"
        params += [(f"{p}.input_layernorm.weight", (h,)),
                   (f"{p}.self_attn.q_proj.weight", (heads * (nope + rope), h)),
                   (f"{p}.self_attn.kv_a_proj_with_mqa.weight", (lora + rope, h)),
                   (f"{p}.self_attn.kv_a_layernorm.weight", (lora,)),
                   (f"{p}.self_attn.kv_b_proj.weight", (heads * (nope + v_dim), lora)),
                   (f"{p}.self_attn.o_proj.weight", (h, heads * v_dim)),
                   (f"{p}.post_attention_layernorm.weight", (h,))]
        if i < int(cfg["first_k_dense_replace"]):
            mlps = [(f"{p}.mlp", int(cfg["intermediate_size"]))]
        else:
            params.append((f"{p}.mlp.gate.weight", (router, h)))
            buffers.append((f"{p}.mlp.gate.e_score_correction_bias", (router,)))
            first = int(cfg["expert_rank"]) * int(cfg["n_routed_experts"])
            mlps = [(f"{p}.mlp.experts.{e}", moe_w)
                    for e in range(first, first + int(cfg["n_routed_experts"]))]
            mlps.append((f"{p}.mlp.shared_experts", moe_w * int(cfg["n_shared_experts"])))
        for q, width in mlps:
            params += [(f"{q}.gate_proj.weight", (width, h)), (f"{q}.up_proj.weight", (width, h)),
                       (f"{q}.down_proj.weight", (h, width))]
    params += [("model.norm.weight", (h,)), ("lm_head.weight", (int(cfg["vocab_size"]), h))]
    return ([("params", n, s) for n, s in params] + [("buffers", n, s) for n, s in buffers]
            + [(g, n, s) for g in ("exp_avg", "exp_avg_sq") for n, s in params])


def state_bytes(cfg: Mapping) -> int:
    return sum(4 * math.prod(s) for _, _, s in state_leaves(cfg))


def leaf_bits(sw, leaf, step, n: int, xp=np):
    """uint32 float32 bit patterns of leaf ``leaf`` at step ``step``, [n]:
    sign, a 4-bit exponent around 1.0 (2**-7 to 2**8) and a 23-bit
    mantissa: finite normal values only, every word different each step."""
    pos = xp.arange(n, dtype=xp.uint32)
    h = hash4(xp.asarray(sw).astype(xp.uint32) ^ xp.uint32(0x27D4EB2F), leaf, step, pos, xp)
    u = xp.uint32
    return ((h & u(0x80000000)) | ((u(0x78) + ((h >> 23) & u(0xF))) << 23)
            | (h & u(0x7FFFFF)))


def leaf_value(sw: int, leaf: int, step: int, shape) -> np.ndarray:
    """The float32 leaf itself, on the host."""
    return leaf_bits(sw, leaf, step, math.prod(shape)).view(np.float32).reshape(shape)


def leaf_crc(leaf: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(leaf).tobytes()) & 0xFFFFFFFF


def save(state: Mapping[str, np.ndarray]) -> Tuple[Dict[str, np.ndarray], Dict[str, int]]:
    """A plain save: a copy of every leaf and its CRC (the manifest)."""
    copies = {k: np.array(v, copy=True) for k, v in state.items()}
    return copies, {k: leaf_crc(v) for k, v in copies.items()}


def resume(copies: Mapping[str, np.ndarray], crcs: Mapping[str, int]
           ) -> Tuple[Dict[str, np.ndarray], List[str]]:
    """A plain resume: every leaf whose CRC matches the manifest, and the
    names of those that do not."""
    refused = [k for k, v in copies.items() if leaf_crc(v) != crcs.get(k)]
    return {k: v for k, v in copies.items() if k not in refused}, refused
