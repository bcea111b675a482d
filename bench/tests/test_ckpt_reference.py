"""The checkpoint cell's reference: the chip's share of Moonlight-16B-A3B's
training state as the configuration states it, leaf contents that agree
between NumPy and the device, and a plain save and resume."""
import ast
import math

import jax.numpy as jnp
import numpy as np

from bench import ckpt_reference, reference, spec

BM = spec.load_benchmark()
CFG = spec.config(BM, "ckpt-moonlight16b")
SW = reference.seed_word(2**31 + 17)


def test_imports_nothing_of_the_program():
    tree = ast.parse(open(ckpt_reference.__file__).read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert not [m for m in names if m.split(".")[0] == "repro"], names


def test_state_is_the_chips_share_at_published_widths():
    """153 trainable tensors of 568,484,352 parameters (layer 0 dense, MoE
    layers 1-4 with 8 of 64 experts, a vocab slice of 20,480), the 4 router
    bias buffers and AdamW's two moments: 463 leaves, 6,821,813,248 B."""
    leaves = ckpt_reference.state_leaves(CFG)
    params = [s for g, _, s in leaves if g == "params"]
    assert len(params) == 153 and sum(math.prod(s) for s in params) == 568_484_352
    assert [s for g, _, s in leaves if g == "buffers"] == [(64,)] * 4
    assert len(leaves) == 463 and ckpt_reference.state_bytes(CFG) == 6_821_813_248
    names = {n for _, n, _ in leaves}
    assert "model.layers.4.mlp.experts.7.down_proj.weight" in names
    assert "model.layers.1.mlp.experts.8.up_proj.weight" not in names
    shapes = dict((n, s) for g, n, s in leaves if g == "params")
    assert shapes["model.layers.1.mlp.gate.weight"] == (64, 2048)  # the router's published width
    assert shapes["model.layers.0.self_attn.q_proj.weight"] == (16 * 192, 2048)
    assert shapes["model.layers.0.self_attn.kv_b_proj.weight"] == (16 * 256, 512)
    assert shapes["model.layers.0.mlp.down_proj.weight"] == (2048, 11264)
    assert shapes["lm_head.weight"] == shapes["model.embed_tokens.weight"] == (20480, 2048)


def test_leaves_agree_between_numpy_and_jax_and_are_normal_floats():
    host = ckpt_reference.leaf_bits(SW, 7, 3, 4096)
    dev = np.asarray(ckpt_reference.leaf_bits(jnp.uint32(SW), jnp.uint32(7), jnp.uint32(3),
                                              4096, xp=jnp))
    np.testing.assert_array_equal(host, dev)
    vals = host.view(np.float32)
    assert np.all(np.isfinite(vals)) and np.all(np.abs(vals) >= 2.0 ** -7)
    assert np.all(host != ckpt_reference.leaf_bits(SW, 7, 4, 4096))  # a step rewrites every word
    assert not np.array_equal(host, ckpt_reference.leaf_bits(SW, 8, 3, 4096))


def test_plain_save_and_resume():
    state = {f"l{i}": ckpt_reference.leaf_value(SW, i, 1, (8, 16)) for i in range(3)}
    copies, crcs = ckpt_reference.save(state)
    assert crcs["l1"] == ckpt_reference.leaf_crc(state["l1"])
    copies["l2"] = copies["l2"].copy()
    copies["l2"][0, 0] = 1.5
    good, refused = ckpt_reference.resume(copies, crcs)
    assert refused == ["l2"] and set(good) == {"l0", "l1"}
    assert good["l0"].tobytes() == state["l0"].tobytes()
