"""Bring-up smoke run on one TPU chip, through the entry points users call.

    python chip_smoke.py [--seed N]

One process, four phases, in order; any failure raises and exits non-zero:

  (a) device   JAX must report a TPU (never falls back to the CPU); every
               StreamEngine must run compiled kernels (interpret is False).
  (b) datapath every descriptor op through ``make_device()``/``Device.submit``
               at 64 B, 1518 B, 4 KiB, 1 MiB and 64 MiB where the op takes a
               size, plus one fused 32-memcpy ``batch_async`` burst; every
               record must be SUCCESS and every result bit-exact with
               ``repro.kernels.ref`` (CRCs with zlib).
  (c) serving  ``repro.launch.serve`` runs VhostStyleServer with
               tinyllama-1.1b at its published widths (--no-reduced):
               8 requests of 128 prompt tokens, 16 new tokens, 4 slots.
  (d) ckpt     CheckpointManager(crc_impl="kernel") saves the served params,
               restores them bit-exact, and every kernel CRC equals zlib's.

Wall times printed per phase are cold set-up times (they include
compilation), not metrics.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
import zlib
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SIZES = (64, 1518, 4096, 1 << 20, 64 << 20)  # bytes
BURST = 32  # DPDK's usual Rx/Tx burst
KV_PAGE = (16, 512)  # tinyllama KV page: 16 tokens x (K+V: 2 x 4 heads x 64)


def expect(cond, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


def padded_words(by: np.ndarray) -> np.ndarray:
    """The ops layer's u32 word view of a byte buffer (last word zero-padded)."""
    return np.frombuffer(by.tobytes() + bytes(-by.size % 4), "<u4")


class Datapath:
    """Phase (b): one descriptor per op x size through Device.submit."""

    def __init__(self, device, seed: int):
        import jax

        self.device = device
        self.key = jax.random.key(seed)
        self.count = 0

    def _key(self):
        import jax

        self.key, k = jax.random.split(self.key)
        return k

    def _bits(self, shape, dtype):
        import jax

        return jax.random.bits(self._key(), shape, dtype)

    def run(self, desc, label: str):
        from repro.core import Status

        t0 = time.perf_counter()
        fut = self.device.submit(desc)
        out = fut.result()
        expect(fut.status is Status.SUCCESS, f"{label}: status {fut.status} {fut.error}")
        self.count += 1
        return out, time.perf_counter() - t0

    def size(self, nbytes: int) -> None:
        import jax.numpy as jnp

        from repro.core import OpType, WorkDescriptor
        from repro.kernels import ref

        W = OpType
        n_words = -(-nbytes // 4)
        x = self._bits((nbytes,), jnp.uint8)  # byte-granular payload
        xh = np.asarray(x)
        xw = padded_words(xh)
        w = self._bits((n_words,), jnp.uint32)  # word-granular payload
        wh = np.asarray(w)
        pat = jnp.asarray([0xA5A5A5A5, 0x0F1E2D3C], jnp.uint32)
        times = {}

        def op(name, desc, check):
            out, dt = self.run(desc, f"{name}@{nbytes}B")
            check(out)
            times[name] = dt

        op("memcpy", WorkDescriptor(op=W.MEMCPY, src=x),
           lambda o: expect(same(o, ref.memcpy_ref(x)), "memcpy"))
        op("dualcast", WorkDescriptor(op=W.DUALCAST, src=x),
           lambda o: expect(all(same(d, r) for d, r in zip(o, ref.dualcast_ref(x))), "dualcast"))
        op("crc32", WorkDescriptor(op=W.CRC32, src=x),
           lambda o: expect(int(o) == zlib.crc32(xh.tobytes()), "crc32 vs zlib"))
        op("copy_crc", WorkDescriptor(op=W.COPY_CRC, src=x),
           lambda o: expect(same(o[0], xh) and int(o[1]) == zlib.crc32(xh.tobytes()),
                            "copy_crc"))

        def check_compare(o, a, b):
            want = ref.compare_ref(jnp.asarray(a), jnp.asarray(b))
            expect(bool(o[0]) == bool(want[0]) and int(o[1]) == int(want[1]),
                   f"compare {tuple(map(int, o))} vs ref {tuple(map(int, want))}")

        op("compare_equal", WorkDescriptor(op=W.COMPARE, src=x, src2=x),
           lambda o: check_compare(o, xw, xw))
        pos = (2 * nbytes) // 3
        y = x.at[pos].add(1)
        op("compare_diff", WorkDescriptor(op=W.COMPARE, src=x, src2=y),
           lambda o: (check_compare(o, xw, padded_words(np.asarray(y))),
                      expect(int(o[1]) == pos // 4, "compare first-diff word")))

        filled = ref.fill_ref((n_words,), pat)
        op("fill", WorkDescriptor(op=W.FILL, pattern=pat, n_words=n_words),
           lambda o: expect(same(o, filled), "fill"))
        op("fill_verify", WorkDescriptor(op=W.FILL_VERIFY, pattern=pat, n_words=n_words),
           lambda o: expect(same(o[0], filled) and bool(o[1][0]) and int(o[1][1]) == -1,
                            "fill_verify"))
        bad = filled.at[n_words // 2].add(1)
        for name, buf in (("compare_pattern_equal", filled), ("compare_pattern_diff", bad)):
            want = ref.compare_pattern_ref(buf, pat)
            op(name, WorkDescriptor(op=W.COMPARE_PATTERN, src=buf, pattern=pat),
               lambda o, want=want: expect(
                   bool(o[0]) == bool(want[0]) and int(o[1]) == int(want[1]),
                   f"compare_pattern {tuple(map(int, o))} vs ref {tuple(map(int, want))}"))

        k = min(64, n_words // 2)
        changed = w.at[jnp.arange(k) * (n_words // k)].add(7)
        want_rec = ref.delta_create_ref(changed, w, 1024)
        rec = {}

        def check_delta(o):
            expect(all(same(a, b) for a, b in zip(o, want_rec)), "delta_create record")
            rec["off"], rec["data"] = o[0], o[1]

        op("delta_create", WorkDescriptor(op=W.DELTA_CREATE, src=changed, src2=w, cap=1024),
           check_delta)
        op("delta_apply", WorkDescriptor(op=W.DELTA_APPLY, src=w, src_idx=rec["off"],
                                         src2=rec["data"]),
           lambda o: expect(same(o, changed), "delta_apply"))

        if nbytes % 512 == 0:  # DIF frames 512 B blocks
            framed_ref = ref.dif_insert_ref(w)
            op("dif_insert", WorkDescriptor(op=W.DIF_INSERT, src=w),
               lambda o: expect(same(o, framed_ref), "dif_insert"))
            torn = framed_ref.at[0, 5].add(1)
            op("dif_check", WorkDescriptor(op=W.DIF_CHECK, src=torn),
               lambda o: expect(same(o, ref.dif_check_ref(torn)) and not bool(o[0]),
                                "dif_check"))
            op("dif_strip", WorkDescriptor(op=W.DIF_STRIP, src=framed_ref),
               lambda o: expect(same(o, ref.dif_strip_ref(framed_ref)) and same(o, wh),
                                "dif_strip"))
        print(f"[b] {nbytes:>9} B: {len(times)} ops bit-exact, SUCCESS; cold set-up s "
              + " ".join(f"{k}={v:.3f}" for k, v in times.items()), flush=True)

    def batch_copy(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core import OpType, WorkDescriptor
        from repro.kernels import ref

        for n_pages in (64, 4096):  # 1 MiB and 64 MiB pools of KV pages
            src, dst = (jax.random.normal(self._key(), (n_pages,) + KV_PAGE, jnp.bfloat16)
                        for _ in range(2))
            perm = np.random.default_rng(n_pages).permutation(n_pages)
            si = jnp.asarray(perm[:BURST], jnp.int32)
            di = jnp.asarray(perm[-BURST:], jnp.int32)
            want = ref.batch_copy_ref(src, dst, si, di)
            out, dt = self.run(WorkDescriptor(op=OpType.BATCH_COPY, src=src, dst_pool=jnp.array(dst),
                                              src_idx=si, dst_idx=di), "batch_copy")
            expect(same(out, want), "batch_copy")
            print(f"[b] batch_copy {BURST} KV pages {KV_PAGE} bf16 in a {n_pages}-page pool: "
                  f"bit-exact, SUCCESS; cold set-up {dt:.3f}s", flush=True)

    def burst(self) -> None:
        import jax.numpy as jnp

        from repro.core import OpType, Status, WorkDescriptor

        srcs = [self._bits((1518,), jnp.uint8) for _ in range(BURST)]
        t0 = time.perf_counter()
        fut = self.device.batch_async([WorkDescriptor(op=OpType.MEMCPY, src=s) for s in srcs])
        outs = fut.result()
        expect(fut.status is Status.SUCCESS, f"batch_async: {fut.status} {fut.error}")
        expect(len(outs) == BURST and all(same(o, s) for o, s in zip(outs, srcs)),
               "batch_async copies")
        self.count += 1
        print(f"[b] batch_async burst of {BURST} x 1518 B memcpy (fused batch_copy): "
              f"bit-exact, SUCCESS; cold set-up {time.perf_counter() - t0:.3f}s", flush=True)


def serving(seed: int):
    from repro.launch import serve

    args = serve.parser().parse_args([
        "--arch", "tinyllama-1.1b", "--no-reduced", "--requests", "8",
        "--prompt-len", "128", "--max-new", "16", "--slots", "4",
        "--max-cache", "512", "--seed", str(seed)])
    server = serve.serve(args)
    cfg = server.model.cfg
    expect((cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.d_ff,
            cfg.vocab_size, cfg.dtype) == (22, 2048, 32, 4, 5632, 32000, "bfloat16"),
           f"published tinyllama widths, got {cfg}")
    m = server.metrics
    expect(m["completed"] == 8 and m["admitted"] == 8, f"8/8 requests served: {m}")
    return server


def checkpoint(device, params, workdir: Path) -> int:
    import jax

    from repro.checkpoint import CheckpointConfig, CheckpointManager
    from repro.checkpoint.manager import _tree_flatten_with_names

    ckpt = CheckpointManager(
        CheckpointConfig(directory=str(workdir / "ckpt"), crc_impl="kernel",
                         async_save=False),
        device=device)
    leaves = {k: np.asarray(jax.device_get(v)) for k, v in _tree_flatten_with_names(params)}
    ckpt.save(1, params, force_full=True)
    manifest = ckpt._manifest(1)
    for key, arr in leaves.items():
        expect(manifest["leaves"][key]["crc"] == zlib.crc32(arr.tobytes()),
               f"kernel CRC of {key} vs zlib")
    step, restored = ckpt.restore()
    expect(step == 1 and set(restored) == set(leaves), "restored step and leaves")
    for key, arr in leaves.items():
        expect(same(restored[key], arr), f"restored {key} bit-exact")
    return sum(a.nbytes for a in leaves.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX reports platform {dev.platform!r}; this check runs only "
              "on a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import make_device
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    device = make_device()
    expect(all(e.interpret is False for e in device.engines),
           "every StreamEngine runs compiled kernels")
    print(f"[a] device {dev.device_kind} x{len(jax.devices())} ({dev.platform}); "
          f"{len(device.engines)} engine(s), compiled kernels: pass", flush=True)

    t = time.perf_counter()
    dp = Datapath(device, args.seed)
    for nbytes in SIZES:
        dp.size(nbytes)
    dp.batch_copy()
    dp.burst()
    print(f"[b] datapath: pass ({dp.count} descriptors); cold set-up "
          f"{time.perf_counter() - t:.1f}s", flush=True)

    t = time.perf_counter()
    server = serving(args.seed)
    print(f"[c] serving tinyllama-1.1b full width: pass (8/8 requests, "
          f"{server.metrics['decoded_tokens']} decoded tokens); cold set-up "
          f"{time.perf_counter() - t:.1f}s", flush=True)

    t = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix=".smoke_ckpt-", dir=ROOT) as d:
        nbytes = checkpoint(server.device, server.params, Path(d))
    print(f"[d] checkpoint {nbytes / 1e9:.2f} GB kernel-CRC save + restore: pass "
          f"(bit-exact, CRCs equal zlib); cold set-up {time.perf_counter() - t:.1f}s",
          flush=True)

    print(f"# total cold wall {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
