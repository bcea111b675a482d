"""A serving stack's KV tier swapping preempted sessions: the driven entry
is ``PagedKVPool.swap_out`` / ``swap_in`` on a pool built with
``device=make_device()``, so every swap is one BATCH_COPY descriptor.

Sessions own ``num_hidden_layers * ceil(tokens / block_size)`` pages.  The
first ``resident`` sessions of the seeded order start in the device pool,
the rest in the host pool.  Each step swaps out the resident session that
came in first and swaps in the swapped-out session that went out first:
the resident set is a window that rotates one session a step through the
seeded cycle, so every session moves once every ``count`` steps.  The
window ends at the first whole rotation after ``seconds``, so that every
seed moves every session equally often.  Page
``p`` of session ``s`` holds ``reference.page_bits(seed, s, p)`` from
set-up on, and must still hold it, wherever it lives, after the window.
"""
from __future__ import annotations

import collections
import contextlib
import math
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import kernel_bytes, reference, traffic
from repro.core import QueueFull, make_device
from repro.obs.trace import TraceConfig
from repro.serving.kv_pool import PagedKVPool

GIB = 1 << 30
CHECK_BLOCK = 256  # pages compared per device call


def feasible(pages: List[int], resident: int, device_pages: int, host_pages: int) -> bool:
    """Whether every step of every seeded order fits: any ``resident``
    sessions fit the device pool, and the host pool holds all sessions
    but any ``resident - 1`` of them."""
    srt = sorted(pages)
    return (sum(srt[-resident:]) <= device_pages
            and sum(pages) - sum(srt[:resident - 1]) <= host_pages)


def _fill(sw, session, page_no, page_shape):
    bits = reference.page_bits(sw, jnp.maximum(session, 0), page_no,
                               math.prod(page_shape), xp=jnp)
    bits = jnp.where((session >= 0)[:, None], bits, jnp.uint16(0))
    return jax.lax.bitcast_convert_type(bits, jnp.bfloat16).reshape(
        (session.shape[0],) + tuple(page_shape))


def _mismatched_pages(pool, idx, session, page_no, valid, sw):
    got = jax.lax.bitcast_convert_type(pool[idx], jnp.uint16).reshape(idx.shape[0], -1)
    want = reference.page_bits(sw, session, page_no, got.shape[1], xp=jnp)
    return jnp.sum(jnp.any(got != want, axis=1) & valid)


class System:
    def __init__(self, cfg: Dict, mix: Dict, seed: int, traced: bool):
        self.seed = seed
        self.sw = reference.seed_word(seed)
        layers = int(cfg["num_hidden_layers"])
        block = int(cfg["block_size"])
        kv_dim = 2 * int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
        if cfg["dtype"] != "bfloat16":
            raise ValueError(f"pages are bfloat16 here, not {cfg['dtype']}")
        self.page_shape = (block, kv_dim)
        self.page_bytes = block * kv_dim * 2
        dev_pages = int(cfg["device_pool_gib"] * GIB) // self.page_bytes
        host_pages = int(cfg["swap_space_gib"] * GIB) // self.page_bytes
        toks = traffic.session_tokens(mix, seed)
        self.pages = [layers * -(-int(t) // block) for t in toks]
        resident = int(mix["sessions"]["resident"])
        if not feasible(self.pages, resident, dev_pages, host_pages):
            raise ValueError(f"sessions of {sorted(self.pages)} pages do not fit "
                             f"{resident} resident in {dev_pages} device / "
                             f"{host_pages} host pages")
        self.dev = make_device(
            trace=TraceConfig(rate=1.0, capacity=1 << 16) if traced else None)
        self.tracer = self.dev.tracer
        self.kv = PagedKVPool(dev_pages, host_pages, block, kv_dim,
                              dtype=jnp.bfloat16, device=self.dev)
        for s, n in enumerate(self.pages):
            self.kv.alloc(s, n, "device" if s < resident else "host")
        fill = jax.jit(_fill, static_argnums=(3,))
        for tier, n_slots in (("device", dev_pages), ("host", host_pages)):
            session, page_no = self._slot_map(tier, n_slots)
            pool = fill(jnp.uint32(self.sw), session, page_no, self.page_shape)
            if tier == "device":
                self.kv._set_device_pool(0, pool)
            else:
                self.kv._set_host_pool(pool)
        self.resident = collections.deque(range(resident))
        self.swapped = collections.deque(range(resident, len(self.pages)))
        # one whole rotation: every session goes out and comes back once,
        # which compiles every page count in both directions and leaves the
        # pools in the order the window starts from
        for _ in range(len(self.pages)):
            self._step(None)
        jax.block_until_ready(self.kv.device_pools[0])

    def _slot_map(self, tier: str, n_slots: int) -> Tuple[jax.Array, jax.Array]:
        session = np.full(n_slots, -1, np.int32)
        page_no = np.zeros(n_slots, np.int32)
        for s, entries in self.kv.page_table.items():
            for p, (t, _node, idx) in enumerate(entries):
                if t == tier:
                    session[idx], page_no[idx] = s, p
        return jnp.asarray(session), jnp.asarray(page_no)

    def _swap(self, fn, s: int, spans, name: str) -> bool:
        try:
            with spans.span(name) if spans else contextlib.nullcontext():
                ok = fn(s)
        except QueueFull:
            return False
        if not ok:
            raise RuntimeError(f"{name} of session {s} found no room")
        return True

    def _step(self, spans) -> Tuple[int, int]:
        """One swap out and one swap in; returns (swaps done, pages moved)."""
        done = moved = 0
        a = self.resident.popleft()
        if self._swap(self.kv.swap_out, a, spans, "swap_out"):
            self.swapped.append(a)
            done, moved = 1, self.pages[a]
        else:
            self.resident.appendleft(a)
            return done, moved
        b = self.swapped.popleft()
        if self._swap(self.kv.swap_in, b, spans, "swap_in"):
            self.resident.append(b)
            done, moved = done + 1, moved + self.pages[b]
        else:
            self.swapped.appendleft(b)
        return done, moved

    def window(self, seconds: float, spans, run) -> None:
        swaps = pages = attempts = steps = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds or steps % len(self.pages):
            done, moved = self._step(spans)
            steps += 1
            attempts += 2 if done else 1
            swaps += done
            pages += moved
        run.window_s = time.perf_counter() - t0
        run.attempted = attempts
        run.failed = attempts - swaps
        run.bytes_done = pages * self.page_bytes
        run.kernel_bytes["batch_copy"] = kernel_bytes.batch_copy(pages, self.page_shape, 2)

    def check(self) -> Dict[str, Tuple[float, float]]:
        table_errors = 0
        seen = set()
        where: Dict[str, List[Tuple[int, int, int]]] = {"device": [], "host": []}
        for s, n in enumerate(self.pages):
            entries = self.kv.page_table.get(s, [])
            table_errors += abs(len(entries) - n)
            for p, (tier, node, idx) in enumerate(entries):
                if (tier, node, idx) in seen:
                    table_errors += 1
                seen.add((tier, node, idx))
                where[tier].append((idx, s, p))
        cmp = jax.jit(_mismatched_pages)
        bad = 0
        for tier, pool in (("device", self.kv.device_pools[0]), ("host", self.kv.host_pool)):
            rows = np.asarray(where[tier], np.int32).reshape(-1, 3)
            for b in range(0, len(rows), CHECK_BLOCK):
                blk = rows[b:b + CHECK_BLOCK]
                valid = np.arange(CHECK_BLOCK) < len(blk)
                blk = np.concatenate([blk, np.repeat(blk[-1:], CHECK_BLOCK - len(blk), 0)])
                bad += int(cmp(pool, jnp.asarray(blk[:, 0]), jnp.asarray(blk[:, 1]),
                               jnp.asarray(blk[:, 2]), jnp.asarray(valid),
                               jnp.uint32(self.sw)))
        return {"bad_pages": (bad, 0), "table_errors": (table_errors, 0)}
