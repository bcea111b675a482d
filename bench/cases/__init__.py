"""What the benchmark's checks need of each system, found by a
configuration's ``system`` as ``bench/cases/<system>.py``, the way
``spec.system`` finds its adapter.  No timed run imports these modules.

Each module has:

- ``small(cfg, mix) -> (cfg, mix)``: copies of a cell's configuration and
  traffic mix at a size a CPU test run holds, derived from the cell's own
  files, so that any cell of the system shrinks;
- ``FAULTS``: name -> ``Fault``, the faults its cells can have, each of
  which must bring ``correct`` out false (``bench/tests/test_faults.py``);
- ``CONTROL``: the kernel entry points of ``repro.kernels.ops`` that the
  reference, one precision step down, replaces (``bench/control.py``);
- ``rehearse(cfg, mixes, report, shape)``: compiles the programs the
  system's cells run, from the configuration and the cells' traffic
  mixes, for a described chip (``bench/rehearse.py``).
"""
from __future__ import annotations

import contextlib
import importlib
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import spec, traffic
from repro.kernels import ops

CASES = Path(__file__).resolve().parent
REAL_COPY = ops.batch_copy


@dataclass(frozen=True)
class Fault:
    """A fault planted under the timed path: ``ops`` replaces kernel entry
    points of ``repro.kernels.ops`` by name; ``patch(monkeypatch)`` plants
    it anywhere else; ``check(result, checks)`` asserts more of the faulty
    run than that it is not correct."""

    ops: Dict[str, Callable] = field(default_factory=dict)
    patch: Optional[Callable] = None
    check: Optional[Callable] = None


def of(cfg: Dict) -> ModuleType:
    """The hooks module of the configuration's ``system``."""
    path = CASES / f"{cfg['system']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no test hooks for system {cfg['system']!r}: {path} is missing")
    return importlib.import_module(f"bench.cases.{cfg['system']}")


def small_cell(workload: str) -> Tuple[ModuleType, Dict, Dict]:
    """(hooks, cfg, mix) of ``workload`` at its system's test size."""
    bm = spec.load_benchmark()
    cell = spec.cell(bm, workload)
    cfg = spec.config(bm, cell["config"])
    hooks = of(cfg)
    return (hooks,) + hooks.small(cfg, traffic.load(cell["traffic"]))


def run_small(workload: str, seed: int):
    """One untraced run of ``workload`` on the CPU at its test size, with a
    0.4 s window: (result, checks) as ``bench.run.run_cell`` gives them."""
    from bench import run

    _, cfg, mix = small_cell(workload)
    return run.run_cell(workload, seed, 0.4, False, cfg=cfg, mix=mix, require_tpu=False)


@contextlib.contextmanager
def replaced(functions: Dict[str, Callable]):
    """Put ``functions`` in place of the program's kernel entry points of
    the same names for the duration of the block."""
    saved = {name: getattr(ops, name) for name in functions}
    try:
        for name, fn in functions.items():
            setattr(ops, name, fn)
        yield
    finally:
        for name, fn in saved.items():
            setattr(ops, name, fn)


@contextlib.contextmanager
def planted(fault: Fault, monkeypatch):
    """Plant ``fault`` for the duration of the block (its ``patch`` until
    ``monkeypatch`` undoes it)."""
    if fault.patch is not None:
        fault.patch(monkeypatch)
    with replaced(fault.ops):
        yield


# ------------------------------------------------- faults of batch_copy, shared
def bump_first(x):
    """``x`` with one added to the bits of the first element of each row
    (an XOR would cancel out on a page that moves twice)."""
    u = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    bits = jax.lax.bitcast_convert_type(x, u)
    flat = bits.reshape(bits.shape[0], -1) if bits.ndim > 1 else bits[None]
    flat = flat.at[:, 0].set(flat[:, 0] + 1)
    return jax.lax.bitcast_convert_type(flat.reshape(bits.shape), x.dtype)


def unchanged_copy(src, dst, si, di, *, interpret=None):
    return dst


def half_copy(src, dst, si, di, *, interpret=None):
    h = si.shape[0] // 2
    return REAL_COPY(src, dst, si[:h], di[:h], interpret=interpret)


def altered_copy(src, dst, si, di, *, interpret=None):
    out = REAL_COPY(src, dst, si, di, interpret=interpret)
    return out.at[di].set(bump_first(out[di]))
