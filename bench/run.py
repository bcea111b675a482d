"""Run one cell of BENCHMARK.json on the chip this process starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (device, data, warm-up of every shape the cell's mix uses) runs
first and counts as ``setup_s``; then the window runs for ``--seconds``.
With ``--trace 0`` the last stdout line holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window and the program's lifecycle spans.  Once the window
has closed, what it produced is compared with ``bench/reference.py``;
each number compared is printed beside its limit as the last lines of
stderr and under ``checks``, the last key of the result.

Exits 3, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import record, spec, trace_reduce, traffic  # noqa: E402

# where a traced run's profile goes: a fixed path inside the checkout,
# emptied before and after each traced run
TRACE_DIR = ROOT / ".bench_trace"
PEAKS = json.loads((spec.BENCH / "peaks.json").read_text())


class NoChip(RuntimeError):
    pass


class CompileCounter:
    """Counts traces and compiles (also persistent-cache hits) while alive:
    none may happen inside the measured window."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **kw) -> None:
        if name.startswith("/jax/core/compile/") or "cache_retrieval" in name:
            self.n += 1

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no per-function Python events
    opts.host_tracer_level = 1  # user annotations, not the runtime's own
    return opts


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, cfg: Optional[Dict] = None,
             mix: Optional[Dict] = None, require_tpu: bool = True,
             keep_trace: Optional[str] = None):
    """One run of ``workload``; returns (result dict, checks).  ``cfg`` and
    ``mix`` replace the cell's files (tests run small copies on the CPU
    with ``require_tpu=False``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bm = spec.load_benchmark()
    cell = spec.cell(bm, workload)
    cfg = cfg or spec.config(bm, cell["config"])
    mix = mix or traffic.load(cell["traffic"])
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
        if len(devices) < int(cell["chips"]):
            raise NoChip(f"the cell needs {cell['chips']} chips, JAX found {len(devices)}")
        if kind not in PEAKS["devices"]:
            raise NoChip(f"no peaks for device kind {kind!r} in bench/peaks.json")
    counter = CompileCounter()
    try:
        sut = spec.system(cfg).System(cfg, mix, seed, traced=trace)
        run = record.Run(setup_s=time.perf_counter() - t_start,
                         peaks=PEAKS["devices"].get(kind, {}))
        spans = record.Spans(trace)
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=_profile_options())
        n_compiles = counter.n
        t_window = time.perf_counter()
        with spans.span("window"):
            sut.window(seconds, spans, run)
        n_compiles = counter.n - n_compiles
    finally:
        counter.close()
    if trace:
        jax.profiler.stop_trace()
        path = next(TRACE_DIR.glob("plugins/profile/*/*.xplane.pb"))
        if keep_trace:
            shutil.copy(path, keep_trace)
        run.device = trace_reduce.reduce(str(path))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if run.device is None and require_tpu:
            raise RuntimeError("the profile of the window holds no TPU plane")
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    run.spans = [s for s in spans.spans if s[0] != "window"]
    if sut.tracer is not None:
        run.traces = [t for t in sut.tracer.traces()
                      if t.marks.get("submit_enter", 0.0) >= t_window]
    checks = sut.check()
    names = [m["name"] for m in spec.metrics(bm, workload, "per_layer" if trace else "end_to_end")]
    metrics = {}
    units = {m["name"]: m["unit"] for m in bm["end_to_end"] + bm["per_layer"]}
    for name in names:
        value = spec.reader(name)(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {
        "correct": all(v <= limit for v, limit in checks.values()),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.device is not None:
        device["busy_s"] = run.device["busy_s"]
        device["window_s"] = run.device["window_s"]
        result["breakdown"] = {"device_ops": run.device["device_ops"],
                               "idle_gaps": run.device["idle_gaps"]}
    result["compiles_in_window"] = n_compiles
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", help="copy the traced window's .xplane.pb here")
    args = ap.parse_args(argv)
    import jax

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    # cache every program, however small, so that only a checkout's first
    # run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        result, checks = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                                  t_start=T0, keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    for name, (value, limit) in checks.items():
        print(f"check {name} = {value} (limit {limit})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
