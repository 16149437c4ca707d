"""One cell, one run: set up, warm, measure, check, report.

The cell's driver (``bench/drivers/<mix["driver"]>.py``) owns the system
under test; this module owns what is common to every cell: the device
check, the compile cache, the set-up clock, the count of programs built
inside the window, the profiler around a traced window, the metric
readers, and the result line.

A driver module exposes ``Cell(cfg, mix, seed, ctx, seconds)`` with

  setup()            build data, weights and the system; warm every shape
  window(seconds)    measure; returns the run record (a dict)
  release()          drop the system's device state
  check(rec)         compare with the plain reference after the window;
                     returns [(name, value, limit), ...], each passing
                     while value <= limit

and reads ``ctx.span(name)`` around each call into the system, so that a
traced run's idle gaps can be named by what the host was doing.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import shutil
import sys
import tempfile
import time

from bench import registry

__all__ = ["main", "run_cell", "Context"]

SPAN_PREFIX = "bench."


class Context:
    """What a driver gets from the harness: logging, spans, tracing."""

    def __init__(self, tracing: bool, root=registry.ROOT):
        self.tracing = tracing
        self.root = root
        self._trace_dir = None
        self.trace_window = None  # (start, end) perf_counter of the trace
        self.limits: dict = {}    # the cell's bench/limits/<workload>.json

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def span(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    @contextlib.contextmanager
    def traced(self):
        """The profiler on around the body when this run traces."""
        if not self.tracing:
            yield
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_window = (t0, t1)

    def trace_file(self) -> str | None:
        if self._trace_dir is None:
            return None
        found = glob.glob(f"{self._trace_dir}/**/*.xplane.pb",
                          recursive=True)
        return found[0] if found else None

    def drop_trace(self) -> None:
        if self._trace_dir is not None:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None


class _ProgramCount:
    """Programs lowered for a device since install (compiled or loaded
    from the persistent cache alike): a window that builds none reads 0."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == self.EVENT:
            self.n += 1


def _device_info(devs, chips: int) -> dict:
    used = devs[:chips]
    peaks = []
    for d in used:
        try:
            st = d.memory_stats() or {}
        except Exception:  # backends without memory statistics
            st = {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(used), "memory_peak_bytes": max(peaks)}


def _setup_compile_cache() -> str:
    import jax
    from repro.launch.compile_cache import init_compile_cache

    # every program goes to the cache, however quick its compile, so the
    # runs after a cell's first load it all and set-up stays steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return init_compile_cache()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None,
             bm: dict | None = None, root=registry.ROOT,
             overrides: dict | None = None,
             compile_cache: bool = True) -> dict:
    """Set up, measure and check one cell; returns the result object.

    ``overrides`` = {"config": {...}, "traffic": {...}} patches the files'
    values (small sizes for tests on the CPU), and ``compile_cache=False``
    leaves JAX's persistent cache as it is.
    """
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    bm = registry.load_benchmark(root) if bm is None else bm
    wl = registry.workload(bm, workload)
    cfg = registry.load_config(bm, wl, root)
    mix = registry.load_traffic(wl, root)
    for key, part in (("config", cfg), ("traffic", mix)):
        part.update((overrides or {}).get(key, {}))
    ctx = Context(bool(trace), root)
    ctx.limits = registry.load_limits(wl, root)
    cache = _setup_compile_cache() if compile_cache else "unchanged"
    ctx.log(f"workload {workload} seed {seed} seconds {seconds} "
            f"trace {int(bool(trace))}; compile cache {cache}")
    programs = _ProgramCount()
    drv = registry.driver(mix["driver"], root)
    cell = drv.Cell(cfg, mix, seed, ctx, seconds)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    ctx.log(f"set-up {setup_s:.3f} s, {programs.n} programs built")
    built = programs.n
    rec = cell.window(seconds)
    rec["programs_in_window"] = programs.n - built
    rec["setup_s"] = setup_s
    rec["cfg"] = cfg
    rec["mix"] = mix
    device = _device_info(jax.devices(), wl["chips"])
    rec["device_kind"] = device["kind"]
    breakdown = None
    if trace:
        from bench import trace as trace_mod

        path = ctx.trace_file()
        if path is None:
            raise RuntimeError("the traced window left no trace file")
        t0, t1 = ctx.trace_window
        t_red = time.perf_counter()
        red = trace_mod.reduce(path, span_prefix=SPAN_PREFIX)
        t_red = time.perf_counter() - t_red
        ctx.drop_trace()
        rec["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["top_ops"],
                     "idle_gaps": red["top_gaps"]}
        ctx.log(f"trace: {red['n_device_ops']} device ops, busy "
                f"{red['busy_s']:.6f} s of {red['window_s']:.6f} s "
                f"(host window {t1 - t0:.6f} s); read in {t_red:.3f} s")
    cell.release()
    checks = cell.check(rec)
    checks.append(("programs_in_window", rec["programs_in_window"], 0))
    correct = all(v <= lim for _, v, lim in checks)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in registry.metrics_for(bm, workload, section):
        if trace and m["name"] == "setup_s":
            continue
        value = registry.reader(m["name"], root).read(rec)
        if value is None:
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(rec["attempted"]),
           "failed": int(rec["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {name: {"value": value, "limit": lim}
                       for name, value, lim in checks}
    for name, value, lim in checks:
        ctx.log(f"compared {name} {value!r} limit {lim!r} "
                f"{'ok' if value <= lim else 'FAILED'}")
    return out


def main(argv=None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bm = registry.load_benchmark()
    wl = registry.workload(bm, args.workload)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program (src/repro) is not in this checkout: {e}",
              file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU found (JAX runs on {devs[0].platform!r}); "
              f"this benchmark measures the chip only", file=sys.stderr)
        return 1
    if len(devs) < wl["chips"]:
        print(f"bench: {args.workload} needs {wl['chips']} chips, JAX sees "
              f"{len(devs)}", file=sys.stderr)
        return 1
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start=t_start, bm=bm)
    print(json.dumps(out), flush=True)
    return 0
