"""Readings for a cell's limits: the program's and the control's, per seed.

    python bench/calibrate.py --workload <name> --seconds <s> --seeds 1 2 3

For each seed, in one process: set the cell up, run a window at the cell's
own load, then compute each number the cell's check compares, for the
program and for the control put in the program's place. Prints one JSON
line per seed. The benchmark's own runs never run this; the limits in
``bench/limits/`` are set from what it prints (``PERF.md`` keeps the
readings).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root / "src"), str(root)]
    from bench import harness, registry

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 1
    harness._setup_compile_cache()
    bm = registry.load_benchmark(root)
    wl = registry.workload(bm, args.workload)
    cfg = registry.load_config(bm, wl, root)
    mix = registry.load_traffic(wl, root)
    drv = registry.driver(mix["driver"], root)
    for seed in args.seeds:
        t0 = time.perf_counter()
        ctx = harness.Context(False, root)
        ctx.limits = registry.load_limits(wl, root)
        cell = drv.Cell(cfg, mix, seed, ctx, args.seconds)
        cell.setup()
        rec = cell.window(args.seconds)
        cell.release()
        out = {"workload": args.workload, "seed": seed,
               "program": dict((n, v) for n, v, _ in cell.check(rec)),
               "control": cell.control(rec),
               "seconds": time.perf_counter() - t0}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
