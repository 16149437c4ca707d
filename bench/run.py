"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, sets it up (data, weights,
server or training state, warm-up of every shape the window uses), measures
for ``--seconds`` and prints one JSON object as the last line of standard
output. Exits non-zero, with no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import pathlib
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root / "src"), str(root)]
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
