"""Sweep launcher: measure a policy grid, persist the tuning table.

  PYTHONPATH=src python -m repro.launch.sweep --smoke --out /tmp/table.json
  PYTHONPATH=src python -m repro.launch.sweep --config sweeps/kernels.json \
      --out src/repro/tune/tables/cpu_kernels.json \
      --bench-out BENCH_kernels.json

``--smoke`` runs the built-in tiny grid (CI's sweep-smoke job); otherwise
``--config`` names a JSON sweep config (format: docs/tuning.md). The
emitted table is what `repro.api` dispatch and `GNNServer` consult when
no explicit policy is given — write it to the packaged default path
(src/repro/tune/tables/cpu_kernels.json) to make it the committed
artifact, or point consumers at it explicitly
(``repro.launch.serve --tuning-table PATH``, ``repro.tune.install``).

``--bench-out`` merges the sweep's trajectory records into a
BENCH_kernels.json-style file: previous ``phase == "sweep"`` records are
replaced, everything else (the kernel_bench records benchmarks/run.py
writes) is preserved.
"""
from __future__ import annotations

import argparse
import inspect
import json
import pathlib

from repro.launch.compile_cache import init_compile_cache
from repro.tune.sweep import SMOKE_CONFIG, run_sweep
from repro.tune.table import provenance


def merge_bench(path, records) -> None:
    """Merge sweep records into a BENCH file, preserving non-sweep records."""
    path = pathlib.Path(path)
    payload = {"schema": 2, "smoke": False, "meta": provenance(),
               "records": []}
    if path.exists():
        try:
            old = json.loads(path.read_text())
            payload["smoke"] = bool(old.get("smoke", False))
            payload["records"] = [r for r in old.get("records", ())
                                  if r.get("phase") != "sweep"]
        except (json.JSONDecodeError, AttributeError, TypeError) as e:
            print(f"[sweep] {path} unreadable ({e}); rewriting", flush=True)
    payload["records"].extend(records)
    path.write_text(json.dumps(payload, indent=1) + "\n")
    print(f"[sweep] merged {len(records)} sweep records into {path} "
          f"({len(payload['records'])} total)", flush=True)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(
        description="config-driven ExecutionPolicy sweep -> tuning table")
    ap.add_argument("--config", help="JSON sweep config (docs/tuning.md)")
    ap.add_argument("--smoke", action="store_true",
                    help="run the built-in tiny grid (CI)")
    ap.add_argument("--out", default="tuning_table.json",
                    help="where to write the tuning table")
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help="merge trajectory records into this "
                         "BENCH_kernels.json-style file")
    ap.add_argument("--kernels-only", action="store_true",
                    help="skip the config's serve section")
    args = ap.parse_args(argv)
    if args.smoke == bool(args.config):
        ap.error("pass exactly one of --smoke or --config")
    init_compile_cache()
    if args.smoke:
        config = dict(SMOKE_CONFIG)
        # candidate rejections point at the literal grid, file:name
        source = f"{inspect.getsourcefile(run_sweep)}:SMOKE_CONFIG"
    else:
        config = json.loads(pathlib.Path(args.config).read_text())
        source = str(args.config)
    if args.kernels_only:
        config = {k: v for k, v in config.items() if k != "serve"}

    result = run_sweep(config, source=source)
    out = result.table.save(args.out)
    if args.bench_out:
        merge_bench(args.bench_out, result.records)
    summary = {
        "config": config.get("name", "unnamed"),
        "entries": len(result.table),
        "records": len(result.records),
        "rejected": result.rejected,
        "table": str(out),
    }
    print(f"[sweep] {json.dumps(summary)}", flush=True)
    return summary


if __name__ == "__main__":
    main()
