"""Finds every piece of a cell by the names in ``BENCHMARK.json``.

  configuration  ``BENCHMARK.json`` configs[].file (JSON sizes)
  reference      ``bench/reference/<config["reference"]>.py``
  traffic mix    ``bench/traffic/<workload["traffic"]>.json``
  driver         ``bench/drivers/<mix["driver"]>.py``
  metric         ``bench/metrics/<metric name>.py``, a ``read(rec)``
  limits         ``bench/limits/<workload>.json``, the limit of each
                 number the cell's check compares

A new configuration, mix or metric is new files plus new entries; nothing
here changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]

__all__ = ["ROOT", "load_benchmark", "workload", "load_config",
           "load_traffic", "load_limits", "load_module", "driver", "reference", "reader",
           "metrics_for"]


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(bm: dict, name: str) -> dict:
    for wl in bm["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def load_config(bm: dict, wl: dict, root: pathlib.Path = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == wl["config"]:
            cfg = json.loads((root / c["file"]).read_text())
            cfg["name"] = c["name"]
            return cfg
    raise KeyError(f"workload {wl['name']!r} names config "
                   f"{wl['config']!r}, which BENCHMARK.json lacks")


def load_traffic(wl: dict, root: pathlib.Path = ROOT) -> dict:
    mix = json.loads((root / "bench" / "traffic" /
                      f"{wl['traffic']}.json").read_text())
    mix["name"] = wl["traffic"]
    return mix


def load_limits(wl: dict, root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "bench" / "limits" /
                       f"{wl['name']}.json").read_text())


def load_module(path: pathlib.Path, tag: str):
    """Import one file as a module of its own (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{tag}_{path.stem.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "drivers" / f"{name}.py", "driver")


def reference(name: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "reference" / f"{name}.py", "ref")


def reader(name: str, root: pathlib.Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py", "metric")


def metrics_for(bm: dict, wl_name: str, section: str) -> list:
    """The metrics of ``section`` ("end_to_end" | "per_layer") this cell
    reports: those listing it under ``workloads``; without the key, every
    cell for an end-to-end metric, and for a per-layer one every cell
    that reports the end-to-end metric it ``moves``."""
    e2e = {m["name"]: m for m in bm["end_to_end"]}

    def reports(m: dict) -> bool:
        if "workloads" in m:
            return wl_name in m["workloads"]
        if section == "per_layer":
            return reports_e2e(e2e[m["moves"]])
        return True

    def reports_e2e(m: dict) -> bool:
        return "workloads" not in m or wl_name in m["workloads"]

    return [m for m in bm[section] if reports(m)]
