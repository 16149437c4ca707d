"""The readers of the program's spans (``bench/spans.py``): each reads a
finite value from a whole serving run at a test size on the CPU in every
cell that lists it, and nothing from a run that has no window step."""
from __future__ import annotations

import math
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(HERE)]

import test_bench_serve_checks as serve_checks  # noqa: E402
from bench import registry, spans  # noqa: E402

SPAN_METRICS = ("coalesce_ms.serve", "compose_ms.serve",
                "tile_build_ms.serve", "pack_ms.serve", "transfer_ms.serve",
                "dispatch_ms.serve", "device_wait_ms.serve",
                "readback_ms.serve", "step_self_ms.serve",
                "composed_hit_pct.serve")
PAIRS = [(m["name"], w) for m in registry.load_benchmark()["per_layer"]
         if m["name"] in SPAN_METRICS for w in m["workloads"]]


@pytest.fixture(scope="module")
def runs():
    """One window per cell, with the ring as it stood after it."""
    from repro.perf import spans as ring

    out = {}

    def get(workload):
        if workload not in out:
            ring.clear()
            _, rec = serve_checks._cell(workload, 2 ** 31 + 99)
            out[workload] = (rec, ring.recorded())
        return out[workload]

    return get


@pytest.mark.parametrize("metric,workload", PAIRS)
def test_reader_is_finite_in_each_listed_cell(metric, workload, runs,
                                              monkeypatch):
    from repro.perf import spans as ring

    rec, held = runs(workload)
    monkeypatch.setattr(ring, "recorded", lambda: list(held))
    value = registry.reader(metric).read(rec)
    assert value is not None and math.isfinite(value) and value >= 0
    if metric.endswith("_pct.serve"):
        assert value <= 100


def test_phases_and_self_time_add_up_to_the_steps(runs, monkeypatch):
    from repro.perf import spans as ring

    rec, held = runs("gcn-arxiv.serve-cold")
    monkeypatch.setattr(ring, "recorded", lambda: list(held))
    steps, kids = spans.window(rec)
    names = {r.name for r in kids}
    parts = sum(spans.phase_ms(rec, n) for n in names)
    whole = 1e3 * sum(r.t1 - r.t0 for r in steps.values()) / len(steps)
    assert parts + spans.self_ms(rec) == pytest.approx(whole)
    assert "serve.tile_build" in names
    # every window step of the closed loop is one served batch
    in_window = [(s, e) for s, e in rec["step_times"]
                 if s >= rec["t_start"] and e <= rec["t_end"]]
    assert len(steps) == len(in_window)


def test_no_window_step_reads_nothing(monkeypatch):
    from repro.perf import spans as ring

    rec = {"kind": "serve", "t_start": 10.0, "t_end": 20.0}
    step = ring.Record(1, None, "serve.step", 5.0, 9.0, {})
    monkeypatch.setattr(ring, "recorded", lambda: [step])
    assert spans.phase_ms(rec, "serve.compose") is None
    assert spans.self_ms(rec) is None
    monkeypatch.setattr(ring, "recorded", lambda: [])
    assert spans.attr_pct(rec, "serve.compose", "composed_hit") is None
    assert spans.window({"kind": "train"}) is None


def test_a_step_whose_phases_left_the_ring_is_not_counted(monkeypatch):
    from repro.perf import spans as ring

    rec = {"kind": "serve", "t_start": 0.0, "t_end": 100.0}
    held = [ring.Record(2, 1, "serve.compose", 1.5, 2.0, {}),  # oldest held
            ring.Record(1, None, "serve.step", 1.0, 3.0, {}),
            ring.Record(4, 3, "serve.compose", 4.0, 5.0, {}),
            ring.Record(3, None, "serve.step", 3.5, 6.0, {})]
    monkeypatch.setattr(ring, "recorded", lambda: held)
    assert list(spans.window(rec)[0]) == [1, 3]  # the ring is not full
    monkeypatch.setattr(ring, "RING", len(held))
    steps, _ = spans.window(rec)
    assert list(steps) == [3]
    assert spans.phase_ms(rec, "serve.compose") == pytest.approx(1e3)
    assert spans.self_ms(rec) == pytest.approx(1.5e3)
