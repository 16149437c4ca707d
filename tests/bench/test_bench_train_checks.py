"""The training cell's check, driven through a whole run at a test size
on the CPU (the harness's look for a chip is skipped): a sound run
passes; the int4 control, half of each batch left out, and a step that
returns its state unchanged each fail at least one compared number."""
from __future__ import annotations

import pathlib
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, registry  # noqa: E402

WL = "gcn-arxiv.train-int"
TINY = {"config": {"scale": 0.01, "cluster_parts": 16},
        "traffic": {"batches": 4}}


def _bm():
    """BENCHMARK.json with the training cell and its metrics added: the
    cell is not in the committed benchmark until it is proven on the chip
    (PERF.md, Open questions)."""
    bm = registry.load_benchmark()
    bm["workloads"].append({"name": WL, "config": "qgtc-gcn-arxiv",
                            "traffic": "train-int", "chips": 1,
                            "why": "integer Cluster-GCN training"})
    bm["end_to_end"].append({"name": "train_step_ms", "unit": "ms",
                             "better": "lower", "bound": 0.25,
                             "source": "host_clock", "workloads": [WL]})
    for name in ("device_idle_pct.train", "bitserial_roofline.train",
                 "mfu_pct.train"):
        bm["per_layer"].append({"name": name, "unit": "%",
                                "better": "lower", "source": "device_trace",
                                "layer": "kernels", "moves": "train_step_ms",
                                "workloads": [WL]})
    return bm


def _failed(out):
    return [k for k, v in out["compared"].items() if v["value"] > v["limit"]]


@pytest.fixture(scope="module")
def cell():
    bm = _bm()
    wl = registry.workload(bm, WL)
    cfg = dict(registry.load_config(bm, wl), **TINY["config"])
    mix = dict(registry.load_traffic(wl), **TINY["traffic"])
    ctx = harness.Context(False)
    ctx.limits = registry.load_limits(wl)
    c = registry.driver("train").Cell(cfg, mix, 2 ** 31 + 3, ctx, 0.3)
    c.setup()
    rec = c.window(0.3)
    c.release()
    c.rec = rec
    return c


def test_sound_run_passes(cell):
    checks = cell.check(cell.rec)
    assert {n for n, _, _ in checks} == {"loss_gap", "grad_gap",
                                         "update_gap"}
    assert all(v <= lim for _, v, lim in checks), checks
    assert cell.rec["steps"] > 0


@pytest.mark.parametrize("tag", ["bits4", "half_batch"])
def test_control_and_half_batch_fail(cell, tag):
    ctrl = cell.control(cell.rec)
    lim = cell.ctx.limits
    over = [k for k in lim if ctrl[f"{k}_{tag}"] > lim[k]]
    assert over, (tag, ctrl)


def test_state_left_unchanged_fails(monkeypatch):
    from repro.train import trainer

    real = trainer._train_step_int

    def frozen(params, ostate, cstate, dbatch, *a, **kw):
        _, _, cstate, loss, acc = real(params, ostate, cstate, dbatch,
                                       *a, **kw)
        return params, ostate, cstate, loss, acc

    monkeypatch.setattr(trainer, "_train_step_int", frozen)
    out = harness.run_cell(WL, 5, 0.3, False, overrides=TINY,
                           compile_cache=False, bm=_bm())
    assert out["correct"] is False
    assert {"grad_gap", "update_gap"} <= set(_failed(out))


def test_half_the_batch_left_out_fails(monkeypatch):
    from repro.train import trainer

    real = trainer._train_step_int

    def half(params, ostate, cstate, dbatch, *a, **kw):
        m = np.asarray(dbatch["mask"]).copy()
        m[np.flatnonzero(m)[1::2]] = False
        return real(params, ostate, cstate, dict(dbatch, mask=jnp.asarray(m)),
                    *a, **kw)

    monkeypatch.setattr(trainer, "_train_step_int", half)
    out = harness.run_cell(WL, 6, 0.3, False, overrides=TINY,
                           compile_cache=False, bm=_bm())
    assert out["correct"] is False
    assert _failed(out)


def test_a_whole_run_reports_its_metrics():
    out = harness.run_cell(WL, 7, 0.3, False, overrides=TINY,
                           compile_cache=False, bm=_bm())
    assert out["correct"] is True, out["compared"]
    assert set(out["metrics"]) == {"setup_s", "train_step_ms"}
    assert out["attempted"] > 0 and out["failed"] == 0
    rec = {"kind": "train", "window_s": 1.0, "steps": 4,
           "cfg": dict(registry.load_config(_bm(), registry.workload(
               _bm(), WL)), **TINY["config"]),
           "mix": {"grad_bits": 8}, "device_kind": "TPU v5 lite",
           "step_batches": [{"sizes": [100, 110], "cross_edges": 30}] * 4,
           "trace": {"busy_s": 0.5, "window_s": 1.0,
                     "ops": {"jit__train_step_int/_bitserial_gemm_call": 0.2}}}
    for name in ("train_step_ms", "device_idle_pct.train",
                 "bitserial_roofline.train", "mfu_pct.train"):
        value = registry.reader(name).read(rec)
        assert value is not None and 0 < value, name
    assert registry.reader("bitserial_roofline.train").read(rec) <= 100
