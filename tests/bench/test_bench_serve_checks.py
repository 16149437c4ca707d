"""The serving cells' check, driven through a whole run at a test size on
the CPU (the harness's look for a chip is skipped): sound runs pass, the
lower-precision control (int4) and a 7-bit program fail, and an answer
altered where the engine produces it makes ``correct`` false."""
from __future__ import annotations

import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, registry  # noqa: E402

TINY = {"config": {"scale": 0.01, "cluster_parts": 16},
        "traffic": {"check_batches": 8, "bucket_sample": 8}}
CELLS = [w["name"] for w in registry.load_benchmark()["workloads"]
         if registry.load_traffic(w)["driver"] == "serve"]


# cells not committed until they are proven on the chip (PERF.md, Open
# questions); their mixes, limits and reference are kept and checked here
GIN = {"name": "gin-arxiv.serve-hot", "config": "qgtc-gin-arxiv",
       "traffic": "serve-hot", "chips": 1, "why": "GIN on the hot mix"}
COLD = {"name": "gcn-arxiv.serve-cold", "config": "qgtc-gcn-arxiv",
        "traffic": "serve-cold", "chips": 1, "why": "the cold mix"}
EXTRA = {w["name"]: w for w in (GIN, COLD) if w["name"] not in CELLS}


def _bm():
    bm = registry.load_benchmark()
    bm["configs"].append({"name": "qgtc-gin-arxiv",
                          "file": "bench/configs/qgtc-gin-arxiv.json"})
    if COLD["name"] not in CELLS:
        bm["workloads"].append(COLD)
        for m in bm["end_to_end"] + bm["per_layer"]:
            if "workloads" in m and "gcn-arxiv.serve-hot" in m["workloads"]:
                m["workloads"].append(COLD["name"])
    return bm


def _cell(workload, seed):
    bm = _bm()
    wl = EXTRA.get(workload) or registry.workload(bm, workload)
    cfg = dict(registry.load_config(bm, wl), **TINY["config"])
    mix = dict(registry.load_traffic(wl), **TINY["traffic"])
    ctx = harness.Context(False)
    ctx.limits = registry.load_limits(wl)
    cell = registry.driver(mix["driver"]).Cell(cfg, mix, seed, ctx, 0.5)
    cell.setup()
    rec = cell.window(0.5)
    cell.release()
    return cell, rec


@pytest.mark.parametrize("workload", CELLS + list(EXTRA))
def test_program_passes_and_control_fails(workload):
    cell, rec = _cell(workload, 2 ** 31 + 7)
    limit = cell.ctx.limits["logit_gap"]
    (name, value, lim), = cell.check(rec)
    assert name == "logit_gap" and lim == limit
    assert value < limit
    ctrl = cell.control(rec)
    assert ctrl["logit_gap_bits4"] > limit
    assert ctrl["logit_gap_bits7"] > limit
    assert rec["failed"] == 0 and rec["completed"] > 0
    assert len(rec["latencies_s"]) == rec["attempted"]


@pytest.mark.parametrize("k", [1, 2, 40])
def test_induced_edges_match_the_graphs_own_subgraph(k):
    from repro.graph import datasets

    csr = datasets.load("ogbn-arxiv", scale=0.01, seed=0).csr
    rng = np.random.default_rng(k)
    nodes = rng.choice(csr.n, min(csr.n, 113 * k), replace=False)
    drv = registry.driver("serve")
    remap = np.full(csr.n, -1, np.int64)
    got = drv.induced_edges(csr.indptr, csr.indices, remap, nodes)
    want = csr.subgraph(nodes).edge_list()
    assert got.dtype == np.int32 and np.array_equal(got, want)
    assert (remap == -1).all()


def test_closed_loop_keeps_every_client_waiting():
    """Every client has one request out until the close, so each batch of
    the window is as full as the budget allows and only the clients' last
    requests are answered after it."""
    cell, rec = _cell("gcn-arxiv.serve-hot", 2 ** 33 + 5)
    clients = cell.mix["clients"]
    assert rec["after_close"] == clients
    assert rec["attempted"] == rec["completed"] + clients
    per_batch = max(len(s) for s in rec["step_sizes"])
    in_window = [s for (_, end), s in zip(rec["step_times"],
                                          rec["step_sizes"])
                 if end <= rec["t_close"]]
    assert in_window and all(len(s) == per_batch for s in in_window)
    assert rec["nodes"] == sum(sum(s) for s in in_window)


def test_an_altered_answer_fails(monkeypatch):
    from repro.serve import engine

    step = engine.GNNServer.step

    def altered(self, return_logits=False):
        out = step(self, return_logits=return_logits)
        if not return_logits:
            return out
        for rid in list(out)[:1]:
            preds, logits = out[rid]
            logits = np.array(logits)
            logits[0] += 0.5 * np.abs(logits).max()
            out[rid] = (preds, logits)
        return out

    monkeypatch.setattr(engine.GNNServer, "step", altered)
    out = harness.run_cell("gcn-arxiv.serve-hot", 11, 0.5, False,
                           overrides=TINY, compile_cache=False)
    assert out["correct"] is False
    assert out["compared"]["logit_gap"]["value"] > \
        out["compared"]["logit_gap"]["limit"]


def test_a_whole_run_is_correct_and_reports_its_metrics():
    out = harness.run_cell("gcn-arxiv.serve-cold", 12, 0.5, False,
                           overrides=TINY, compile_cache=False, bm=_bm())
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "serve_nodes_per_s"}
    assert list(out)[-1] == "compared"
    assert out["compared"]["programs_in_window"]["value"] == 0
