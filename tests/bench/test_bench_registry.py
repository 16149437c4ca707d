"""The benchmark finds every piece of a cell by name, and BENCHMARK.json
keeps to the benchmark's contract."""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import generator, registry  # noqa: E402

BM = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51
    assert 1 <= len(BM["paths"]) <= 16
    for p in BM["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    assert len(BM["command"]) <= 32
    assert (ROOT / BM["command"][1]).is_file()
    assert any(BM["command"][1].startswith(p + "/") for p in BM["paths"])
    assert len(json.dumps(BM)) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)


def test_names_are_unique_and_well_formed():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BM[section]]
        assert len(names) == len(set(names)), section
        assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("wl", BM["workloads"], ids=lambda w: w["name"])
def test_workload_resolves_to_its_files(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert wl["chips"] in (1, 4) and 1 <= len(wl["why"]) <= 200
    assert NAME.match(wl["traffic"]) and NAME.match(wl["config"])
    cfg = registry.load_config(BM, wl)
    mix = registry.load_traffic(wl)
    assert registry.driver(mix["driver"]).Cell
    assert registry.reference(cfg["reference"]).forward
    limits = registry.load_limits(wl)
    assert limits and all(v > 0 for v in limits.values())
    section = registry.metrics_for(BM, wl["name"], "end_to_end")
    names = {m["name"] for m in section}
    assert "setup_s" in names and len(names) >= 2
    assert registry.metrics_for(BM, wl["name"], "per_layer")


@pytest.mark.parametrize("cfg", BM["configs"], ids=lambda c: c["name"])
def test_config_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert any(cfg["file"].startswith(p + "/") for p in BM["paths"])
    sizes = json.loads((ROOT / cfg["file"]).read_text())
    assert len(cfg["reduced"]) <= 16
    assert all(k in sizes for k in cfg["reduced"])
    assert any(w["config"] == cfg["name"] for w in BM["workloads"])
    files = [c["file"] for c in BM["configs"]]
    assert files.count(cfg["file"]) == 1


@pytest.mark.parametrize("m", BM["end_to_end"] + BM["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry_has_a_reader(m):
    e2e = {x["name"]: x for x in BM["end_to_end"]}
    assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert callable(registry.reader(m["name"]).read)
    if m["name"] in e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
    else:
        allowed = {"name", "unit", "better", "source", "layer", "moves",
                   "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["workloads"]
        for w in m["workloads"]:
            reported = {x["name"] for x in registry.metrics_for(
                BM, w, "end_to_end")}
            assert m["moves"] in reported, (m["name"], w)
    assert set(m) <= allowed
    if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
        assert m["unit"] == "%"


def test_every_layer_name_is_spelled_one_way():
    layers = {m["layer"] for m in BM["per_layer"]}
    assert {x.lower() for x in layers} == {x for x in layers}


REQUEST_MIXES = sorted(
    p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")
    if "parts_per_request" in json.loads(p.read_text()))


@pytest.mark.parametrize("mix_name", REQUEST_MIXES)
def test_generator_is_seeded(mix_name):
    mix = json.loads((ROOT / "bench" / "traffic" / f"{mix_name}.json")
                     .read_text())

    def head(seed, n=64):
        s = generator.PartStream(mix, 1500, seed)
        return [next(s) for _ in range(n)]

    big = 2 ** 31 + 12345
    assert head(big) == head(big)
    assert head(big) != head(big + 1)
    stream = head(7, 400)
    assert all(len(t) == mix["parts_per_request"] for t in stream)
    if mix.get("unique"):
        assert len(set(stream)) == len(stream)


def test_hot_population_is_the_same_for_every_seed():
    mix = json.loads((ROOT / "bench/traffic/serve-hot.json").read_text())
    a = generator.PartStream(mix, 1500, 1)
    b = generator.PartStream(mix, 1500, 2)
    assert list(a.members) == list(b.members)
    assert len(a.members) == 375


def test_run_without_a_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         BM["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_run_outside_a_checkout_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BM["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         BM["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120, env=dict(os.environ, PYTHONPATH=""))
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_new_pieces_need_only_new_files(tmp_path):
    """A throwaway configuration, mix and metric in a copy of the
    benchmark resolve without editing any file already there."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    (tmp_path / "bench/configs/toy.json").write_text(json.dumps(
        dict(json.loads((ROOT / "bench/configs/qgtc-gcn-arxiv.json")
                        .read_text()), hidden=32)))
    mix = json.loads((ROOT / "bench/traffic/serve-hot.json").read_text())
    (tmp_path / "bench/traffic/toy-mix.json").write_text(
        json.dumps(dict(mix, clients=2)))
    (tmp_path / "bench/limits/toy.serve.json").write_text(
        json.dumps({"logit_gap": 0.1}))
    (tmp_path / "bench/metrics/toy_count.serve.py").write_text(
        "def read(rec):\n    return rec.get('completed')\n")
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "toy", "source": "x",
                          "file": "bench/configs/toy.json", "reduced": [],
                          "why": "throwaway"})
    bm["workloads"].append({"name": "toy.serve", "config": "toy",
                            "traffic": "toy-mix", "chips": 1,
                            "why": "throwaway"})
    bm["per_layer"].append({"name": "toy_count.serve", "unit": "req",
                            "better": "higher", "source": "host_clock",
                            "layer": "serving engine",
                            "moves": "serve_nodes_per_s",
                            "workloads": ["toy.serve"]})
    for m in bm["end_to_end"]:
        if m["name"] == "serve_nodes_per_s":
            m["workloads"].append("toy.serve")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p
    wl = registry.workload(bm, "toy.serve")
    assert registry.load_config(bm, wl, tmp_path)["hidden"] == 32
    assert registry.load_traffic(wl, tmp_path)["clients"] == 2
    assert registry.load_limits(wl, tmp_path) == {"logit_gap": 0.1}
    names = [m["name"] for m in registry.metrics_for(bm, "toy.serve",
                                                     "per_layer")]
    assert "toy_count.serve" in names
    assert registry.reader("toy_count.serve", tmp_path).read(
        {"completed": 3}) == 3
