"""Trace reduction on a small trace recorded on a TPU v5e: two dozen
steps of the hot serving cell (``fixtures/serve_hot.xplane.pb``)."""
from __future__ import annotations

import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import trace  # noqa: E402

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixtures" / \
    "serve_hot.xplane.pb"
KERNEL = r"/_bitserial_(gemm|fused)_call$"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(str(FIXTURE))


def test_busy_and_gaps_cover_the_window(red):
    assert red["n_devices"] == 1 and red["n_device_ops"] > 0
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] + sum(red["gaps"].values()) == pytest.approx(
        red["window_s"], rel=1e-9)
    assert set(red["gaps"]) <= {"bench.step", "bench.submit",
                                "bench.generate", "outside"}
    assert red["gaps"]["bench.step"] > 0


def test_ops_have_stable_names(red):
    assert red["ops"]
    for name in red["ops"]:
        assert not re.search(r"\.\d+$", name), name
        assert "=" not in name and "(" not in name, name
    assert sum(red["ops"].values()) >= red["busy_s"] * (1 - 1e-9)
    top = red["top_ops"]
    assert 1 <= len(top) <= 10
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)


def test_the_bitserial_kernel_is_found(red):
    secs = trace.kernel_seconds(red, KERNEL)
    assert secs is not None and 0 < secs < red["busy_s"]
    assert trace.kernel_seconds(red, r"no_such_kernel") is None


@pytest.mark.parametrize("ivs,merged", [
    ([], []),
    ([(0, 2), (1, 3)], [[0, 3]]),
    ([(0, 1), (2, 3)], [[0, 1], [2, 3]]),
    ([(5, 6), (0, 10), (2, 3)], [[0, 10]]),
    ([(0, 1), (1, 2)], [[0, 2]]),
])
def test_merge_intervals(ivs, merged):
    assert trace.merge_intervals(ivs) == merged


@pytest.mark.parametrize("op,module,want", [
    ("%_bitserial_gemm_call.6 = s32[512,128]{1,0} custom-call(u32[8])",
     "jit__fwd(11617168509800000493)", "jit__fwd/_bitserial_gemm_call"),
    ("%copy-done = s32[16,1]{0,1} copy-done(%copy-start)",
     "jit_dynamic_update_slice(12870765468995636225)",
     "jit_dynamic_update_slice/copy-done"),
    ("%fusion.12 = f32[512,16]{1,0} fusion(%a, %b)", None, "fusion"),
])
def test_stable_name(op, module, want):
    assert trace.stable_name(op, module) == want


def test_span_lookup():
    spans = [(0, 10, "bench.step"), (12, 14, "bench.submit")]
    starts = [s for s, _, _ in spans]
    assert trace._span_at(spans, starts, 5) == "bench.step"
    assert trace._span_at(spans, starts, 11) == "outside"
    assert trace._span_at(spans, starts, 13) == "bench.submit"
    assert trace._span_at(spans, starts, -1) == "outside"
