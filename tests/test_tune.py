"""repro.tune: table semantics, the dispatch fallback chain, sweep harness.

Every test pins its table explicitly (``use_table``) so outcomes never
depend on whether the committed artifact is present.
"""
import json
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro import tune
from repro.api.policy import DEFAULT_POLICY, ExecutionPolicy
from repro.core import bitops
from repro.tune.table import TableEntry, TuningTable


def _entry(op="bitserial_mm", bits=2, band=0.0, shape=(8, 64, 8),
           jump="mask", **pol_kw):
    return TableEntry(op=op, bits=bits, sparsity_band=band,
                      shape_bucket=shape,
                      policy=ExecutionPolicy(jump=jump, **pol_kw),
                      backend="pallas", median_ms=1.0)


# --------------------------------------------------------- table round trip

def test_table_roundtrip_and_replacement(tmp_path):
    t = TuningTable([_entry()], meta={"note": "x"})
    t.put(_entry(jump="compact"))  # same cell key -> replaces
    assert len(t) == 1
    assert t.lookup("bitserial_mm", bits=2).policy.jump == "compact"
    p = t.save(tmp_path / "t.json")
    t2 = TuningTable.load(p)
    assert len(t2) == 1 and t2.meta["note"] == "x"
    assert t2.lookup("bitserial_gemm", bits=2).policy.jump == "compact"
    # ^ BENCH-spelling alias resolves to the same cells


@pytest.mark.parametrize("payload, match", [
    ("{nope", "unusable"),
    (json.dumps({"entries": []}), "missing schema_version"),
    (json.dumps({"schema_version": 99, "entries": []}), "stale"),
    (json.dumps({"schema_version": 1, "entries": [{"op": "bgemm"}]}),
     "missing"),
    (json.dumps({"schema_version": 1, "entries": [
        {"op": "bgemm", "bits": 1, "sparsity_band": 0.0,
         "shape_bucket": [8, 64, 8],
         "policy": {"block_m": 12}}]}), "multiple of 8"),
])
def test_bad_table_files_warn_and_disable(tmp_path, payload, match):
    """Corrupt/stale/malformed files: warn once + None, or raise in strict
    mode (the sweep-smoke CI validator)."""
    p = tmp_path / "bad.json"
    p.write_text(payload)
    with pytest.warns(RuntimeWarning, match="falling back"):
        assert TuningTable.load(p) is None
    with pytest.raises(ValueError, match=match):
        TuningTable.load(p, strict=True)


def test_missing_table_warns_and_dispatch_survives(tmp_path):
    with pytest.warns(RuntimeWarning, match="not found"):
        with tune.use_table(tmp_path / "never_written.json"):
            # the chain degrades to DEFAULT_POLICY, never crashes dispatch
            a = jnp.asarray(np.arange(12, dtype=np.int32).reshape(3, 4) & 3)
            out = api.bitserial_mm(a, a.T, 2, 2)
            want = (np.asarray(a, np.int64) @ np.asarray(a.T, np.int64))
            np.testing.assert_array_equal(np.asarray(out), want)
            assert tune.active_table() is None


# ------------------------------------------------------------ nearest bucket

def test_nearest_bucket_resolution():
    t = TuningTable([
        _entry(band=0.0, shape=(8, 64, 8), jump="none"),
        _entry(band=0.9, shape=(8, 64, 8), jump="compact"),
        _entry(band=0.0, shape=(64, 2048, 64), jump="mask"),
    ])
    # exact band, nearest shape
    assert t.lookup("bitserial_mm", bits=2, sparsity=0.0,
                    shape=(48, 1500, 48)).policy.jump == "mask"
    assert t.lookup("bitserial_mm", bits=2, sparsity=0.0,
                    shape=(8, 80, 8)).policy.jump == "none"
    # band dominates shape: a sparse query lands on the sparse cell even
    # at the far shape
    assert t.lookup("bitserial_mm", bits=2, sparsity=0.8,
                    shape=(64, 2048, 64)).policy.jump == "compact"
    # unknown sparsity counts as dense (conservative: jumping never pays)
    assert t.lookup("bitserial_mm", bits=2,
                    shape=(8, 64, 8)).policy.jump == "none"
    # bits nearest on a log scale
    t2 = TuningTable([_entry(bits=1, jump="none"),
                      _entry(bits=8, jump="mask")])
    assert t2.lookup("bitserial_mm", bits=6).policy.jump == "mask"
    # unknown op: no opinion
    assert t.lookup("wq_mm") is None


# ------------------------------------------------------- dispatch precedence

def test_dispatch_precedence_explicit_beats_table_beats_default():
    table = TuningTable([_entry(jump="mask", block_m=16)])
    with tune.use_table(table):
        # table fills silence
        _, pol = api.resolve("bitserial_mm", s=2, t=2, shape=(8, 64, 8))
        assert pol.jump == "mask" and pol.block_m == 16
        # explicit per-call policy beats the table
        _, pol = api.resolve("bitserial_mm", s=2, t=2, shape=(8, 64, 8),
                             policy=DEFAULT_POLICY)
        assert pol == DEFAULT_POLICY
        # a use() context policy beats the table
        with api.use(policy=ExecutionPolicy(jump="compact")):
            _, pol = api.resolve("bitserial_mm", s=2, t=2, shape=(8, 64, 8))
            assert pol.jump == "compact" and pol.block_m == 8
        # tuned=False (precomputed tile artifacts in flight) skips the table
        _, pol = api.resolve("bitserial_mm", s=2, t=2, shape=(8, 64, 8),
                             tuned=False)
        assert pol == DEFAULT_POLICY
    with tune.use_table(None):  # tuning disabled -> the hand-picked default
        _, pol = api.resolve("bitserial_mm", s=2, t=2, shape=(8, 64, 8))
        assert pol == DEFAULT_POLICY


def test_dispatch_results_identical_with_and_without_table():
    """Tuning is advisory: a table-picked policy changes performance only."""
    rng = np.random.default_rng(3)
    a = rng.integers(0, 4, (9, 70)).astype(np.int32)
    b = rng.integers(0, 8, (70, 5)).astype(np.int32)
    want = a.astype(np.int64) @ b
    table = TuningTable([_entry(bits=3, jump="compact", mode="mxu")])
    for backend in api.list_backends():
        with tune.use_table(table):
            got = api.bitserial_mm(jnp.asarray(a), jnp.asarray(b), 2, 3,
                                   backend=backend)
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=backend)


def test_tiles_dispatch_never_consults_table():
    """tiles= artifacts are built on DEFAULT_POLICY's grid; a table entry
    with a different grid must not be swapped under them."""
    from repro.core import zerotile

    rng = np.random.default_rng(5)
    a = rng.integers(0, 4, (16, 256)).astype(np.int32)
    a[:, 64:192] = 0
    b = rng.integers(0, 4, (256, 8)).astype(np.int32)
    ap = bitops.pack_a(jnp.asarray(a), 2)
    tiles = zerotile.compact_artifacts(ap, DEFAULT_POLICY.block_m,
                                       DEFAULT_POLICY.block_w)
    table = TuningTable([_entry(shape=(16, 256, 8),
                                jump="compact", block_m=16, block_w=8)])
    with tune.use_table(table):
        got = api.bitserial_mm(jnp.asarray(a), jnp.asarray(b), 2, 2,
                               backend="pallas", tiles=tiles)
    np.testing.assert_array_equal(np.asarray(got), a.astype(np.int64) @ b)


# ----------------------------------------------------------- sweep harness

def test_sweep_smoke_grid_rejects_and_measures():
    from repro.tune.sweep import run_sweep

    cfg = {
        "name": "unit", "ops": ["bitserial_mm"], "bits": [2],
        "sparsity_bands": [0.9], "shapes": [[16, 256, 16]],
        "backend": "pallas", "iters": 1, "warmup": 1,
        "candidates": [{}, {"jump": "compact"}, {"block_n": 100}],
    }
    res = run_sweep(cfg, log=lambda *_: None, source="unit.json")
    assert len(res.table) == 1
    # the rejection names the offending candidate slot AND keeps the
    # construction-time ValueError text
    assert [r["error"] for r in res.rejected] == [
        "unit.json:candidates[2]: block_n must be a multiple of 128 "
        "(lane width of a packed B tile), got 100"]
    assert [r["source"] for r in res.rejected] == ["unit.json:candidates[2]"]
    e = res.table.entries[0]
    assert e.op == "bitserial_mm" and e.baseline_ms is not None
    # trajectory records: BENCH spelling + phase tag, one per valid arm
    assert [r["op"] for r in res.records] == ["bitserial_gemm"] * 2
    assert all(r["phase"] == "sweep" for r in res.records)
    assert sum(r.get("best", False) for r in res.records) == 1


# ------------------------------------------------------ policy validation

def test_policy_rejects_misaligned_tile_grids():
    with pytest.raises(ValueError, match="multiple of 8"):
        ExecutionPolicy(block_m=12)
    with pytest.raises(ValueError, match="multiple of 128"):
        ExecutionPolicy(block_n=64)
    with pytest.raises(ValueError, match="positive int"):
        ExecutionPolicy(block_w=0)
    # sweep-relevant grids stay constructible
    ExecutionPolicy(block_m=16, block_w=8)
    ExecutionPolicy(block_m=8, block_n=256)


# ----------------------------------------------------------- active table

def test_install_and_context_precedence(tmp_path):
    t_ctx = TuningTable([_entry(jump="mask")])
    t_inst = TuningTable([_entry(jump="compact")])
    try:
        tune.install(t_inst)
        assert tune.active_table() is t_inst
        with tune.use_table(t_ctx):  # context beats install
            assert tune.active_table() is t_ctx
        with tune.use_table(None):   # context can disable
            assert tune.active_table() is None
        assert tune.active_table() is t_inst
    finally:
        tune.install()  # restore AUTO
    assert tune.active_table() is tune.default_table()
