"""Declarative ExecutionPolicy sweep: measure a grid, persist the winners.

A sweep CONFIG is a plain dict (usually a JSON file — see docs/tuning.md)
describing a measurement grid:

  name           — stamped into the emitted table's provenance metadata
  ops            — subset of ("bgemm", "bitserial_mm", "bitserial_fused")
  bits           — operand bitwidths (bgemm cells run only at 1 bit)
  sparsity_bands — zeroed fractions of A's reduction dim (tile-aligned
                   band, kernel_bench-style)
  shapes         — [m, k, n] shape buckets
  backend        — backend name the candidates run on (default "pallas")
  candidates     — list of ExecutionPolicy field-override dicts applied
                   over DEFAULT_POLICY ({} = the hand-picked default arm)
  iters/warmup   — timing repeats (median) / warm-up runs per arm

Every candidate is asserted bit-identical against the dense ``xla_dot``
reference AS it is timed — a sweep doubles as a cross-backend exactness
gate, exactly like benchmarks/kernel_bench.py. Invalid candidates (e.g. a
tile grid ExecutionPolicy rejects) are not errors: they are recorded in
``SweepResult.rejected`` with the construction-time ValueError message
prefixed by the offending location (``<config source>:candidates[i]``),
so generated candidate grids get fast, legible rejection that points back
at the grid that produced the bad override.

Timed arms also become BENCH_kernels.json-style trajectory records
(``phase: "sweep"``) so `repro.launch.sweep --bench-out` can merge the
measurement history into the tracked perf file.
"""
from __future__ import annotations

import dataclasses
import inspect

import jax.numpy as jnp
import numpy as np

from repro import api
from repro.api.policy import DEFAULT_POLICY, ExecutionPolicy
from repro.core import bitops, zerotile
from repro.kernels import sgt as sgt_lib
from repro.perf.report import bench_median
from repro.tune.table import (TableEntry, TuningTable, policy_to_dict,
                              provenance)

__all__ = ["KERNEL_OPS", "DEFAULT_CANDIDATES", "SMOKE_CONFIG",
           "SweepResult", "run_sweep"]

KERNEL_OPS = ("bgemm", "bitserial_mm", "bitserial_fused")

# dispatch-layer op name -> the historical BENCH_kernels.json spelling
_BENCH_OP = {"bitserial_mm": "bitserial_gemm"}

DEFAULT_CANDIDATES = (
    {},                              # the hand-picked DEFAULT_POLICY arm
    {"jump": "mask"},
    {"jump": "compact"},
    {"jump": "sgt"},                 # sparse-graph translation (kernels/sgt)
    {"mode": "mxu"},
    {"block_m": 16, "block_w": 8},
)

# Tiny grid for `repro.launch.sweep --smoke` (CI): one shape, two bands,
# four candidates — one of them (block_m=12) deliberately invalid to
# exercise the legible-rejection path end to end.
SMOKE_CONFIG = {
    "name": "smoke",
    "ops": ["bgemm", "bitserial_mm"],
    "bits": [1, 2],
    "sparsity_bands": [0.0, 0.9],
    "shapes": [[16, 256, 16]],
    "backend": "pallas",
    "candidates": [{}, {"jump": "compact"}, {"jump": "sgt"},
                   {"block_m": 12}],
    "iters": 2,
    "warmup": 1,
}


@dataclasses.dataclass
class SweepResult:
    table: TuningTable
    records: list        # BENCH-style trajectory records (phase: "sweep")
    rejected: list       # [{candidate, source, error}] — invalid overrides,
                         # error prefixed with the offending source:candidates[i]


def _banded(rng, m, k, bits, sparsity):
    """s-bit operand with a leading zero band covering ``sparsity`` of K
    (tile-aligned under any block split — kernel_bench's generator)."""
    a = rng.integers(1, 1 << bits, (m, k)).astype(np.int32)
    z = int(k * sparsity)
    if z:
        a[:, :z] = 0
    return a


def _cells(config):
    for op in config.get("ops", KERNEL_OPS):
        if op not in KERNEL_OPS:
            raise ValueError(f"unknown sweep op {op!r} "
                             f"(expected one of {KERNEL_OPS})")
        for bits in config.get("bits", (1, 2, 4)):
            if op == "bgemm" and bits != 1:
                continue  # bgemm is the 1-bit kernel by definition
            for band in config.get("sparsity_bands", (0.0, 0.5, 0.9)):
                for shape in config.get("shapes", ((64, 2048, 64),)):
                    m, k, n = (int(x) for x in shape)
                    yield op, int(bits), float(band), (m, k, n)


def _candidates(raw, rejected, source="config"):
    """Validate policy-override dicts; invalid ones -> rejected, legibly.

    ``source`` is the offending location (config path or caller
    ``file:line``); each candidate is tagged ``{source}:candidates[i]`` so
    a rejection in a generated grid points back at the construction site,
    not just at the ValueError text.  Returns ``(override, policy,
    source_tag)`` triples for the valid candidates."""
    out = []
    for i, ov in enumerate(raw):
        src = f"{source}:candidates[{i}]"
        try:
            pol = DEFAULT_POLICY.replace(**dict(ov))
        except (TypeError, ValueError) as e:
            rejected.append({"candidate": dict(ov), "source": src,
                             "error": f"{src}: {e}"})
            continue
        out.append((dict(ov), pol, src))
    return out


def _cell_runner(op, backend, ap, bp, alpha, beta):
    """One callable per cell: dispatch with an EXPLICIT backend+policy.

    Explicit policy means `resolve` never consults the active tuning
    table here — the sweep measures candidates, it must not recurse into
    its own output.
    """
    def run(pol, tiles=None):
        if op == "bgemm":
            return api.bgemm(ap[0], bp[0], backend=backend, policy=pol,
                             tiles=tiles)
        if op == "bitserial_mm":
            return api.bitserial_mm_packed(ap, bp, backend=backend,
                                           policy=pol, tiles=tiles)
        return api.bitserial_fused(ap, bp, alpha, beta, out_bits=4,
                                   relu=True, backend=backend, policy=pol,
                                   tiles=tiles)
    return run


def _sweep_cell(op, bits, band, shape, backend, cands, iters, warmup,
                rng, log):
    m, k, n = shape
    a = _banded(rng, m, k, bits, band)
    b = rng.integers(0, 1 << bits, (k, n)).astype(np.int32)
    ap = bitops.pack_a(jnp.asarray(a), bits)
    bp = bitops.pack_b(jnp.asarray(b), bits)
    alpha = jnp.full((m, 1), 0.01, jnp.float32)
    beta = jnp.zeros((1, n), jnp.float32)
    run = _cell_runner(op, backend, ap, bp, alpha, beta)
    # dense reference on the registration-default engine: parity target
    ref = np.asarray(_cell_runner(op, "xla_dot", ap, bp, alpha, beta)(
        DEFAULT_POLICY))
    tiles_by_grid = {}
    sgt_by_bm = {}
    records, arms = [], []
    for ov, pol, _src in cands:
        tiles = None
        if pol.jump == "compact":
            grid = (pol.block_m, pol.block_w)
            if grid not in tiles_by_grid:
                # precomputed artifacts with the true max count — the
                # eager/serving contract the compact path is honest under
                tiles_by_grid[grid] = zerotile.compact_artifacts(ap, *grid)
            tiles = tiles_by_grid[grid]
        elif pol.jump == "sgt":
            # translation artifacts depend only on block_m (word-granular
            # remap), so they survive block_w-varying candidates
            if pol.block_m not in sgt_by_bm:
                sgt_by_bm[pol.block_m] = sgt_lib.sgt_artifacts(ap,
                                                               pol.block_m)
            tiles = sgt_by_bm[pol.block_m]
        out = np.asarray(run(pol, tiles))
        np.testing.assert_array_equal(
            out, ref, err_msg=(f"sweep parity: {op} {bits}b z{band} "
                               f"{shape} {backend} candidate {ov}"))
        ms = bench_median(run, pol, tiles, warmup=warmup, iters=iters) * 1e3
        rec = {
            "op": _BENCH_OP.get(op, op), "bits": bits, "sparsity": band,
            "jump": pol.jump, "median_ms": round(ms, 3),
            "m": m, "k": k, "n": n, "backend": backend,
            "phase": "sweep", "candidate": dict(ov),
            "policy": policy_to_dict(pol),
        }
        records.append(rec)
        arms.append((ms, ov, pol, rec))
    best_ms, best_ov, best_pol, best_rec = min(arms, key=lambda x: x[0])
    best_rec["best"] = True
    baseline = next((ms for ms, ov, _, _ in arms if not ov), None)
    entry = TableEntry(op=op, bits=bits, sparsity_band=band,
                       shape_bucket=shape, policy=best_pol, backend=backend,
                       median_ms=round(best_ms, 3),
                       baseline_ms=(round(baseline, 3)
                                    if baseline is not None else None))
    log(f"[sweep] {op} {bits}b z{band} {shape}: best={best_ov or 'default'} "
        f"{best_ms:.3f}ms" + (f" (default {baseline:.3f}ms)"
                              if baseline is not None else ""))
    return entry, records


# -------------------------------------------------------------------- driver

def run_sweep(config: dict, *, log=print, source: str | None = None
              ) -> SweepResult:
    """Measure the config's grid; returns the table + trajectory records.

    ``source`` names where the config came from (its JSON path, or e.g.
    ``".../sweep.py:SMOKE_CONFIG"``) so candidate rejections carry the
    offending location; when omitted it falls back to ``config["source"]``
    and then to the caller's ``file:line``."""
    if source is None:
        source = config.get("source")
    if source is None:
        caller = inspect.stack()[1]
        source = f"{caller.filename}:{caller.lineno}"
    rejected: list = []
    cands = _candidates(config.get("candidates", DEFAULT_CANDIDATES),
                        rejected, source=source)
    if not cands:
        raise ValueError(
            f"no valid policy candidates in config "
            f"{config.get('name', '?')!r}: {rejected}")
    backend = config.get("backend", "pallas")
    iters = int(config.get("iters", 3))
    warmup = int(config.get("warmup", 1))
    rng = np.random.default_rng(int(config.get("seed", 0)))
    entries, records = [], []
    for op, bits, band, shape in _cells(config):
        entry, recs = _sweep_cell(op, bits, band, shape, backend, cands,
                                  iters, warmup, rng, log)
        entries.append(entry)
        records.extend(recs)
    meta = provenance({
        "config": config.get("name", "unnamed"),
        "generated_by": "repro.launch.sweep",
        "candidates": [dict(ov) for ov, _, _ in cands],
    })
    table = TuningTable(entries, meta=meta)
    for rej in rejected:
        log(f"[sweep] rejected candidate {rej['candidate']}: "
            f"{rej['error']}")
    return SweepResult(table=table, records=records, rejected=rejected)
