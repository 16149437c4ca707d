"""Per-step phase times from the program's own spans (``repro.perf.spans``).

A window step is a ``serve.step`` record that starts at or after the run's
``t_start`` and ends by its ``t_end``; its phases are the records whose
parent it is. The ring keeps the latest spans: once it is full, a step
counts only when it began after the oldest record still held closed, so
none of its phases can have been dropped. Every function returns None when the program
records no spans (a checkout from before them) or the run has no window
step.
"""
from __future__ import annotations

from bench.trace import merge_intervals

__all__ = ["window", "phase_ms", "self_ms", "attr_pct"]

STEP = "serve.step"


def window(rec: dict):
    """(window steps by id, their children) of a serving run, or None."""
    if rec.get("kind") != "serve":
        return None
    try:
        from repro.perf import spans
    except ImportError:
        return None
    held = spans.recorded()
    if not held:
        return None
    lo = rec["t_start"]
    if len(held) >= spans.RING:  # full: older spans were dropped
        lo = max(lo, held[0].t1)
    steps = {r.id: r for r in held
             if r.name == STEP and r.t0 >= lo and r.t1 <= rec["t_end"]}
    if not steps:
        return None
    return steps, [r for r in held if r.parent_id in steps]


def phase_ms(rec: dict, name: str):
    """Summed duration of the ``name`` phase per window step, in ms."""
    got = window(rec)
    if got is None:
        return None
    steps, kids = got
    total = sum(r.t1 - r.t0 for r in kids if r.name == name)
    return 1e3 * total / len(steps)


def self_ms(rec: dict):
    """Per window step, the step's duration less the union of its
    phases' intervals (what the spans leave unexplained), in ms."""
    got = window(rec)
    if got is None:
        return None
    steps, kids = got
    by_step: dict = {}
    for r in kids:
        by_step.setdefault(r.parent_id, []).append((r.t0, r.t1))
    total = 0.0
    for sid, r in steps.items():
        covered = sum(e - s for s, e in
                      merge_intervals(by_step.get(sid, [])))
        total += (r.t1 - r.t0) - covered
    return 1e3 * total / len(steps)


def attr_pct(rec: dict, name: str, attr: str):
    """Share, in %, of the window's ``name`` phases whose ``attr`` is 1."""
    got = window(rec)
    if got is None:
        return None
    hits = [r.attrs.get(attr) == 1 for r in got[1] if r.name == name]
    if not hits:
        return None
    return 100.0 * sum(hits) / len(hits)
