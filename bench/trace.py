"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

  busy_s      union of the intervals in which an operation ran on a
              device, inside the traced window, averaged over the devices
  window_s    the traced window: from the first to the last harness span
              (``bench.*``) on the host
  ops         seconds per stable operation name, summed over devices
  gaps        idle seconds per harness span the host was in when the
              device went idle ("outside" when in none)
  top_ops / top_gaps   the ten largest of each, for the result's breakdown

Devices are the planes named ``/device:TPU:<n>``; their operations are the
events of the line named ``XLA Ops``, and the programs they belong to the
events of ``XLA Modules``. An operation's stable name is
``<program>/<instruction>`` with the program's fingerprint and the
instruction's number dropped, e.g. ``jit__fwd/_bitserial_gemm_call``: a
kernel is found by that name (``kernel_seconds``), which survives a
recompile.
"""
from __future__ import annotations

import bisect
import collections
import re

__all__ = ["reduce", "kernel_seconds", "merge_intervals", "stable_name"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_INSTR = re.compile(r"^%?([A-Za-z0-9_\-]+?)(?:\.\d+)?(?:\s*=|$)")
_MODULE = re.compile(r"^(.*?)(?:\(\d+\))?$")


def stable_name(op: str, module: str | None = None) -> str:
    """``<program>/<instruction>`` without fingerprint or number."""
    m = _INSTR.match(op.strip())
    instr = m.group(1) if m else op.split(" ")[0]
    if module is None:
        return instr
    return f"{_MODULE.match(module).group(1)}/{instr}"


def merge_intervals(intervals) -> list:
    """Overlapping or touching (start, end) intervals merged, in order."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce(path: str, span_prefix: str = "bench.", top: int = 10) -> dict:
    pd = _load(path)
    spans = []  # (start_ns, end_ns, name) of harness spans on the host
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(span_prefix):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    if not devices:
        raise RuntimeError(f"no device plane in the trace: "
                           f"{[p.name for p in pd.planes]}")
    if not spans:
        raise RuntimeError("no harness span in the trace")
    w0 = min(s for s, _, _ in spans)
    w1 = max(e for _, e, _ in spans)
    ops = collections.Counter()
    busy, gaps = 0.0, collections.Counter()
    n_ops = 0
    spans.sort()
    starts = [s for s, _, _ in spans]
    for plane in devices:
        ivs = []
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for line in plane.lines if line.name == MODULES_LINE
                      for ev in line.events)
        mod_starts = [m[0] for m in mods]
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= w0 or s >= w1:
                    continue
                i = bisect.bisect_right(mod_starts, s) - 1
                module = mods[i][2] if i >= 0 and mods[i][1] >= s else None
                s, e = max(s, w0), min(e, w1)
                ivs.append((s, e))
                ops[stable_name(ev.name, module)] += (e - s) * 1e-9
                n_ops += 1
        merged = merge_intervals(ivs)
        busy += sum(e - s for s, e in merged) * 1e-9
        prev = w0
        for s, e in merged + [[w1, w1]]:
            if s > prev:
                mid = (prev + s) / 2
                gaps[_span_at(spans, starts, mid)] += (s - prev) * 1e-9
            prev = max(prev, e)
    n_dev = len(devices)
    return {
        "busy_s": busy / n_dev,
        "window_s": (w1 - w0) * 1e-9,
        "n_devices": n_dev,
        "n_device_ops": n_ops,
        "ops": dict(ops),
        "gaps": {k: v / n_dev for k, v in gaps.items()},
        "top_ops": [[k, v] for k, v in ops.most_common(top)],
        "top_gaps": [[k, v / n_dev] for k, v in gaps.most_common(top)],
    }


def _span_at(spans, starts, t) -> str:
    """The harness span covering host time ``t`` (the harness's spans do
    not nest: the last one to start before ``t`` is the only candidate)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][1] >= t:
        return spans[i][2]
    return "outside"


def kernel_seconds(red: dict, pattern: str) -> float | None:
    """Seconds of the operations whose stable name matches ``pattern``;
    None when none ran."""
    rx = re.compile(pattern)
    hits = [v for k, v in red["ops"].items() if rx.search(k)]
    return sum(hits) if hits else None
