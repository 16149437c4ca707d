"""Small arithmetic shared by the metric readers."""
from __future__ import annotations

import json
import pathlib

__all__ = ["percentile", "peaks"]

_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def percentile(xs, q: float) -> float | None:
    """Nearest-rank percentile (q in [0, 100]); None for no samples."""
    if not len(xs):
        return None
    s = sorted(xs)
    rank = -(-q * len(s) // 100)
    return float(s[min(max(int(rank), 1), len(s)) - 1])


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a chip missing from the table is an
    error, never a default."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
