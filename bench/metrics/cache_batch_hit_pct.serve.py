"""Share of the window's batches whose members were all in the tile
cache (``ServeStats.cache_hits`` over hits plus misses, as deltas)."""


def read(rec):
    c = rec.get("counters") if rec.get("kind") == "serve" else None
    if not c or c["cache_hits"] + c["cache_misses"] == 0:
        return None
    return 100.0 * c["cache_hits"] / (c["cache_hits"] + c["cache_misses"])
