"""Share of the traced serving window in which no operation ran on the
device (profiler trace)."""


def read(rec):
    t = rec.get("trace") if rec.get("kind") == "serve" else None
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
