"""Nodes of every request answered by the window's close, over the
window."""


def read(rec):
    if rec.get("kind") != "serve" or rec["window_s"] <= 0:
        return None
    return rec["nodes"] / rec["window_s"]
