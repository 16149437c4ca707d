"""The whole window over the training steps it ran (the window ends when
the last step's outputs are ready)."""


def read(rec):
    if rec.get("kind") != "train" or not rec["steps"]:
        return None
    return rec["window_s"] / rec["steps"] * 1e3
