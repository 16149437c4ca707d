"""Mean duration of one ``GNNServer.step()`` over the run's steps (harness
span)."""


def read(rec):
    steps = rec.get("step_times") if rec.get("kind") == "serve" else None
    if not steps:
        return None
    return sum(e - s for s, e in steps) / len(steps) * 1e3
