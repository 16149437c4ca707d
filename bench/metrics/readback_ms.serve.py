"""Host time per serving step in ``serve.readback``: the logits copied to
the host and split into per-request predictions (program span,
``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.phase_ms(rec, "serve.readback")
