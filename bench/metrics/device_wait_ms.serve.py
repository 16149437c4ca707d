"""Host time per serving step in ``serve.device_wait``: the host blocked
on the logits (the device's queued work, the composition's programs
included) (program span, ``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.phase_ms(rec, "serve.device_wait")
