"""Host time per serving step in ``serve.coalesce``: FIFO run selection
and the host concatenation of the batch (``MicroBatcher.next_plan``)
(program span, ``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.phase_ms(rec, "serve.coalesce")
