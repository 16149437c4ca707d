"""Share of the window's batches whose composed tile entry came from the
engine's memo of composed batches (``serve.compose`` records with
``composed_hit`` 1; program counter, ``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.attr_pct(rec, "serve.compose", "composed_hit")
