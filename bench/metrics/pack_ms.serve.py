"""Host time per serving step in the ``pack`` span (``graph/packing.py``,
recorded under ``serve.step``): host quantize, bit-plane pack and
concatenation of the compound buffer (program span, ``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.phase_ms(rec, "pack")
