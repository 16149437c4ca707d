"""Host time per serving step in the ``transfer`` span
(``graph/packing.py``, recorded under ``serve.step``): ``jax.device_put``
of the compound buffer and the dispatch of its device unpack (program
span, ``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.phase_ms(rec, "transfer")
