"""Self time per serving step: ``serve.step`` less the union of its
phases, the part of the step the program's spans leave unexplained
(program span, ``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.self_ms(rec)
