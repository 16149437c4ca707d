"""Host time per serving step in ``serve.dispatch``: the forward's
arguments (policy, scalars, jump tiles) and the call of the jitted forward
(program span, ``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.phase_ms(rec, "serve.dispatch")
