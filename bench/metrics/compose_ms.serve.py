"""Host time per serving step in ``serve.compose``: the memo lookup of
composed batch entries and ``compose_entries`` over the members' cached
tile entries (program span, ``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.phase_ms(rec, "serve.compose")
