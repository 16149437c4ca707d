"""Host time per serving step in ``serve.tile_build``: slicing the batch
adjacency and building the tile entries of the members the cache missed
(program span, ``bench/spans.py``)."""
from bench import spans


def read(rec):
    return spans.phase_ms(rec, "serve.tile_build")
