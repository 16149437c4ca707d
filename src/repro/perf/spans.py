"""Program spans: one ``span()`` call, two sinks.

``span(name, **attrs)`` opens ``jax.profiler.TraceAnnotation("qgtc." +
name)``, so a profiler trace shows the span on the same clock as the
device's operations, and on exit appends one :class:`Record` to a
process-wide ring of the last ``RING`` spans, which ``recorded()`` returns.

A record holds stamps, never durations: ``t0``/``t1`` are
``time.perf_counter()`` readings (the clock a caller's own timings use),
and readers subtract. ``parent_id`` is the innermost span open on the same
thread when this one opened (None at a root), so a reader can split a
step into its phases and find the step's self time. ``attrs`` is the dict
the ``with`` statement yields: the body may set counts in it at the
boundary where they happen.

The recorder is always on: a span costs a few microseconds of host time,
against the milliseconds of the serving step it splits.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import NamedTuple

import jax

__all__ = ["span", "recorded", "clear", "Record", "PREFIX", "RING"]

PREFIX = "qgtc."
RING = 1 << 16


class Record(NamedTuple):
    id: int
    parent_id: int | None
    name: str
    t0: float
    t1: float
    attrs: dict


_ring: collections.deque = collections.deque(maxlen=RING)
_ids = itertools.count(1)
_open = threading.local()  # .stack: ids of this thread's open spans


@contextlib.contextmanager
def span(name: str, **attrs):
    """Record the body as span ``name``; yields ``attrs`` for the body to
    add counts to."""
    stack = _open.__dict__.setdefault("stack", [])
    sid = next(_ids)
    parent = stack[-1] if stack else None
    stack.append(sid)
    with jax.profiler.TraceAnnotation(PREFIX + name):
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            t1 = time.perf_counter()
            stack.pop()
            _ring.append(Record(sid, parent, name, t0, t1, attrs))


def recorded() -> list:
    """The ring's records, oldest first (in the order the spans closed)."""
    return list(_ring)


def clear() -> None:
    _ring.clear()
