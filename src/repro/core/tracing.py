"""Host spans of the serving path, on the device trace's clock.

``span(name, **attrs)`` marks a region of host work in the scheduler or the
engine. While no JAX profiler session is active it returns one shared null
context: no clock is read and nothing is recorded, so the cost is one
``TraceAnnotation.is_enabled()`` check. While a session is active
(``jax.profiler.start_trace`` or ``jax.profiler.trace``) a span does two
things:

- it enters a ``jax.profiler.TraceAnnotation(name, **attrs)``, so the span
  lands in the profiler's host plane, with its attributes as event stats,
  on the same clock as the device's operations;
- it appends ``[name, start_ns, end_ns, parent, attrs]`` to an in-memory
  record (``time.perf_counter_ns()``; ``parent`` is the index of the
  enclosing span's record, -1 at the top), which ``records()`` returns and
  ``clear()`` empties. The record holds at most ``MAX_RECORDS`` spans;
  later ones reach only the profiler.

``set(**attrs)`` on the object a ``with`` binds adds attributes known only
inside the span (a no-op on the null context). Attributes are host values
the caller already holds: a span never waits on the device or reads from
it. Spans time the wall clock, never an injected ``clock``, so they measure
host time under a :class:`~repro.core.clock.VirtualClock` too.

The record and the stack of open spans are process-wide: the serving loop
runs on one thread, and a benchmark reads the record after its window.
"""
from __future__ import annotations

import time
from typing import List

from jax.profiler import TraceAnnotation

MAX_RECORDS = 1 << 20

_records: List[list] = []
_open: List[int] = []            # record indices of the open spans


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set(self, **attrs) -> None:
        pass


_NULL = _Null()


class _Span:
    __slots__ = ("_ann", "_rec")

    def __init__(self, name: str, attrs: dict):
        self._ann = TraceAnnotation(name, **attrs)
        self._rec = [name, 0, 0, -1, attrs]

    def __enter__(self):
        self._ann.__enter__()
        if _open:
            self._rec[3] = _open[-1]
        if len(_records) < MAX_RECORDS:
            _open.append(len(_records))
            _records.append(self._rec)
        else:
            _open.append(-1)
        self._rec[1] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._rec[2] = time.perf_counter_ns()
        _open.pop()
        return self._ann.__exit__(*exc)

    def set(self, **attrs) -> None:
        self._rec[4].update(attrs)
        self._ann.set_metadata(**attrs)


def span(name: str, **attrs):
    """A context manager for one host span (see the module docstring)."""
    if not TraceAnnotation.is_enabled():
        return _NULL
    return _Span(name, attrs)


def records() -> List[list]:
    """The spans recorded since the last :func:`clear`, in start order."""
    return _records


def clear() -> None:
    """Empty the record; call it while no span is open."""
    _records.clear()
