"""Named spans and counters of the LiLAC pass and the serving engine.

``span(name, **stats)`` wraps a block of host work.  It does two things:

* it enters a ``jax.profiler.TraceAnnotation`` of the same name, so while
  a profiler trace runs the span lands in the ``.xplane.pb`` on the same
  clock as the device's operations;
* it always adds its duration to one in-memory table, name -> ``count``,
  ``total_s``, ``max_s``, which ``totals()`` returns.  A span begun while
  a profiler trace records is added to a second table as well, which
  ``totals(traced=True)`` returns: the spans of a traced window alone.

Names start with ``lilac.`` (the pass) or ``serve.`` (the engine); ids
such as a request's go in ``stats`` (``rid=...``), never in the name, so
spans sum by name.  ``count(name, n)`` adds to a counter kept in the same
table (``count`` only).  ``annotate(name, **stats)`` adds stats to the
innermost open span from code that does not hold it (a conversion inside
the data plane's ``lilac.marshal``).  With no profiler running a span
costs two clock reads, one dict update and the annotation's inactive
check.

The table is process-wide and never trimmed: one entry per name.
"""
from __future__ import annotations

import threading
from time import perf_counter_ns
from typing import Dict

from jax.profiler import TraceAnnotation

_profiling = TraceAnnotation.is_enabled
_SPANS: Dict[str, list] = {}        # name -> [count, total ns, max ns]
_TRACED: Dict[str, list] = {}       # the same, of spans a profiler recorded
_COUNTS: Dict[str, int] = {}
_OPEN = threading.local()           # .stack: this thread's annotated spans


def _add(table: Dict[str, list], name: str, dt: int):
    rec = table.get(name)
    if rec is None:
        table[name] = [1, dt, dt]
    else:
        rec[0] += 1
        rec[1] += dt
        if dt > rec[2]:
            rec[2] = dt


class span:
    """Context manager: ``with span("lilac.bake"): ...``.  ``set(**stats)``
    adds stats known only at the end of the block (bytes produced) to the
    profiler's event.  The annotation is made only while a profiler runs."""

    __slots__ = ("_name", "_stats", "_ann", "_t0")

    def __init__(self, name: str, **stats):
        self._name = name
        self._stats = stats

    def __enter__(self) -> "span":
        self._ann = (TraceAnnotation(self._name, **self._stats)
                     if _profiling() else None)
        if self._ann is not None:
            self._ann.__enter__()
            _open_stack().append(self)
        self._t0 = perf_counter_ns()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        dt = perf_counter_ns() - self._t0
        _add(_SPANS, self._name, dt)
        if self._ann is not None:
            _open_stack().pop()
            self._ann.__exit__(et, ev, tb)
            _add(_TRACED, self._name, dt)
        return False

    def set(self, **stats):
        if self._ann is not None:
            self._ann.set_metadata(**stats)


def _open_stack() -> list:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


def annotate(name: str, **stats):
    """Add ``stats`` to the profiler's event of this thread's innermost
    open span if it is named ``name``; a no-op with no profiler running."""
    stack = getattr(_OPEN, "stack", None)
    if stack and stack[-1]._name == name:
        stack[-1].set(**stats)


def count(name: str, n: int = 1):
    """Add ``n`` to the counter ``name``."""
    _COUNTS[name] = _COUNTS.get(name, 0) + n


def totals(traced: bool = False) -> Dict[str, Dict[str, float]]:
    """Every span as ``{"count", "total_s", "max_s"}`` and every counter as
    ``{"count"}``, by name.  ``traced``: only the spans begun while a
    profiler trace recorded, and no counters."""
    out: Dict[str, Dict[str, float]] = {
        name: {"count": c, "total_s": t / 1e9, "max_s": m / 1e9}
        for name, (c, t, m) in (_TRACED if traced else _SPANS).items()}
    if not traced:
        out.update({name: {"count": n} for name, n in _COUNTS.items()})
    return dict(sorted(out.items()))


def reset():
    """Clear the tables."""
    _SPANS.clear()
    _TRACED.clear()
    _COUNTS.clear()
