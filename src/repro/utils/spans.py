"""Named host spans of the program, for the JAX profiler.

``span(name, **counts)`` marks a stretch of host work. With no profiler
session on it is a shared no-op context. While a session is on (any
``jax.profiler`` capture: ``start_trace``/``stop_trace``, ``trace``, or
the profiler server) it enters a ``TraceAnnotation``, so the span lands
in the capture's host plane on the device's clock with its counts as
event stats, and it appends the span to an in-memory record.
``recorded()`` returns the record as ``Span(name, start_ns, end_ns,
parent, counts)``, ``parent`` being the record index of the span open
around it on the same thread, and ``clear()`` empties it; a caller
clears before a capture and reads after it to get that capture's spans.
"""
from __future__ import annotations

import threading
import time
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

# spans past this many are annotated but not recorded, so a long capture
# that nobody clears cannot grow the record without bound
MAX_RECORDED = 1 << 18


class Span(NamedTuple):
    name: str
    start_ns: int  # time.perf_counter_ns()
    end_ns: Optional[int]  # None while the span is open
    parent: Optional[int]  # index of the enclosing span in the record
    counts: dict


# each entry is [name, start_ns, end_ns, enclosing entry, counts]; a
# span keeps its own entry, so it closes it without a lock or an index
_record: list = []
_local = threading.local()


class _Null:
    """What ``span`` returns while no profiler session is on."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **counts):
        pass


_NULL = _Null()


class _Open:
    __slots__ = ("annotation", "entry", "stack")

    def __init__(self, name: str, counts: dict):
        self.annotation = TraceAnnotation(name, **counts)
        self.entry = [name, 0, None, None, counts]

    def __enter__(self):
        stack = self.stack = getattr(_local, "stack", None)
        if stack is None:
            stack = self.stack = _local.stack = []
        self.annotation.__enter__()
        entry = self.entry
        entry[3] = stack[-1] if stack else None
        entry[1] = time.perf_counter_ns()
        if len(_record) < MAX_RECORDED:
            _record.append(entry)
        stack.append(entry)
        return self

    def __exit__(self, *exc):
        self.entry[2] = time.perf_counter_ns()
        self.stack.pop()
        self.annotation.__exit__(*exc)
        return False

    def set(self, **counts):
        """Add counts known only once the span is open."""
        self.annotation.set_metadata(**counts)
        self.entry[4].update(counts)


def span(name: str, **counts):
    """Context manager marking host work ``name`` with integer ``counts``;
    a no-op unless a profiler session is on. The object it yields takes
    further counts with ``.set(**counts)``."""
    if not TraceAnnotation.is_enabled():
        return _NULL
    return _Open(name, counts)


def recorded() -> list:
    """The spans recorded since the last ``clear()``, in the order they
    opened (open spans have ``end_ns`` None)."""
    entries = list(_record)
    index = {id(e): i for i, e in enumerate(entries)}
    return [Span(name, start, end,
                 None if parent is None else index.get(id(parent)),
                 dict(counts))
            for name, start, end, parent, counts in entries]


def clear() -> None:
    _record.clear()
