"""The program's own host spans over the traced window.

The program marks its phases with ``repro.utils.spans.span``, which
records a span (name, start and end in ns, parent, counts) while a
profiler session is on; the traced window is exactly such a session, so
after it the record holds the window's spans. A program without that
module, or a record with nothing in it, gives ``None`` here, and each
reader of these spans then reports nothing.
"""
from __future__ import annotations

import importlib


def recorded():
    """The window's closed spans, or ``None`` where the program records
    none."""
    try:
        spans = importlib.import_module("repro.utils.spans")
    except ImportError:
        return None
    rec = [s for s in spans.recorded() if s.end_ns is not None]
    return rec or None


def count(rec, name: str) -> int:
    """How many spans are named ``name``."""
    return sum(1 for s in rec if s.name == name)


def seconds(rec, name: str) -> float:
    """Total seconds of the spans named ``name``."""
    return 1e-9 * sum(s.end_ns - s.start_ns for s in rec if s.name == name)


def counted(rec, name: str, key: str) -> int:
    """The sum of count ``key`` over the spans named ``name``."""
    return sum(s.counts.get(key, 0) for s in rec if s.name == name)


def per(rec, numerator: float, name: str):
    """``numerator`` over the number of spans named ``name``, in ms;
    ``None`` where there are none."""
    n = count(rec, name)
    return 1e3 * numerator / n if n else None
