"""Host spans and counters of the counting path, off unless a caller turns
them on.

:func:`span` names a stretch of host work: while recording is on it enters a
``jax.profiler.TraceAnnotation("repro.<name>")``, so the span lands on the
profiler's clock beside the device's operations, and keeps
``(name, parent, start_ns, end_ns, attrs)`` in a bounded ring, the parent
taken from the calling thread's open spans.  :func:`count` adds to a named
counter.  While recording is off (the default) both return after one check
of a module-level flag: no clock read, no annotation, nothing kept.

Spans go in host code only: inside a traced function one would time the
tracing, not the work.  Device work is named by ``jax.named_scope`` in the
traced code instead (``core/table_program.py``).
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, List

import jax

__all__ = ["span", "count", "enable", "disable", "snapshot"]

#: spans kept at most; the oldest go first
RING = 1 << 16

_on = False
_spans: collections.deque = collections.deque(maxlen=RING)
_counters: Dict[str, int] = collections.Counter()
_lock = threading.Lock()
_local = threading.local()
_OFF = contextlib.nullcontext()


def _stack() -> List[str]:
    """The calling thread's open spans, innermost last."""
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


class _Span:
    __slots__ = ("name", "attrs", "parent", "start", "annotation")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.annotation = jax.profiler.TraceAnnotation("repro." + self.name, **self.attrs)
        self.annotation.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        _stack().pop()
        with _lock:
            _spans.append((self.name, self.parent, self.start, end, self.attrs))
        return False


def span(name: str, **attrs):
    """A context manager timing host work under ``name``; ``attrs`` are
    kept with the span and written as the annotation's stats."""
    if not _on:
        return _OFF
    return _Span(name, attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while recording is on."""
    if _on:
        with _lock:
            _counters[name] += n


def enable() -> None:
    """Start a fresh recording: spans and counters from now on."""
    global _on
    with _lock:
        _spans.clear()
        _counters.clear()
    _on = True


def disable() -> None:
    global _on
    _on = False


def snapshot() -> Dict[str, object]:
    """``{"spans": [(name, parent, start_ns, end_ns, attrs)], "counters":
    {name: n}}``, the spans in the order they ended."""
    with _lock:
        spans: List[tuple] = list(_spans)
        return {"spans": spans, "counters": dict(_counters)}
