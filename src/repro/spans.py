"""Host spans of the program, kept in memory and in the profiler's trace.

``span(name, **attrs)`` opens a ``jax.profiler.TraceAnnotation``, so a
profiler trace shows the span on its own clock beside the device ops, and on
exit appends a :class:`Span` to a bounded record that a reader can consult
after the object that did the work is gone.  Attributes set on the yielded
dict while the span is open are how a counter attaches to the span of the
layer that produced it.

The record is process-wide because its readers (a benchmark's metric
readers, a service's status page) hold no handle on the solver that wrote
it; it is bounded so that a long-running service cannot grow it without end.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import NamedTuple

import jax

__all__ = ["Span", "Recorder", "span", "records", "RECORDER"]


class Span(NamedTuple):
    name: str
    parent: str | None  # the span still open on this thread when this one began
    start_ns: int  # time.perf_counter_ns()
    end_ns: int
    attrs: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class Recorder:
    """The newest ``maxlen`` closed spans, oldest first."""

    def __init__(self, maxlen: int = 4096):
        self._closed = collections.deque(maxlen=maxlen)
        self._open = threading.local()

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        stack = self._open.__dict__.setdefault("stack", [])
        parent = stack[-1] if stack else None
        stack.append(name)
        start = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield attrs
        finally:
            stack.pop()
            end = time.perf_counter_ns()
            self._closed.append(Span(name, parent, start, end, attrs))

    def records(self, name: str | None = None) -> list:
        """Closed spans named ``name`` (all of them if ``None``), oldest first."""
        return [s for s in list(self._closed) if name is None or s.name == name]


RECORDER = Recorder()
span = RECORDER.span
records = RECORDER.records
