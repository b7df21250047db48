"""Host spans of the log tier: one name, two sinks.

``with span("store.append_sessions", sessions=n, events=e):`` opens a
``jax.profiler.TraceAnnotation`` of that name, so a profiler trace shows
the span on the same clock as the device ops, its counts as the host
event's stats; and on close it appends a ``Span`` record to a bounded
in-memory buffer, which ``recent()`` reads without a profiler.

Spans sit at batch boundaries only (a tick, a day, a store append), never
per session or per event: one costs a few microseconds. Counts are given
when the span opens, or, where the block itself learns them, set in the
dict the span yields (``with span("store.encode_payloads") as counts:
... counts["bytes"] = n``) and recorded when it closes. A span never
synchronizes with the device: the ``*.wait`` spans wrap reads that block
anyway.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

# The buffer holds the newest RECENT spans; older ones fall off.
RECENT = 4096


class Span(NamedTuple):
    name: str
    parent: str | None      # the innermost span open on this thread
    start_ns: int           # time.perf_counter_ns()
    end_ns: int
    counts: dict[str, int]


_buffer: collections.deque[Span] = collections.deque(maxlen=RECENT)
_open = threading.local()


@contextlib.contextmanager
def span(name: str, **counts: int):
    """Time the block as ``name``; ``counts``, and those the block adds
    to the yielded dict, label it in both sinks."""
    stack = _open.__dict__.setdefault("stack", [])
    parent = stack[-1] if stack else None
    stack.append(name)
    opened = dict(counts)
    start = time.perf_counter_ns()
    try:
        with TraceAnnotation(name, **opened) as annotation:
            yield counts
            late = {k: v for k, v in counts.items() if opened.get(k) != v}
            if late:
                annotation.set_metadata(**late)
    finally:
        end = time.perf_counter_ns()
        stack.pop()
        _buffer.append(Span(name, parent, start, end, counts))


def recent() -> list[Span]:
    """The newest spans, in the order they closed (a child before its
    parent)."""
    return list(_buffer)
