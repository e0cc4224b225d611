"""The program's host spans and layer tables, read beside a profiler trace.

A span is ``(name, start_ns, end_ns)`` on ``time.time_ns()``: the host's
real-time clock, which the profiler also stamps its trace with, so a reader
puts spans and device ops on one clock by the profile's start time.  Spans
go into a bounded ring, so a long-running process keeps only the newest.

Each ``NetworkExecutor`` registers its layer table once, when it is built:
the name of its jitted forward and, per planned layer, the named scope that
holds the layer's device ops, its index, kind, algorithm and the plan's
``predicted_s``.  The tables are bounded too.

Recording is always on; a span costs a few microseconds of host time.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Dict, Iterator, List, Tuple

#: Spans kept: a 10 s window at ~50 executor calls a second records ~2,000.
RING = 1 << 16
#: Layer tables kept: one per executor built (one per batch bucket).
TABLES = 64

Span = Tuple[str, int, int]


class Record:
    """A bounded ring of host spans and the executors' layer tables."""

    def __init__(self, ring: int = RING, tables: int = TABLES):
        self._spans: collections.deque = collections.deque(maxlen=ring)
        self._tables: collections.deque = collections.deque(maxlen=tables)
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        t0 = time.time_ns()
        try:
            yield
        finally:
            t1 = time.time_ns()
            with self._lock:
                self._spans.append((name, t0, t1))

    def register(self, table: Dict[str, Any]) -> None:
        with self._lock:
            self._tables.append(table)

    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def layer_tables(self) -> List[Dict[str, Any]]:
        """Registered tables, oldest first."""
        with self._lock:
            return list(self._tables)


#: The process's record: what ``CompiledCNN.run`` and ``NetworkExecutor``
#: write and a trace reader reads.
RECORD = Record()
span = RECORD.span
