"""Program spans: named host intervals of the protocol and the state objects.

``span(name, **attrs)`` is a context manager around one piece of work: a
persist, an epoch wait, a barrier, a restore, a store write, one leaf of a
trainer snapshot. The recorder is off by default, and then ``span`` returns
one shared no-op context: a flag check, with no clock read and no append.
``enable()`` turns it on. Each span then records its ``perf_counter_ns``
start and end, its thread, its id, its parent (the innermost span open on
the same thread when it began, or the span it was handed off from: see
``current``) and its attrs into a bounded buffer that
``records()`` returns; a full buffer counts what it drops. Where JAX is
already imported, the span also opens ``jax.profiler.TraceAnnotation``
named ``dse.<name>`` with the same attrs, so a profiler trace shows it on
the host timeline, on the clock its device events are mapped onto.

The module never imports JAX itself. Spans read the real clock and take no
lock that blocks, so under the deterministic simulator (DESIGN.md §8) they
change nothing the scheduler sees.
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from typing import Dict, List, NamedTuple

#: prefix of a span's name in the profiler's trace
PREFIX = "dse."
#: records the default recorder keeps before it counts drops
CAPACITY = 1 << 20


class Record(NamedTuple):
    """One ended span. ``parent`` is 0 for a thread's outermost span."""

    name: str
    start_ns: int
    end_ns: int
    thread: int
    id: int
    parent: int
    attrs: Dict[str, object]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class _Noop:
    """The context every ``span`` returns while the recorder is off."""

    __slots__ = ()

    def __enter__(self) -> "_Noop":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        """Attrs known only inside the span; nothing to record while off."""


_NOOP = _Noop()


class Recorder:
    """A bounded in-memory buffer of ended spans, with per-thread nesting."""

    def __init__(self, capacity: int = CAPACITY) -> None:
        self.capacity = capacity
        self.on = False
        self.dropped = 0
        self._records: List[Record] = []
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()

    def span(self, name: str, *, parent: int = 0, **attrs):
        if not self.on:
            return _NOOP
        return _Span(self, name, attrs, parent)

    def current(self) -> int:
        stack = self._stack()
        return stack[-1] if self.on and stack else 0

    def records(self) -> List[Record]:
        with self._lock:
            return list(self._records)

    def clear(self) -> None:
        with self._lock:
            self._records = []
            self.dropped = 0

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, rec: Record) -> None:
        with self._lock:
            if len(self._records) < self.capacity:
                self._records.append(rec)
            else:
                self.dropped += 1


class _Span:
    __slots__ = ("_rec", "name", "attrs", "_id", "_parent", "_start", "_ann")

    def __init__(self, rec: Recorder, name: str, attrs: Dict[str, object],
                 parent: int = 0) -> None:
        self._rec = rec
        self.name = name
        self.attrs = attrs
        self._parent = parent

    def __enter__(self) -> "_Span":
        stack = self._rec._stack()
        if not self._parent and stack:
            self._parent = stack[-1]
        self._id = next(self._rec._ids)
        stack.append(self._id)
        profiler = sys.modules.get("jax.profiler")
        self._ann = None
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(PREFIX + self.name, **self.attrs)
            self._ann.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._append(Record(self.name, self._start, end, threading.get_ident(),
                                 self._id, self._parent, self.attrs))

    def set(self, **attrs) -> None:
        """Add attrs known only inside the span (a persist's version). They
        reach the record; the profiler's copy keeps the attrs it began with."""
        self.attrs.update(attrs)


#: the process's recorder, which the program's spans write to
RECORDER = Recorder()


def span(name: str, *, parent: int = 0, **attrs):
    """A span named ``name``; the shared no-op context while off. A
    ``parent`` (an id from ``current``) stands in for the innermost span open
    on this thread: the span is work handed off from a span on another
    thread."""
    if not RECORDER.on:
        return _NOOP
    return _Span(RECORDER, name, attrs, parent)


def current() -> int:
    """The id of the innermost span open on this thread, to hand off to
    work another thread does for it; 0 where none is, or the recorder is
    off."""
    return RECORDER.current()


def enable() -> None:
    RECORDER.on = True


def disable() -> None:
    RECORDER.on = False


def enabled() -> bool:
    return RECORDER.on


def records() -> List[Record]:
    """The ended spans, in the order they ended."""
    return RECORDER.records()


def dropped() -> int:
    """Spans that ended while the buffer was full."""
    return RECORDER.dropped


def clear() -> None:
    """Forget every record and the drop count; on or off stays as it was."""
    RECORDER.clear()
