"""The program's own spans and counters, on the profiler's clock.

``span(name)`` times a stage where the work happens (decide,
checkpoint save and restore, migration); ``count(name, n)`` adds to a
counter at the same boundaries.  Both record only while a profiler trace
is active (``jax.profiler.TraceAnnotation.is_enabled()``) or inside
``with recording():``.  Otherwise a span costs one ``is_enabled()`` call
and returns a shared no-op, allocating nothing.

While recording, a span also enters ``TraceAnnotation("repro:<name>")``,
which puts it on the profiler's host timeline beside the device
operations.  Each record keeps its start and end on
``time.perf_counter_ns``, the index of the enclosing span of its thread
(``parent``, -1 at the top), and the time its direct children cover, so
a span's self time is ``self_ns``.

The registry holds the newest session only: it clears at the first
record after a stretch of not recording (a ``recording()`` block always
starts a new session).  It keeps at most ``CAP`` spans and counts the
rest under the counter ``dropped``.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, NamedTuple

from jax.profiler import TraceAnnotation

CAP = 1 << 17

_records: List[list] = []  # [name, parent, start_ns, end_ns, child_ns]
_counters: Dict[str, int] = {}
_lock = threading.Lock()
_local = threading.local()
_forced = 0  # depth of open recording() blocks
_live = False  # a session is being recorded
_session = 0  # bumped at every clear, so stale parents are never linked


class Span(NamedTuple):
    name: str
    parent: int
    start_ns: int
    end_ns: int  # 0 while the span is open
    child_ns: int

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns

    @property
    def self_ns(self) -> int:
        return self.dur_ns - self.child_ns


def _on() -> bool:
    """Whether to record now; opens a new session after a stretch off."""
    global _live, _session
    if not (_forced or TraceAnnotation.is_enabled()):
        _live = False
        return False
    if not _live:
        with _lock:
            _records.clear()
            _counters.clear()
            _session += 1
        _live = True
    return True


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _Span:
    __slots__ = ("name", "idx", "session", "ann")

    def __init__(self, name: str):
        self.name, self.idx = name, -1

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        top = stack[-1] if stack else None
        with _lock:
            self.session = _session
            parent = top.idx if top is not None and top.session == _session else -1
            if len(_records) < CAP:
                self.idx = len(_records)
                _records.append([self.name, parent, 0, 0, 0])
            else:
                _counters["dropped"] = _counters.get("dropped", 0) + 1
        stack.append(self)
        self.ann = TraceAnnotation("repro:" + self.name)
        self.ann.__enter__()
        if self.idx >= 0:
            _records[self.idx][2] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _local.stack.pop()
        with _lock:
            if self.idx >= 0 and self.session == _session:
                rec = _records[self.idx]
                rec[3] = end
                if rec[1] >= 0:
                    _records[rec[1]][4] += end - rec[2]
        return False


def active() -> bool:
    """Whether spans and counters record now, for a caller whose count
    costs work to compute."""
    return _on()


def span(name: str):
    """A context manager timing one stage."""
    return _Span(name) if _on() else _NOOP


def count(name: str, n: int = 1) -> None:
    if _on():
        with _lock:
            _counters[name] = _counters.get(name, 0) + n


@contextlib.contextmanager
def recording():
    """Record with no profiler running, as a new session (tests, or an
    operator without a profiler)."""
    global _forced, _live
    if not _forced:
        _live = False
    _forced += 1
    try:
        yield
    finally:
        _forced -= 1


def spans() -> List[Span]:
    """The session's spans in the order they opened."""
    with _lock:
        return [Span(*r) for r in _records]


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)
