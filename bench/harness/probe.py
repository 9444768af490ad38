"""Benchmark-side instruments: host spans around the calls into each
layer, and a count of compilations.

A span records its wall time on the host clock and, at the same time,
writes a ``jax.profiler.TraceAnnotation`` named ``bench:<name>``, so a
traced run can tell what the host was doing while the device sat idle.
"""
from __future__ import annotations

import contextlib
import os
import time
import traceback
from collections import defaultdict
from typing import Dict, List

import jax

class Probe:
    def __init__(self):
        self.spans: Dict[str, List[float]] = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation("bench:" + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans[name].append(time.perf_counter() - t0)

    def timed(self, name: str, fn):
        """``fn`` wrapped in a span of its own."""
        def wrapper(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapper


class CompileCounter:
    """Counts XLA compilations while armed, and keeps where each was
    asked for.  JAX reports each request for an executable as a
    monitoring event from inside the call that makes it, whether it is
    compiled or loaded from the persistent cache; a load also reports a
    cache hit, so compilations are the requests less the hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.armed = False
        self.requests = 0
        self.cache_hits = 0
        self.where: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    @property
    def compiles(self) -> int:
        return self.requests - self.cache_hits

    def _on_event(self, event: str, **kwargs) -> None:
        if self.armed and event == self.HIT:
            self.cache_hits += 1

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and event == self.EVENT:
            self.requests += 1
            frames = [f for f in traceback.extract_stack()
                      if "/repro/" in f.filename or "/bench/" in f.filename]
            self.where.append(f"{duration:.3f} s at " + " < ".join(
                f"{os.path.basename(f.filename)}:{f.lineno}"
                for f in reversed(frames[-4:])))
