"""The profiler trace of a run and its reduction to numbers.

``load_events`` reads the ``.xplane.pb`` the JAX profiler writes into a
flat list of :class:`Event`; ``reduce_events`` turns that list into the
device's busy time, the time per device operation, and the idle gaps,
each labelled by the benchmark span (``bench:<name>``) that was open on
the host at the time.  Readers of per-layer metrics take their numbers
from the :class:`Reduced` result.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

HOST_PREFIX = "bench:"
WINDOW_SPAN = HOST_PREFIX + "window"
OPS_LINE = "XLA Ops"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    meta: str = ""  # string-valued stats, joined (kernel names live here)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def _meta(ev) -> str:
    try:
        return " ".join(str(v) for _, v in ev.stats if isinstance(v, str))
    except (TypeError, ValueError):
        return ""


def load_events(path: str) -> List[Event]:
    """Every event of the device planes and the ``bench:`` spans of the
    host planes in one ``.xplane.pb`` file (or the newest under a
    directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if device:
                    out.append(Event(plane.name, line.name, ev.name,
                                     ev.start_ns, ev.duration_ns, _meta(ev)))
                elif ev.name.startswith(HOST_PREFIX):
                    out.append(Event(plane.name, line.name, ev.name,
                                     ev.start_ns, ev.duration_ns))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint cover of the given intervals."""
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


@dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over the device planes that ran anything
    devices: int
    # device events inside the window, by plane
    ops: List[Event] = field(default_factory=list)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_times(self) -> Dict[str, float]:
        """Self seconds per device operation, summed over the window: an
        operation that contains others (a ``while`` around its body) is
        charged only for the time none of them runs."""
        out: Dict[str, float] = {}
        for e, self_ns in _self_times(self.ops):
            name = short_name(e.name)
            out[name] = out.get(name, 0.0) + self_ns * 1e-9
        return out

    def matching(self, *needles: str) -> List[Event]:
        """Device operations whose name or stats contain any needle."""
        return [e for e in self.ops
                if any(n in e.name or n in e.meta for n in needles)]

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_times().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def short_name(name: str) -> str:
    """An XLA operation's name and result type, without its layout and
    operands: ``%fusion.4 = f32[32,1024]`` out of the HLO text the trace
    carries."""
    head = name.split("{", 1)[0].split("(", 2)
    text = head[0] if not head[0].rstrip().endswith("=") else "(".join(head[:2])
    return text.strip()[:120]


def _self_times(events: Sequence[Event]):
    """(event, self time) for events of one timeline that may nest."""
    out = []
    by_plane: Dict[str, List[Event]] = {}
    for e in events:
        by_plane.setdefault(e.plane, []).append(e)
    for evs in by_plane.values():
        evs = sorted(evs, key=lambda e: (e.start_ns, -e.dur_ns))
        selft = [e.dur_ns for e in evs]
        stack: List[int] = []
        for i, e in enumerate(evs):
            while stack and evs[stack[-1]].end_ns <= e.start_ns:
                stack.pop()
            if stack and e.end_ns <= evs[stack[-1]].end_ns:
                selft[stack[-1]] -= e.dur_ns
            stack.append(i)
        out.extend(zip(evs, selft))
    return out


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def _label(t: float, spans: Sequence[Event]) -> str:
    """The innermost ``bench:`` span open at host time ``t``."""
    best = None
    for s in spans:
        if s.start_ns <= t <= s.end_ns and s.name != WINDOW_SPAN:
            if best is None or s.dur_ns < best.dur_ns:
                best = s
    return best.name[len(HOST_PREFIX):] if best else "outside spans"


def reduce_events(events: Sequence[Event]) -> Reduced:
    """Busy time, per-operation time and labelled idle gaps inside the
    ``bench:window`` span.  Busy is the union of the intervals in which
    an operation ran on a device (line ``XLA Ops``), averaged over the
    devices that ran any."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench:window span")
    w = max(windows, key=lambda e: e.dur_ns)
    lo, hi = w.start_ns, w.end_ns
    spans = [e for e in events if e.name.startswith(HOST_PREFIX)
             and not e.plane.startswith("/device:")]
    by_plane: Dict[str, List[Event]] = {}
    for e in events:
        if e.plane.startswith("/device:") and e.line == OPS_LINE:
            c = _clip(e.start_ns, e.end_ns, lo, hi)
            if c:
                by_plane.setdefault(e.plane, []).append(e)
    busy, gaps, kept = [], [], []
    for plane in sorted(by_plane):
        evs = by_plane[plane]
        kept.extend(evs)
        cover = union([_clip(e.start_ns, e.end_ns, lo, hi) for e in evs])
        busy.append(sum(b - a for a, b in cover))
        if plane == sorted(by_plane)[0]:
            edges = [lo] + [x for ab in cover for x in ab] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((_label((a + b) / 2, spans), (b - a) * 1e-9))
    window_s = (hi - lo) * 1e-9
    n = len(busy)
    return Reduced(window_s=window_s,
                   busy_s=(sum(busy) / n) * 1e-9 if n else 0.0,
                   devices=n, ops=kept, gaps=gaps)


class Session:
    """A profiler session writing under ``log_dir`` with Python-function
    tracing off (it would dwarf the trace and slow the host)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False
