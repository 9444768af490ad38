"""The program's own spans and counters (``repro.telemetry``), as the
readers of ``program_span`` and ``program_counter`` metrics take them.

The program records only while a profiler trace is active, and keeps the
newest such session, so after a traced run its registry holds the traced
window's work alone.  Every function here returns ``None`` where the
checkout's program has no such module (an older commit) or the window
recorded none of what is asked.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

#: the decide stages that copy operands to the device, launch the kernel
#: and wait for its answer
ROUNDTRIP = ("decide.put", "decide.launch", "decide.fetch")


def registry():
    """``(spans, counters)`` of the program's newest session; a span
    still open has ``end_ns`` 0."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    return telemetry.spans(), telemetry.counters()


def per_span(names: Sequence[str], per: str) -> Optional[float]:
    """Summed self seconds of the closed spans named in ``names``, over
    the number of closed spans named ``per``."""
    reg = registry()
    if reg is None:
        return None
    spans = [s for s in reg[0] if s.end_ns]
    n = sum(s.name == per for s in spans)
    own = [s.self_ns for s in spans if s.name in names]
    if not n or not own:
        return None
    return sum(own) * 1e-9 / n


def decide_split() -> Optional[Tuple[float, float]]:
    """Milliseconds per ``decide`` call ``(host, roundtrip)``: the
    roundtrip is the self time of its ``ROUNDTRIP`` children, the host
    part the rest of the call, so the two add up to the call."""
    reg = registry()
    if reg is None:
        return None
    spans = reg[0]
    calls = {i for i, s in enumerate(spans) if s.name == "decide" and s.end_ns}
    if not calls:
        return None
    total = sum(spans[i].dur_ns for i in calls)
    trip = sum(s.self_ns for s in spans
               if s.parent in calls and s.name in ROUNDTRIP and s.end_ns)
    return (total - trip) * 1e-6 / len(calls), trip * 1e-6 / len(calls)


def counter_share(part: str, whole: str) -> Optional[float]:
    """``part`` over ``whole`` of the session's counters, in percent."""
    reg = registry()
    if reg is None or not reg[1].get(whole):
        return None
    return 100.0 * reg[1].get(part, 0) / reg[1][whole]
