"""Host spans on one clock: self time, clipping to a window, and the
innermost span that covers an instant."""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float                       # time.perf_counter() seconds
    end: float
    span_id: Optional[str] = None
    parent_id: Optional[str] = None
    attrs: Dict = dataclasses.field(default_factory=dict)


def from_tracer(spans: Iterable[Dict]) -> List[HostSpan]:
    """Spans as ``repro.runtime.trace.Tracer.spans()`` returns them."""
    return [HostSpan(s["name"], s["start"], s["end"], s["span_id"],
                     s["parent_id"], s.get("attrs", {})) for s in spans]


def _union_length(intervals: List[tuple]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def self_time(spans: Sequence[HostSpan], names: Sequence[str], lo: float,
              hi: float) -> float:
    """Seconds inside [lo, hi] that spans named ``names`` spend outside
    their own child spans, summed over spans (threads add up)."""
    children: Dict[str, List[HostSpan]] = {}
    for s in spans:
        if s.parent_id is not None:
            children.setdefault(s.parent_id, []).append(s)
    total = 0.0
    for s in spans:
        if s.name not in names:
            continue
        own = _clip(s.start, s.end, lo, hi)
        if own is None:
            continue
        kids = [c for c in (_clip(k.start, k.end, *own)
                            for k in children.get(s.span_id, [])) if c]
        total += (own[1] - own[0]) - _union_length(kids)
    return total


def total_time(spans: Sequence[HostSpan], names: Sequence[str], lo: float,
               hi: float) -> float:
    """Seconds inside [lo, hi] covered by spans named ``names``, summed
    over spans."""
    total = 0.0
    for s in spans:
        if s.name in names:
            c = _clip(s.start, s.end, lo, hi)
            if c:
                total += c[1] - c[0]
    return total


def innermost(spans: Sequence[HostSpan], t: float) -> Optional[HostSpan]:
    """The shortest span that covers instant ``t``."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None
                                      or s.end - s.start < best.end - best.start):
            best = s
    return best
