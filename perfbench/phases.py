"""Phases of the program's spans, clipped to the window.

The program's tracer (``repro.runtime.trace``) times the steps inside a
span as phases, ``[name, start, end, cpu_s]`` in the span's
``attrs["phases"]``, on the clock of the spans; every span it records
also carries its thread-CPU seconds as ``attrs["cpu_s"]``. A program
without phases records neither, and the readers built on this module
then read None: no phases is no reading, not a reading of nothing.
"""
from __future__ import annotations

from typing import Iterator, Optional, Sequence, Tuple


# the decide phases spent on the host, without the oracle's ``label``
DECIDE_HOST = ("threshold", "known", "need", "merge")


def recorded(r) -> bool:
    """Whether the run's program records phases."""
    return any("cpu_s" in s.attrs for s in r.spans)


def clipped(r, spans: Optional[Sequence[str]], names: Sequence[str]
            ) -> Iterator[Tuple[float, float, float, Optional[float]]]:
    """``(start, end, share, cpu_s)`` of each phase named ``names`` of the
    spans named ``spans`` (every span when None), clipped to the window;
    ``share`` is the part of the phase inside it."""
    lo, hi = r.window.start, r.window.end
    for s in r.spans:
        if spans is not None and s.name not in spans:
            continue
        for name, start, end, cpu_s in s.attrs.get("phases", ()):
            if name not in names:
                continue
            a, b = max(start, lo), min(end, hi)
            if b > a:
                yield a, b, (b - a) / (end - start), cpu_s


def share(r, spans: Sequence[str], names: Sequence[str]) -> Optional[float]:
    """Seconds of the phases in the window over the window, in percent;
    None when the program records no phases or has no such span."""
    if not recorded(r) or not any(s.name in spans for s in r.spans):
        return None
    seconds = sum(b - a for a, b, _, _ in clipped(r, spans, names))
    return 100.0 * seconds / r.window.seconds


def wall_and_cpu(r, spans: Sequence[str], names: Sequence[str]
                 ) -> Optional[Tuple[float, float]]:
    """Wall and thread-CPU seconds of the phases in the window, a phase
    that straddles an edge counting its CPU in proportion."""
    if not recorded(r) or not any(s.name in spans for s in r.spans):
        return None
    wall = cpu = 0.0
    for a, b, part, cpu_s in clipped(r, spans, names):
        wall += b - a
        cpu += part * (cpu_s or 0.0)
    return wall, cpu

