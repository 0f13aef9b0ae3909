"""Exact-boundary windows: a rate stops its numerator and its
denominator at the same completed unit.

A unit is whatever completes whole: a durable commit group for ingest,
a query for query cells. The window opens at a completed unit after
warm-up and closes at the last unit that completed by ``seconds`` after
the opening. Units that straddle either boundary count on neither side,
so a run never gains or loses part of a unit at random.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Window:
    start: float        # host clock of the opening unit's completion
    end: float          # host clock of the last unit completed in time
    units: float        # work completed in (start, end]
    completions: int    # marks in (start, end]

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def rate(self) -> float:
        """Units per second over the exact window."""
        return self.units / self.seconds

    @property
    def seconds_per_completion(self) -> float:
        return self.seconds / self.completions


def close_window(marks: Sequence[Tuple[float, float]], start: Tuple[float, float],
                 seconds: float) -> Window:
    """``marks`` are (host time, cumulative units) at each completion, in
    time order; ``start`` is the opening mark. The window ends at the last
    mark no later than ``start[0] + seconds``."""
    t0, u0 = start
    deadline = t0 + seconds
    inside = [(t, u) for t, u in marks if t0 < t <= deadline]
    if not inside:
        raise RuntimeError(
            f"no unit completed within {seconds} s of the window's opening; "
            "the window is shorter than one unit")
    t1, u1 = inside[-1]
    return Window(start=t0, end=t1, units=u1 - u0, completions=len(inside))
