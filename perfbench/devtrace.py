"""Device busy time from a ``jax.profiler`` trace, on the host's clock.

``DeviceTrace`` starts the profiler and drops one annotation whose host
time is known, so every event of the trace maps onto
``time.perf_counter()``, the clock of the program's spans. ``reduce``
takes a window on that clock and returns, averaged over the chips used,
the seconds in which an operation ran on the device (the union of the
ops' intervals), the ops that took most time, and the longest idle gaps
named by the innermost host span that covers them.
"""
from __future__ import annotations

import dataclasses
import glob
import re
import shutil
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from perfbench.spans import HostSpan, innermost

SYNC = "perfbench.sync"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class DeviceSummary:
    busy_s: float                      # mean over chips of busy seconds
    window_s: float
    device_ops: List[list]             # [[name, seconds], ...] on chip 0
    idle_gaps: List[list]              # [[host span name, seconds], ...]


class DeviceTrace:
    def __init__(self, directory: Path):
        self.directory = Path(directory)
        self.t_sync: Optional[float] = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.directory), profiler_options=opts)
        with jax.profiler.TraceAnnotation(SYNC):
            self.t_sync = time.perf_counter()

    def stop(self) -> Path:
        import jax
        jax.profiler.stop_trace()
        found = sorted(glob.glob(str(self.directory / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.directory}")
        return Path(found[-1])


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def innermost_ops(events: List[tuple]) -> List[tuple]:
    """The ops that enclose no other op (a ``while`` encloses its body's)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= e[2]]


def device_events(profile) -> Dict[int, List[tuple]]:
    """chip id -> [(name, start_s, end_s)] of its XLA ops, trace clock."""
    out: Dict[int, List[tuple]] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                evs.append((op_name(ev.name), ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9))
        out[int(m.group(1))] = evs
    return out


def sync_time(profile) -> float:
    """Trace-clock seconds of the sync annotation."""
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SYNC:
                    return ev.start_ns * 1e-9
    raise RuntimeError(f"the trace holds no {SYNC!r} annotation")


def reduce(profile, t_sync: float, lo: float, hi: float, chips: int,
           spans: Sequence[HostSpan] = (), top: int = 10) -> DeviceSummary:
    """Busy time, top ops and idle gaps inside [lo, hi] (host clock)."""
    offset = sync_time(profile) - t_sync
    per_chip = device_events(profile)
    if len(per_chip) < chips:
        names = {p.name: sorted({ln.name for ln in p.lines}) for p in profile.planes}
        raise RuntimeError(f"the trace has {len(per_chip)} TPU planes, the cell "
                           f"uses {chips}; planes and lines: {names}")
    busy, gaps_by = [], defaultdict(float)
    ops_by = defaultdict(float)
    for rank, chip in enumerate(sorted(per_chip)[:chips]):
        clipped = []
        for name, s, e in per_chip[chip]:
            s, e = max(s - offset, lo), min(e - offset, hi)
            if e > s:
                clipped.append((name, s, e))
        if rank == 0:
            for name, s, e in innermost_ops(clipped):
                ops_by[name] += e - s
        merged = _union([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged))
        if rank == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    span = innermost(spans, (s + e) / 2)
                    gaps_by[span.name if span else "host.other"] += e - s
    rank_top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                    key=lambda kv: -kv[1])[:top]]
    return DeviceSummary(busy_s=sum(busy) / len(busy), window_s=hi - lo,
                         device_ops=rank_top(ops_by), idle_gaps=rank_top(gaps_by))


def load(path: Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))
