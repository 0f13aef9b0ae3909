"""What a traced run hands to the per-layer metric readers.

Each per-layer metric of BENCHMARK.json has a reader,
``perfbench/metrics/<name>.py``, with ``read(r: Readings)`` returning a
number or None when the run has nothing for it to read; a metric read as
None is left out of the result line. One quantity split by the cells'
end-to-end metrics (``device_idle.cold``, ``device_idle.served``) shares
the reader named by the part before the first dot (``device_idle.py``)
unless the split name has a file of its own.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from perfbench import spans as spans_mod
from perfbench.devtrace import DeviceSummary
from perfbench.spans import HostSpan
from perfbench.window import Window

METRICS_DIR = Path(__file__).with_name("metrics")


@dataclasses.dataclass
class Readings:
    window: Window
    chips: int
    peak: Dict                      # the peak table's row for this chip
    spans: List[HostSpan]           # program and benchmark spans, host clock
    counters: Dict[str, float]      # program counters and work from shapes
    device: Optional[DeviceSummary] = None

    def span_share(self, names: Sequence[str], self_only: bool = True) -> Optional[float]:
        """Percent of the window spent in spans named ``names`` (their
        self time unless ``self_only`` is False); None without such spans."""
        if not any(s.name in names for s in self.spans):
            return None
        fn = spans_mod.self_time if self_only else spans_mod.total_time
        return 100.0 * fn(self.spans, names, self.window.start,
                          self.window.end) / self.window.seconds

    def device_idle(self) -> Optional[float]:
        if self.device is None or self.device.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.device.busy_s / self.device.window_s)

    def mfu(self, flops_per_unit: Optional[float]) -> Optional[float]:
        """Percent of the chips' bf16 peak that the window's work needs."""
        if not flops_per_unit:
            return None
        return (100.0 * flops_per_unit * self.window.rate
                / (self.chips * self.peak["bf16_flops_per_s"]))


def read_metric(name: str, r: Readings) -> Optional[float]:
    path = METRICS_DIR / f"{name}.py"
    if not path.exists():
        path = METRICS_DIR / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(r)
    return None if value is None else float(value)
