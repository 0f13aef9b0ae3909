"""The scoring executor waiting on its prefetch queue: the ``stall``
phases of the engine's ``score`` spans (``engine/executor.py``), over the
window, in percent."""
from perfbench import phases


def read(r):
    return phases.share(r, ["score"], ["stall"])
