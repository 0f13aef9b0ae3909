"""Thread-CPU time of the cascade's ``decide`` phases other than the
oracle's (``threshold``, ``known``, ``need``, ``merge``;
``engine._decide_pending``), per query completed in the window, in
milliseconds."""
from perfbench import phases


def read(r):
    got = phases.wall_and_cpu(r, ["decide"], phases.DECIDE_HOST)
    return None if got is None else 1000.0 * got[1] / r.window.completions
