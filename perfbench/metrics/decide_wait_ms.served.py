"""Wall time less thread-CPU time of the cascade's ``decide`` phases
other than the oracle's (``threshold``, ``known``, ``need``, ``merge``;
``engine._decide_pending``): the time the worker waited, for the
interpreter lock or the host's cores, per query completed in the window,
in milliseconds."""
from perfbench import phases


def read(r):
    got = phases.wall_and_cpu(r, ["decide"], phases.DECIDE_HOST)
    if got is None:
        return None
    return 1000.0 * (got[0] - got[1]) / r.window.completions
