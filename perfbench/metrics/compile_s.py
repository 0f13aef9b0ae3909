"""Seconds of the window spent building programs: the union of the
``compile`` (backend compile or compile-cache load) and ``lower`` (jaxpr
trace, MLIR lowering) phases the program's tracer records on any span.
Read for ``compile_s.cold``, ``compile_s.served`` and
``compile_s.ingest``; 0.0 when the program records phases and nothing
compiled."""
from perfbench import phases
from perfbench.spans import _union_length


def read(r):
    if not phases.recorded(r):
        return None
    return _union_length(
        [(a, b) for a, b, _, _ in phases.clipped(r, None, ["compile", "lower"])])
