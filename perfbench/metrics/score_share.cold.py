"""Self time of the engine's ``score`` spans (the scoring executor,
``engine/executor.py``) over the window, in percent."""


def read(r):
    return r.span_share(["score"])
