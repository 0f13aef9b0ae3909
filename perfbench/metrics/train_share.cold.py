"""Self time of the engine's ``train`` spans (proxy training,
``core/trainer.py``) over the window, in percent."""


def read(r):
    return r.span_share(["train"])
