"""The trainer's copy and program inside the engine's ``train`` spans:
the ``put`` (host->device copy, as dispatched) and ``run`` (the program
until its losses are on the host) phases (``core/trainer.py``), over the
window, in percent."""
from perfbench import phases


def read(r):
    return phases.share(r, ["train"], ["put", "run"])
