"""Host preparation inside the engine's ``train`` spans: the ``sample``
(draw and store gather), ``label``, ``rebalance`` and ``pad`` phases
(``engine.py``, ``core/trainer.py``), over the window, in percent."""
from perfbench import phases


def read(r):
    return phases.share(r, ["train"], ["sample", "label", "rebalance", "pad"])
