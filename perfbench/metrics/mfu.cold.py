"""The proxy work of the window's queries, training and scoring both
leaves from the shapes (``perfbench/flops.py``), over the chip's bf16
peak, in percent."""


def read(r):
    return r.mfu(r.counters.get("flops_per_query"))
