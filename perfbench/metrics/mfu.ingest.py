"""The backbone forward of the window's committed documents, from the
configuration's shapes (``perfbench/flops.py``), over the chips' bf16
peak, in percent."""


def read(r):
    return r.mfu(r.counters.get("flops_per_doc"))
