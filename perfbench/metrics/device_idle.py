"""Share of the window in which no operation ran on a chip, from the
device trace, averaged over the cell's chips, in percent. Read for
``device_idle.cold``, ``device_idle.served`` and ``device_idle.ingest``."""


def read(r):
    return r.device_idle()
