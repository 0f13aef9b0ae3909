"""The ingest writer's time (``IngestStats.write_seconds``: appends to
the store file and each commit's fsyncs and manifest swap,
``engine/store.py``) over the window, in percent."""


def read(r):
    w = r.counters.get("write_seconds")
    return None if w is None else 100.0 * w / r.window.seconds
