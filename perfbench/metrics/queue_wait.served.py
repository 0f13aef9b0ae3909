"""Mean time from a query's submission to a server worker opening its
``session`` span (``serve/server.py``), over the window's queries, in
milliseconds."""


def read(r):
    submitted = r.counters.get("submitted_at") or {}
    waits = [s.start - submitted[s.attrs.get("session")] for s in r.spans
             if s.name == "session" and s.attrs.get("session") in submitted]
    return 1000.0 * sum(waits) / len(waits) if waits else None
