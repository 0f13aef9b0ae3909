"""Time in the cascade's ``calibrate`` and ``decide`` spans
(``core/cascade.py`` thresholds and the band's resolution, numpy on the
host) over the window, in percent."""


def read(r):
    return r.span_share(["calibrate", "decide"], self_only=False)
