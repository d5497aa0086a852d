"""Kernel on device: the percent of the window's waves whose source
gather ran once for the whole wave, from the ``gather`` attribute
(``"wave"`` or ``"per_query"``) of their ``run.device`` spans, joined by
wave id; the last such span of a wave is the one that served it.
``None`` when no ``run.device`` span of the window's waves carries the
attribute (a program that does not record it)."""

from bench.metrics import _spans


def read(win):
    waves = {s.attrs["wave"] for s in _spans.in_window("wave", win)}
    gather = {}
    for s in sorted(_spans.in_window("run.device", win),
                    key=lambda s: s.end_ns):
        if s.attrs.get("wave") in waves and "gather" in s.attrs:
            gather[s.attrs["wave"]] = s.attrs["gather"]
    if not gather:
        return None
    return 100.0 * sum(g == "wave" for g in gather.values()) / len(gather)
