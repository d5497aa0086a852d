"""Scheduler: mean wait of a request in the queue, from its submit to
the close of the wave that took it, over the requests submitted in the
window (the program's ``request.queue`` spans)."""

from bench.metrics import _spans


def read(win):
    d = [s.dur_ns for s in _spans.in_window("request.queue", win)]
    return sum(d) / len(d) / 1e6 if d else None
