"""Plan build: set-up seconds spent clustering the graph
(``core/cluster.cluster_graph``), from the program's ``plan.cluster``
spans that end before the window opens."""

from bench.metrics import _spans


def read(win):
    return _spans.before_window_s("plan.cluster", win)
