"""Plan build: set-up seconds spent putting the plan's arrays on the
device, until they are there, from the program's ``plan.upload`` spans
that end before the window opens."""

from bench.metrics import _spans


def read(win):
    return _spans.before_window_s("plan.upload", win)
