"""Plan build: set-up seconds spent tiling the plan (permute,
transpose, ``to_bsr``, lane-tile padding, group and halo geometry), from
the program's ``plan.tile`` spans that end before the window opens."""

from bench.metrics import _spans


def read(win):
    return _spans.before_window_s("plan.tile", win)
