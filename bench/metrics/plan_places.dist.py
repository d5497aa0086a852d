"""Dispatch: placements of plan rows onto devices during the window, the
program's ``plan.place`` spans that start in it.  A plan placed at
build and used as it lies reads 0.  ``None`` on a program that records
no ``plan.place`` span at all (set-up included)."""

from bench.metrics import _spans


def read(win):
    if not _spans.spans("plan.place"):
        return None
    return float(len(_spans.in_window("plan.place", win)))
