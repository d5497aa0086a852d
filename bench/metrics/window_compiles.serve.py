"""Compile: backend compiles (or compile-cache fetches) that start in
the window, from the program's ``jax.compile`` spans; 0 when the
window's ``wave`` spans are there and no compile is."""

from bench.metrics import _spans


def read(win):
    if not _spans.in_window("wave", win):
        return None
    return float(len(_spans.in_window("jax.compile", win)))
