"""Scheduler: mean wave size over the window as a share of ``max_wave``,
from the scheduler's own counters (``waves``, ``wave_queries``)."""


def read(win):
    waves = win.sched_after["waves"] - win.sched_before["waves"]
    queries = win.sched_after["wave_queries"] - \
        win.sched_before["wave_queries"]
    if waves <= 0:
        return None
    return 100.0 * queries / waves / win.max_wave
