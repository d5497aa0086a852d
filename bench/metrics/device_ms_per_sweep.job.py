"""Kernel on the device: device-busy milliseconds in the traced window
over the sweeps of the jobs that ran in it."""


def read(win):
    if win.trace is None or win.trace.busy_s <= 0:
        return None
    sweeps = sum(r.stats.sweeps for r in win.records if r.ok)
    if sweeps <= 0:
        return None
    return 1e3 * win.trace.busy_s / sweeps
