"""Kernel on the device: device-busy milliseconds in the traced window
(the union of the device's operations) over the sweeps of the waves
that ran in it; each wave's sweeps counted once."""


def read(win):
    if win.trace is None or win.trace.busy_s <= 0:
        return None
    waves = {id(r.stats): r.stats for r in win.records if r.ok}
    sweeps = sum(s.sweeps for s in waves.values())
    if sweeps <= 0:
        return None
    return 1e3 * win.trace.busy_s / sweeps
