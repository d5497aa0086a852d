"""Engine: mean sweeps to convergence per job over the window, from
``Result.stats.sweeps``."""


def read(win):
    ok = [r for r in win.records if r.ok]
    if not ok:
        return None
    return sum(r.stats.sweeps for r in ok) / len(ok)
