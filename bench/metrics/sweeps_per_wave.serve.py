"""Engine: mean sweeps to convergence per wave over the window, from
``Result.stats.sweeps`` (the queries of one wave share one stats
object, whose sweeps are the wave's straggler's)."""


def read(win):
    waves = {id(r.stats): r.stats for r in win.records if r.ok}
    if not waves:
        return None
    return sum(s.sweeps for s in waves.values()) / len(waves)
