"""Dispatch: host time per wave outside the wait on the device, the
mean over the window's waves of ``wave.launch`` + ``wave`` - its
``run.device`` spans (the program's spans in the window, joined by wave
id)."""

from bench.metrics import _spans


def read(win):
    waves = {s.attrs["wave"]: s.dur_ns
             for s in _spans.in_window("wave", win)}
    if not waves:
        return None
    for name, sign in (("wave.launch", 1), ("run.device", -1)):
        for s in _spans.in_window(name, win):
            if s.attrs.get("wave") in waves:
                waves[s.attrs["wave"]] += sign * s.dur_ns
    return sum(waves.values()) / len(waves) / 1e6
