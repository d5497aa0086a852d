"""Exchange: the mean over the window's waves of the all_gather payload
one device received, ``halo_bytes`` of the wave's ``run.device`` span
(the engine's ``DistStats``: bytes per exchange times exchanges), in
MB; the last such span of a wave is the one that served it.  ``None``
when no span of the window's waves records ``halo_bytes``."""

from bench.metrics import _spans


def read(win):
    waves = {s.attrs["wave"] for s in _spans.in_window("wave", win)}
    halo = {}
    for s in sorted(_spans.in_window("run.device", win),
                    key=lambda s: s.end_ns):
        if s.attrs.get("wave") in waves and "halo_bytes" in s.attrs:
            halo[s.attrs["wave"]] = s.attrs["halo_bytes"]
    if not halo:
        return None
    return sum(halo.values()) / len(halo) / 1e6
