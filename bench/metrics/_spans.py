"""The program's own spans (``repro.obs``), for the per-layer readers
of ``program_span`` metrics.  Span times are ``time.perf_counter_ns()``,
the clock of ``Window.t_open`` and ``t_close``.  A program without
``repro.obs`` records none, and its readers then return ``None``."""


def spans(name: str) -> list:
    try:
        from repro import obs
    except ImportError:
        return []
    return obs.spans(name)


def window_ns(win):
    """``(t_open, t_close)`` of the window in nanoseconds."""
    return int(win.t_open * 1e9), int(win.t_close * 1e9)


def in_window(name: str, win) -> list:
    """The spans called ``name`` that start inside the window."""
    lo, hi = window_ns(win)
    return [s for s in spans(name) if lo <= s.start_ns <= hi]


def before_window_s(name: str, win):
    """Seconds of the spans called ``name`` that end before the window
    opens (set-up), or ``None`` when there are none."""
    lo, _ = window_ns(win)
    d = [s.dur_ns for s in spans(name) if s.end_ns <= lo]
    return sum(d) / 1e9 if d else None
