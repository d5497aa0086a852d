"""Reduction of a JAX profiler trace to device busy time, idle gaps and
the device operations that took the most time.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` alone.  The harness wraps the measured
window in a host span named ``window`` and its own calls into the
system in the spans of ``HOST_SPANS``; device operations come from the
lines that ``device_line`` selects (on a TPU: each ``/device:TPU:<i>``
plane's ``XLA Ops`` line).  Everything is clipped to the window span.

- busy: the union of the device operations' intervals, per device,
  averaged over the devices;
- idle gaps: the holes in that union on the first device, each named by
  the host span that overlaps it most (``no_span`` when none does);
- device ops: self time per operation (time not covered by an operation
  nested inside it on the same line), averaged over devices, named by
  the HLO instruction and its shape.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "window"
HOST_SPANS = ("submit", "wave_wait", "job", "result_fetch")
TOP = 10

_TPU_PLANE = re.compile(r"^/device:TPU:\d+$")
_HLO = re.compile(r"^%?(\S+) = (\(|[a-z0-9]+\[[^\]]*\])")


def tpu_line(plane: str, line: str) -> bool:
    return bool(_TPU_PLANE.match(plane)) and line == "XLA Ops"


@dataclasses.dataclass
class Summary:
    busy_s: float
    window_s: float
    devices: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    inventory: str       # planes and lines seen, for diagnosis


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _busy_and_gaps(starts, ends, lo, hi):
    """Busy nanoseconds of the union of [start, end) intervals clipped
    to [lo, hi], and the holes in it as (start, length) arrays."""
    keep = (ends > lo) & (starts < hi)
    s = np.clip(starts[keep], lo, hi)
    e = np.clip(ends[keep], lo, hi)
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e) if len(e) else e
    prev = np.concatenate([[lo], reach])
    nxt = np.concatenate([s, [hi]])
    hole = nxt - prev
    at = hole > 0
    return (hi - lo) - float(hole[at].sum()), prev[at], hole[at]


def _self_times(events, lo, hi) -> Dict[str, float]:
    """Self time per name of (start_ns, duration_ns, name) events on one
    line, clipped to [lo, hi]; an event nested in another is charged to
    itself and not to its parent."""
    acc: Dict[str, float] = collections.defaultdict(float)
    if not events:
        return acc
    starts = np.array([ev[0] for ev in events], dtype=np.float64)
    ends = starts + np.array([ev[1] for ev in events], dtype=np.float64)
    order = np.lexsort((-ends, starts))
    clip = np.maximum(np.minimum(ends, hi) - np.maximum(starts, lo), 0.0)
    stack: list = []      # [end, index, child_ns]

    def close(ent):
        end, i, child = ent
        acc[events[i][2]] += clip[i] - child
        if stack:
            stack[-1][2] += clip[i]

    for i in order.tolist():
        while stack and stack[-1][0] <= starts[i]:
            close(stack.pop())
        stack.append([ends[i], i, 0.0])
    while stack:
        close(stack.pop())
    return acc


def short_name(name: str) -> str:
    """``%fusion.34 = f32[490752,16]{0,1:...} fusion(...)`` ->
    ``fusion.34 f32[490752,16]``: the HLO instruction and its shape."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    return f"{m.group(1)} {'tuple' if m.group(2) == '(' else m.group(2)}"


def reduce(path: str,
           device_line: Callable[[str, str], bool] = tpu_line) -> Summary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window: Optional[Tuple[float, float]] = None
    host: List[Tuple[float, float, str]] = []
    by_plane: Dict[str, list] = collections.OrderedDict()
    inventory = []
    for plane in pd.planes:
        for line in plane.lines:
            if device_line(plane.name, line.name):
                evs = [(ev.start_ns, ev.duration_ns, ev.name)
                       for ev in line.events]
                by_plane.setdefault(plane.name, []).append(evs)
                count = len(evs)
            else:
                count = 0
                for ev in line.events:
                    count += 1
                    name = ev.name
                    if name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    elif name in HOST_SPANS:
                        host.append((ev.start_ns, ev.end_ns, name))
            inventory.append(f"{plane.name}|{line.name}|{count}")
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    lo, hi = float(window[0]), float(window[1])
    busy, holes = [], None
    ops: Dict[str, float] = collections.defaultdict(float)
    for lines in by_plane.values():
        evs = [ev for line in lines for ev in line]
        st = np.array([ev[0] for ev in evs], dtype=np.float64)
        en = st + np.array([ev[1] for ev in evs], dtype=np.float64)
        b, at, length = _busy_and_gaps(st, en, lo, hi)
        busy.append(b)
        if holes is None:
            holes = (at, length)
        for line in lines:
            for name, t in _self_times(line, lo, hi).items():
                ops[name] += t
    ndev = len(by_plane)
    named = []
    if holes is not None:
        at, length = holes
        for k in np.argsort(-length, kind="stable")[:TOP]:
            s, e = at[k], at[k] + length[k]
            best, name = 0.0, "no_span"
            for hs, he, hn in host:
                ov = min(e, he) - max(s, hs)
                if ov > best:
                    best, name = ov, hn
            named.append((name, float(length[k]) / 1e9))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return Summary(
        busy_s=(sum(busy) / ndev / 1e9) if ndev else 0.0,
        window_s=(hi - lo) / 1e9, devices=ndev,
        device_ops=[(short_name(n), t / ndev / 1e9) for n, t in top],
        idle_gaps=named, inventory=" ; ".join(inventory))
