"""Spans: where the time of a served query and of a plan build goes.

``span(name, **attrs)`` times one step of the program.  It does two
things at once: it enters ``jax.profiler.TraceAnnotation(name)``, so a
profiler trace shows the step under the same name on the host timeline
beside the device's operations, and it appends a ``Span`` to a bounded
in-memory ring.  Times are ``time.perf_counter_ns()``.  A span's parent
is the span open around it on the same thread, and it inherits the
parent's ``wave`` and ``ticket`` attributes, so every span of one wave
carries that wave's id.  ``record`` adds a span whose ends lie on two
threads (a request's wait in the queue); it reaches the ring only.

Recording is always on, at request, wave and plan-phase granularity:
never per sweep, and never inside jitted code.  A span costs a few
microseconds.  A ``jax.monitoring`` listener adds one ``jax.compile``
span (attribute ``fun_name``) for every backend compile or compile
cache fetch.

An operator reads the spans with ``spans()``:

    from repro import obs
    waits = [s.dur_ns for s in obs.spans("request.queue")]

The names in use: ``request.queue``, ``wave.launch``, ``wave``,
``wave.resolve`` (``serve/sched.py``), ``run.prep``, ``run.device``,
``run.fetch`` (``core/api.py``), ``plan.cluster``, ``plan.tile``,
``plan.upload`` (``core/engine.prepare``), ``plan.place`` (every
placement of plan rows onto a mesh, ``core/placement.place_rows``: in
the ``plan.upload`` of a sharded plan, and wherever a plan would move)
and ``jax.compile``.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

#: attributes a span takes from the span open around it
INHERITED = ("wave", "ticket")
RING = 1 << 16
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    attrs: Dict[str, object]

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


class _Open:
    """A span being timed: the context manager ``Recorder.span`` returns."""

    __slots__ = ("rec", "name", "attrs", "start_ns", "span_id",
                 "parent_id", "last_child_end_ns", "_ann")

    def __init__(self, rec: "Recorder", name: str,
                 start_ns: Optional[int], attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs
        self.start_ns = start_ns
        self.last_child_end_ns: Optional[int] = None

    def __enter__(self) -> "_Open":
        stack = self.rec._stack()
        parent = stack[-1] if stack else None
        self.parent_id = parent.span_id if parent else None
        if parent is not None:
            for k in INHERITED:
                if k in parent.attrs:
                    self.attrs.setdefault(k, parent.attrs[k])
        self.span_id = next(self.rec._ids)
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        if self.start_ns is None:
            self.start_ns = time.perf_counter_ns()
        stack.append(self)
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        stack = self.rec._stack()
        stack.pop()
        if stack:
            stack[-1].last_child_end_ns = end
        self.rec._add(Span(self.name, self.start_ns, end, self.span_id,
                           self.parent_id, self.attrs))


class Recorder:
    """A bounded ring of finished spans; ``dropped`` counts the spans
    pushed out of it."""

    def __init__(self, maxlen: int = RING):
        self._ring: "collections.deque[Span]" = collections.deque(
            maxlen=maxlen)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.dropped = 0

    def _stack(self) -> List[_Open]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _add(self, s: Span) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append(s)

    def span(self, name: str, start_ns: Optional[int] = None,
             **attrs) -> _Open:
        """Time the ``with`` block; ``start_ns`` back-dates the start to
        when the step began before the block did."""
        return _Open(self, name, start_ns, attrs)

    def record(self, name: str, start_ns: int, end_ns: int,
               **attrs) -> Span:
        """Add a finished span timed elsewhere (its ends on two
        threads); its parent is the span open on this thread, if any."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        if parent is not None:
            for k in INHERITED:
                if k in parent.attrs:
                    attrs.setdefault(k, parent.attrs[k])
        s = Span(name, int(start_ns), int(end_ns), next(self._ids),
                 parent.span_id if parent else None, attrs)
        self._add(s)
        return s

    def spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._ring)
        return out if name is None else [s for s in out if s.name == name]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0


_default = Recorder()
span = _default.span
record = _default.record
spans = _default.spans
clear = _default.clear


def dropped() -> int:
    return _default.dropped


def _on_compile(event: str, start_time: float, end_time: float,
                **kw) -> None:
    # jax stamps the span with time.time(); it is reported as it ends
    if event == _COMPILE_EVENT:
        end = time.perf_counter_ns()
        record("jax.compile", end - int((end_time - start_time) * 1e9),
               end, fun_name=str(kw.get("fun_name", "")))


jax.monitoring.register_event_time_span_listener(_on_compile)
