"""``correct`` comes out true on a sound run and false when the timed
path is broken underneath: a step that returns its state unchanged,
half of a batch left out, an answer altered where it is produced.  (On
one chip there is no exchange between chips to leave out.)  The chip
check is skipped; the rest of a run is driven as ``bench/run.py``
drives it."""

import dataclasses

import numpy as np
import pytest

from bench import control, harness
from bench.loads import closed_loop, jobs
from repro.serve.graph import GraphService


def _run(reg, cell):
    return harness.run_cell(reg, cell, 2 ** 31 + 5, 0.5, False,
                            log=lambda m: None)


def _in_window(monkeypatch, load, attr, fault):
    """Break ``GraphService.<attr>`` from the window's start: set-up
    and warm-up run sound, the timed path does not."""
    orig_run = load.run

    def run(system, t_open, t_end):
        monkeypatch.setattr(GraphService, attr, fault)
        return orig_run(system, t_open, t_end)

    monkeypatch.setattr(load, "run", run)


def _wave_fault(kind):
    """Wrap GraphService._run_wave so each wave's answers are broken."""
    orig = GraphService._run_wave

    def broken(self, name, algo, pol, group):
        out = orig(self, name, algo, pol, group)
        ts = [q.ticket for q in group]
        res = {t: out[t] for t in ts}
        if kind == "unchanged":
            for q in group:
                v = np.full_like(res[q.ticket].values, np.inf)
                v[q.spec.sources[0]] = 0.0
                res[q.ticket] = dataclasses.replace(res[q.ticket], values=v)
        elif kind == "half":
            half = len(ts) // 2 or 1
            for i, t in enumerate(ts[half:]):
                res[t] = dataclasses.replace(
                    res[t], values=res[ts[i % half]].values.copy())
        elif kind == "altered":
            r = res[ts[0]]
            v = r.values.copy()
            fin = np.flatnonzero(np.isfinite(v))
            i = fin[np.argmax(v[fin])]
            v[i] = v[i] * 1.01 if v[i] > 0 else 1.0
            res[ts[0]] = dataclasses.replace(r, values=v)
        return res

    return broken


def _job_fault(kind):
    orig = GraphService.run

    def broken(self, name, spec):
        r = orig(self, name, spec)
        v = r.values.copy()
        if kind == "unchanged":
            v[:] = 1.0 / len(v)
        elif kind == "half":
            v[len(v) // 2:] = 1.0 / len(v)
            v /= v.sum()
        elif kind == "altered":
            top = np.argsort(v)[-max(len(v) // 100, 1):]
            v[top] = 0.0
        return dataclasses.replace(r, values=v)

    return broken


@pytest.mark.parametrize("cell", ["tiny.sssp", "tiny.bfs", "tiny.pagerank"])
def test_sound_run_is_correct(tiny, cell):
    line = _run(tiny, cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
@pytest.mark.parametrize("cell", ["tiny.sssp", "tiny.bfs"])
def test_broken_waves_are_not_correct(tiny, cell, kind, monkeypatch):
    _in_window(monkeypatch, closed_loop, "_run_wave", _wave_fault(kind))
    line = _run(tiny, cell)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_broken_jobs_are_not_correct(tiny, kind, monkeypatch):
    _in_window(monkeypatch, jobs, "run", _job_fault(kind))
    line = _run(tiny, "tiny.pagerank")
    assert not line["correct"], line["checks"]


def test_failed_query_is_not_correct(tiny, monkeypatch):
    def boom(self, name, algo, pol, group):
        return {q.ticket: RuntimeError("injected") for q in group}
    _in_window(monkeypatch, closed_loop, "_run_wave", boom)
    line = _run(tiny, "tiny.sssp")
    assert not line["correct"] and line["failed"] == line["attempted"] > 0


@pytest.mark.parametrize("cell", ["tiny.sssp", "tiny.bfs", "tiny.pagerank"])
def test_control_is_not_correct(tiny, cell):
    checks = control.control_numbers(tiny, cell, 9, 4)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks
