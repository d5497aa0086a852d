"""The reader of ``wave_gather_share.serve``, on spans recorded through
``repro.obs.record`` around a synthetic window."""

import sys

import pytest

from bench import harness
from bench.tests.conftest import ROOT
from repro import obs

# a window far from any span the process records for itself
T_OPEN, T_CLOSE = -100.0, -90.0
S, MS = 10 ** 9, 10 ** 6
NAME = "wave_gather_share.serve"


@pytest.fixture(scope="module")
def reg():
    return harness.Registry.load(ROOT)


@pytest.fixture
def win():
    obs.clear()
    yield harness.Window(records=[], t_open=T_OPEN, t_close=T_CLOSE,
                         sched_before={}, sched_after={}, max_wave=8,
                         setup={})
    obs.clear()


def at(t_s, dur_ns):
    start = int(t_s * S)
    return start, start + dur_ns


def test_metric_is_listed_for_the_served_cells(reg):
    listed = {m["name"]: m for m in reg.spec["per_layer"]}
    assert listed[NAME]["source"] == "program_span"
    assert listed[NAME]["layer"] == "kernel on device"
    assert listed[NAME]["moves"] == "queries_per_s"
    assert listed[NAME]["workloads"] == ["ca_road.sssp_c8",
                                         "g500_s16.bfs_c8"]


def test_no_spans_read_none(reg, win):
    assert reg.metric(NAME).read(win) is None


def test_a_program_without_obs_reads_none(reg, win, monkeypatch):
    obs.record("wave", *at(-95, MS), wave=0)
    obs.record("run.device", *at(-94.9, MS), wave=0, gather="wave")
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reg.metric(NAME).read(win) is None


def test_wave_gather_share_reads_none_without_the_attribute(reg, win):
    obs.record("wave", *at(-99, 100 * MS), wave=0)
    obs.record("run.device", *at(-98.9, 80 * MS), wave=0)
    assert reg.metric(NAME).read(win) is None


@pytest.mark.parametrize("gathers,share", [
    (("wave", "wave", "wave"), 100.0),
    (("wave", "per_query", "wave", "per_query"), 50.0),
    (("per_query",), 0.0)])
def test_wave_gather_share_is_the_percent_of_window_waves(
        reg, win, gathers, share):
    for w, g in enumerate(gathers):
        obs.record("wave", *at(-99 + w, 100 * MS), wave=w)
        obs.record("run.device", *at(-98.9 + w, 80 * MS), wave=w,
                   gather=g, q=8 if g == "wave" else 1)
    # a wave before the window, and a retried wave whose last attempt
    # (the one that served it) gathered per query
    obs.record("wave", *at(-120, 100 * MS), wave=99)
    obs.record("run.device", *at(-119.9, 80 * MS), wave=99, gather="wave")
    w = len(gathers)
    obs.record("wave", *at(-95, 900 * MS), wave=w)
    obs.record("run.device", *at(-94.9, 10 * MS), wave=w, gather="wave")
    obs.record("run.device", *at(-94.8, 10 * MS), wave=w,
               gather="per_query")
    n = len(gathers) + 1
    assert reg.metric(NAME).read(win) == \
        pytest.approx(share * len(gathers) / n)
