"""The readers of the program-span metrics, on spans recorded through
``repro.obs.record`` around a synthetic window."""

import sys

import pytest

from bench import harness
from bench.tests.conftest import ROOT
from repro import obs

# a window far from any span the process records for itself
T_OPEN, T_CLOSE = -100.0, -90.0
S, MS = 10 ** 9, 10 ** 6
SPAN_METRICS = ("queue_wait_ms.serve", "wave_host_ms.serve",
                "window_compiles.serve", "plan_cluster_s", "plan_tile_s",
                "plan_upload_s")


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


def test_metrics_are_listed_for_the_served_cells(reg):
    listed = {m["name"]: m for m in reg.spec["per_layer"]}
    for name in SPAN_METRICS:
        assert listed[name]["source"] == "program_span"
        assert listed[name]["workloads"] == ["ca_road.sssp_c8",
                                             "g500_s16.bfs_c8"]


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_no_spans_read_none(reg, win, name):
    assert reg.metric(name).read(win) is None


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_obs_reads_none(reg, win, name, monkeypatch):
    obs.record("wave", *at(-95, MS), wave=0)
    obs.record("plan.tile", *at(-150, S))
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reg.metric(name).read(win) is None


def test_queue_wait_is_the_mean_over_requests_submitted_in_the_window(
        reg, win):
    obs.record("request.queue", *at(-99.5, 2 * MS), ticket=0, wave=0)
    obs.record("request.queue", *at(-95, 4 * MS), ticket=1, wave=1)
    obs.record("request.queue", *at(-101, 100 * MS), ticket=2, wave=9)
    obs.record("request.queue", *at(-89, 100 * MS), ticket=3, wave=9)
    assert reg.metric("queue_wait_ms.serve").read(win) == 3.0


def test_wave_host_time_leaves_out_the_wait_on_the_device(reg, win):
    for w, t, launch, wave, devices in (
            (0, -99, 1, 100, (80,)), (1, -95, 2, 50, (40, 5)),
            (2, -120, 5, 500, (1,))):
        obs.record("wave.launch", *at(t - 0.01, launch * MS), wave=w)
        obs.record("wave", *at(t, wave * MS), wave=w)
        for d in devices:
            obs.record("run.device", *at(t + 0.001, d * MS), wave=w)
    # an earlier wave 0, before the window, is not joined
    obs.record("run.device", *at(-130, 1000 * MS), wave=0)
    # (1 + 100 - 80 + 2 + 50 - 45) / 2
    assert reg.metric("wave_host_ms.serve").read(win) == 14.0


def test_window_compiles_count_those_that_start_in_the_window(reg, win):
    obs.record("wave", *at(-99, MS), wave=0)
    assert reg.metric("window_compiles.serve").read(win) == 0
    obs.record("jax.compile", *at(-98, MS), fun_name="f")
    obs.record("jax.compile", *at(-97, MS), fun_name="g")
    obs.record("jax.compile", *at(-150, MS), fun_name="warm")
    assert reg.metric("window_compiles.serve").read(win) == 2


@pytest.mark.parametrize("phase", ["cluster", "tile", "upload"])
def test_plan_phases_sum_the_spans_that_end_before_the_window(
        reg, win, phase):
    obs.record(f"plan.{phase}", *at(-200, 3 * S // 2))
    obs.record(f"plan.{phase}", *at(-150, S // 2))
    obs.record(f"plan.{phase}", *at(-100.5, S))      # ends in the window
    assert reg.metric(f"plan_{phase}_s").read(win) == 2.0
