"""The four-chip cell ``g500_s17.bfs_c8``: its configuration and cell are
found by name, and its three per-layer readers read the program's
spans (``plan.place``, ``run.device``'s ``halo_bytes``, ``plan.upload``'s
``shard_bytes``) around a synthetic window, and ``None`` without them."""

import json
import os
import sys

import pytest

from bench import harness
from bench.tests.conftest import BENCH, ROOT
from repro import obs

CELL = "g500_s17.bfs_c8"
METRICS = ("plan_places.dist", "halo_mb_per_wave.dist", "plan_shard_gb")
# accepted served-cell metrics whose readers read the four-chip cell too
SERVED = ("wave_occupancy.serve", "sweeps_per_wave.serve",
          "device_ms_per_sweep.serve", "device_idle.serve")
T_OPEN, T_CLOSE = -100.0, -90.0
S, MS = 10 ** 9, 10 ** 6


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


def test_the_cell_and_its_configuration_are_found(reg):
    cell = reg.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "g500_s17", "bfs_c8", 4)
    cfg = reg.config("g500_s17")
    assert cfg["policy"] == {"mode": "distributed", "kernel": "ref",
                             "degrade": False}
    assert reg.limits(CELL)["bfs_level_mismatch"]["limit"] == 0
    assert reg.traffic("bfs_c8")["clients"] == 8
    names = {m["name"] for m in reg.end_to_end(CELL)}
    assert names == {"queries_per_s", "query_p95_s", "hbm_peak_gb",
                     "setup_s"}
    assert {m["name"] for m in reg.per_layer(CELL)} == {
        "plan_build_s", "compile_s", *SERVED, *METRICS}


def test_only_the_scale_differs_from_g500_s16(reg):
    entry = {c["name"]: c for c in reg.spec["configs"]}["g500_s17"]
    assert entry["reduced"] == ["scale"]
    with open(os.path.join(BENCH, "configs", "g500_s16.json")) as f:
        s16 = json.load(f)
    s17 = reg.config("g500_s17")
    assert s17["generator_params"] == dict(s16["generator_params"],
                                           scale=17)
    assert (s17["b"], s17["num_clusters"], s17["wave"]) == (
        s16["b"], s16["num_clusters"], s16["wave"])
    assert s17["plan_bytes"]["bytes"] == 8192 * 16 * 3112 * 16 * 4
    assert s17["plan_bytes"]["per_chip_tile_bytes"] * 4 == \
        s17["plan_bytes"]["bytes"]


def test_the_new_metrics_are_listed_for_the_cell_alone(reg):
    listed = {m["name"]: m for m in reg.spec["per_layer"]}
    for name in METRICS:
        assert listed[name]["workloads"] == [CELL]
    assert listed["plan_shard_gb"]["moves"] == "hbm_peak_gb"
    assert listed["halo_mb_per_wave.dist"]["layer"] == "exchange"


@pytest.mark.parametrize("name", METRICS)
def test_no_spans_read_none(reg, win, name):
    assert reg.metric(name).read(win) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_program_without_obs_reads_none(reg, win, name, monkeypatch):
    obs.record("plan.place", *at(-150, S), devices=4, mesh="4x1")
    obs.record("plan.upload", *at(-150, S), shard_bytes=6 * S)
    obs.record("wave", *at(-95, MS), wave=0)
    obs.record("run.device", *at(-95, MS), wave=0, halo_bytes=1e6)
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reg.metric(name).read(win) is None


def test_plan_places_counts_the_window_and_reads_zero_without(reg, win):
    m = reg.metric("plan_places.dist")
    obs.record("plan.place", *at(-150, S), devices=4, mesh="4x1")
    assert m.read(win) == 0.0
    obs.record("plan.place", *at(-95, MS), devices=4, mesh="4x1")
    obs.record("plan.place", *at(-85, MS), devices=4, mesh="4x1")
    assert m.read(win) == 1.0


def test_halo_is_the_mean_over_the_window_waves(reg, win):
    m = reg.metric("halo_mb_per_wave.dist")
    for w, t, halo in ((0, -99, 20e6), (1, -95, 30e6), (2, -120, 1e9)):
        obs.record("wave", *at(t, 10 * MS), wave=w)
        obs.record("run.device", *at(t + 0.001, MS), wave=w,
                   mesh="4x1", halo_exchanges=7, halo_bytes=halo)
    # a run.device of a window wave without the attribute is skipped
    obs.record("wave", *at(-93, 10 * MS), wave=3)
    obs.record("run.device", *at(-93 + 0.001, MS), wave=3, gather="wave")
    assert m.read(win) == pytest.approx(25.0)


def test_halo_without_the_attribute_reads_none(reg, win):
    obs.record("wave", *at(-99, 10 * MS), wave=0)
    obs.record("run.device", *at(-99 + 0.001, MS), wave=0, gather="wave")
    assert reg.metric("halo_mb_per_wave.dist").read(win) is None


def test_plan_shard_is_the_set_up_upload(reg, win):
    m = reg.metric("plan_shard_gb")
    obs.record("plan.upload", *at(-150, S))           # the parent's form
    assert m.read(win) is None
    obs.record("plan.upload", *at(-140, S), devices=4, mesh="4x1",
               shard_bytes=6_526_337_024)
    obs.record("plan.upload", *at(-95, S), shard_bytes=9 * S)  # in window
    assert m.read(win) == pytest.approx(6.526337024)
