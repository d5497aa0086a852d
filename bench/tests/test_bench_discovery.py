"""Cells, configurations, mixes and metrics are found by name, and a new
one needs only new files and entries."""

import json
import os
import re

import pytest

from bench import harness
from bench.tests.conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def reg():
    return harness.Registry.load(ROOT)


def test_every_cell_finds_its_files(reg):
    for w in reg.spec["workloads"]:
        cfg = reg.config(w["config"])
        mix = reg.traffic(w["traffic"])
        load = reg.load_kind(mix["load"])
        assert callable(load.warm) and callable(load.run)
        assert callable(reg.generator(cfg["generator"]).generate)
        assert callable(reg.check(mix["algo"]).compare)
        assert reg.limits(w["name"])
        names = {m["name"] for m in reg.end_to_end(w["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert reg.per_layer(w["name"])


def test_every_metric_has_a_reader_and_valid_names(reg):
    spec = reg.spec
    for m in spec["per_layer"]:
        assert callable(reg.metric(m["name"]).read)
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_config_files_state_their_cut(reg):
    for c in reg.spec["configs"]:
        cfg = reg.config(c["name"])
        for key in ("source", "reduced", "assumed", "deployment",
                    "guarantees", "plan_bytes"):
            assert key in cfg
        assert cfg["reduced"] == c["reduced"]


def test_a_new_config_mix_and_metric_are_new_files_only(tmp_path, reg):
    d = str(tmp_path)
    for sub in ("configs", "traffic", "metrics", "limits"):
        os.makedirs(os.path.join(d, sub))
    with open(os.path.join(BENCH, "configs", "ca_road.json")) as f:
        cfg = json.load(f)
    cfg["generator_params"]["side"] = 20
    with open(os.path.join(d, "configs", "small_road.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(d, "traffic", "sssp_c2.json"), "w") as f:
        json.dump(dict(load="closed_loop", algo="sssp", clients=2,
                       sources="uniform", pool=16, pool_seed=0,
                       check_sample=0), f)
    with open(os.path.join(d, "metrics", "queries_seen.serve.py"),
              "w") as f:
        f.write("def read(win):\n    return float(len(win.records))\n")
    with open(os.path.join(d, "limits", "small_road.sssp_c2.json"),
              "w") as f:
        json.dump({"sssp_rel_err": {"limit": 1e-4}}, f)
    spec = json.loads(json.dumps(reg.spec))
    spec["configs"].append({"name": "small_road", "source": "test",
                            "file": "configs/small_road.json",
                            "reduced": ["side"], "why": "test"})
    spec["workloads"].append({"name": "small_road.sssp_c2",
                              "config": "small_road",
                              "traffic": "sssp_c2", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "queries_seen.serve", "unit": "n",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "scheduler",
                              "moves": "queries_per_s",
                              "workloads": ["small_road.sssp_c2"]})
    spec["end_to_end"][0].setdefault("workloads", []).append(
        "small_road.sssp_c2")
    new = harness.Registry(spec, dirs=(d, BENCH), root=d)
    assert new.config("small_road")["generator_params"]["side"] == 20
    assert new.traffic("sssp_c2")["clients"] == 2
    assert new.limits("small_road.sssp_c2")
    names = [m["name"] for m in new.per_layer("small_road.sssp_c2")]
    assert sorted(names) == ["compile_s", "plan_build_s",
                             "queries_seen.serve"]
    win = harness.Window(records=[None] * 3,
                         t_open=0.0, t_close=1.0, sched_before={},
                         sched_after={}, max_wave=8, setup={})
    assert new.metric("queries_seen.serve").read(win) == 3.0
    # the accepted cells are untouched by the addition
    assert new.per_layer("ca_road.sssp_c8") == reg.per_layer(
        "ca_road.sssp_c8")


def test_unknown_names_are_errors(reg):
    with pytest.raises(KeyError):
        reg.cell("no_such.cell")
    with pytest.raises(FileNotFoundError):
        reg.traffic("no_such_mix")
    with pytest.raises(FileNotFoundError):
        reg.metric("no_such_metric")


def test_unknown_device_kind_is_an_error():
    from bench import peaks
    assert peaks.lookup("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(KeyError):
        peaks.lookup("TPU v99")
