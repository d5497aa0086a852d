"""Fixtures for the benchmark's CPU tests: a tiny road graph served
through a real ``GraphServer``, under cells defined in a temporary
directory in front of ``bench/``."""

from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny cells and the real cells whose limits they are held to
TINY_CELLS = {
    "tiny.sssp": ("tiny_sssp", "ca_road.sssp_c8"),
    "tiny.bfs": ("tiny_bfs", "g500_s16.bfs_c8"),
    "tiny.pagerank": ("tiny_pagerank", "ca_road.pagerank_jobs"),
}


def make_registry(tmp, side: int = 24):
    from bench import harness
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with open(os.path.join(BENCH, "configs", "ca_road.json")) as f:
        cfg = json.load(f)
    cfg["generator_params"]["side"] = side
    cfg["num_clusters"] = 4
    cfg["wave"]["max_wave"] = 2
    mixes = {
        "tiny_sssp": dict(load="closed_loop", algo="sssp", clients=2,
                          sources="uniform", pool=64, pool_seed=0,
                          check_sample=0),
        "tiny_bfs": dict(load="closed_loop", algo="bfs", clients=2,
                         sources="degree_ge_1", pool=64, pool_seed=0,
                         check_sample=0),
        "tiny_pagerank": dict(load="jobs", algo="pagerank",
                              damping_low=0.8, damping_high=0.9,
                              strata=4, check_sample=3),
    }
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(tmp, sub), exist_ok=True)
    with open(os.path.join(tmp, "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    for name, mix in mixes.items():
        with open(os.path.join(tmp, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = []
    for cell, (mix, real) in TINY_CELLS.items():
        spec["workloads"].append({"name": cell, "config": "tiny",
                                  "traffic": mix, "chips": 1, "why": "t"})
        shutil.copy(os.path.join(BENCH, "limits", real + ".json"),
                    os.path.join(tmp, "limits", cell + ".json"))
        for m in spec["end_to_end"] + spec["per_layer"]:
            if real in m.get("workloads", []):
                m["workloads"].append(cell)
    return harness.Registry(spec, dirs=(str(tmp), BENCH), root=str(tmp))


@pytest.fixture
def tiny(tmp_path):
    return make_registry(str(tmp_path))


def cpu_line(plane: str, line: str) -> bool:
    """The CPU backend's stand-in for a device's ``XLA Ops`` line."""
    return plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")
