"""The fast reference agrees with the copied oracles, and each control
fails the limits the cells hold the system to."""

import json
import os

import numpy as np
import pytest

from bench import reference
from bench.data import kronecker, road_network
from bench.tests.conftest import BENCH


@pytest.fixture(scope="module")
def road():
    return road_network.generate(
        dict(side=30, keep_frac=0.68, extra_frac=0.05, topology_seed=0,
             weight_low=1.0, weight_high=10.0), seed=11)


def _limit(cell, name):
    with open(os.path.join(BENCH, "limits", cell + ".json")) as f:
        return json.load(f)[name]["limit"]


def test_sssp_matches_oracle(road):
    # the copied oracle adds float32 weights in float32 (NumPy 2 keeps
    # the scalar's type); the fast form adds in float64
    for src in (0, 17, 400):
        np.testing.assert_allclose(reference.sssp(road, src),
                                   reference.sssp_oracle(road, src),
                                   rtol=1e-6)


def test_bfs_matches_oracle(road):
    for src in (0, 17, 400):
        np.testing.assert_array_equal(reference.bfs(road, src),
                                      reference.bfs_oracle(road, src))


@pytest.mark.parametrize("damping", [0.8, 0.9])
def test_pagerank_matches_oracle(road, damping):
    fast = reference.pagerank(road, damping)
    slow = reference.pagerank_oracle(road, damping, tol=1e-14,
                                     max_iter=5000)
    np.testing.assert_allclose(fast, slow, rtol=1e-9, atol=1e-14)


def test_controls_fail_the_limits(road):
    from bench.checks import bfs, pagerank, sssp
    got = sssp.compare(road, [(0, None), (17, None)], control=True)
    assert got["sssp_rel_err"] > _limit("ca_road.sssp_c8", "sssp_rel_err")
    got = bfs.compare(road, [(0, None)], control=True)
    assert got["bfs_level_mismatch"] > _limit("g500_s16.bfs_c8",
                                              "bfs_level_mismatch")
    g = kronecker.generate(dict(scale=10, edgefactor=16, a=0.57, b=0.19,
                                c=0.19, topology_seed=0, weight_low=1.0,
                                weight_high=10.0), seed=2)
    got = pagerank.compare(g, [(0.85, None)], control=True)
    assert got["pagerank_l1_err"] > _limit("g500_s16.pagerank_jobs",
                                           "pagerank_l1_err")
