"""The benchmark's copied generators give the system's graphs."""

import numpy as np
import pytest

from bench.data import csr, kronecker, road_network


def _road(side):
    return dict(side=side, keep_frac=0.68, extra_frac=0.05,
                topology_seed=0, weight_low=1.0, weight_high=10.0)


def _kron(scale):
    return dict(scale=scale, edgefactor=16, a=0.57, b=0.19, c=0.19,
                topology_seed=0, weight_low=1.0, weight_high=10.0)


def test_road_streets_run_both_ways():
    g = road_network.generate(_road(64), seed=5)
    w = {(int(u), int(v)): x
         for u, v, x in zip(g.sources(), g.indices, g.weights)}
    assert all(w[(v, u)] == x for (u, v), x in w.items())
    assert g.weights.min() >= 1.0 and g.weights.max() < 10.0


def test_road_keeps_a_share_of_the_lattice():
    side = 64
    g = road_network.generate(_road(side), seed=5)
    u, v = g.sources(), g.indices.astype(np.int64)
    down = (v - u == side)
    right = (v - u == 1) & (u % side != side - 1)
    kept = np.count_nonzero(down | right) / (2 * side * (side - 1))
    assert kept == pytest.approx(0.68, abs=0.02)
    shortcuts = g.nnz // 2 - np.count_nonzero(down | right)
    assert shortcuts == pytest.approx(0.05 * side * side, rel=0.05)


def test_road_full_size_counts():
    # roadNet-CA: 1,965,206 vertices, 2,766,607 undirected edges
    g = road_network.generate(_road(1401), seed=0)
    assert (g.n, g.nnz) == (1962801, 5531706)


def test_kronecker_topology_matches_the_system():
    from repro.core.graph import rmat
    g = kronecker.generate(_kron(10), seed=5)
    ref = rmat(1 << 10, 16 << 10, seed=0).to_undirected()
    np.testing.assert_array_equal(g.indptr, ref.indptr)
    np.testing.assert_array_equal(g.indices, ref.indices)


def test_kronecker_scale_15_counts():
    # the Graph500 reckoning: scale 15 has 882,814 directed edges
    g = kronecker.generate(_kron(15), seed=0)
    assert (g.n, g.nnz) == (32768, 882814)


@pytest.mark.parametrize("gen,params", [
    (road_network, _road(32)), (kronecker, _kron(8))])
def test_seed_draws_weights_not_topology(gen, params):
    a = gen.generate(params, seed=1)
    b = gen.generate(params, seed=2 ** 33 + 7)
    again = gen.generate(params, seed=1)
    np.testing.assert_array_equal(a.indices, b.indices)
    assert not np.array_equal(a.weights, b.weights)
    np.testing.assert_array_equal(a.weights, again.weights)


def test_kronecker_weights_are_symmetric():
    g = kronecker.generate(_kron(8), seed=3)
    w = {}
    for u, v, x in zip(g.sources(), g.indices, g.weights):
        w[(int(u), int(v))] = x
    assert all(w[(v, u)] == x for (u, v), x in w.items())


def test_to_undirected_dedups():
    g = csr.from_edges(3, np.array([0, 1, 0]), np.array([1, 0, 2]),
                       np.ones(3, np.float32))
    u = csr.to_undirected(g)
    assert u.nnz == 4
