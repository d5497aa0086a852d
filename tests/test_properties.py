"""Hypothesis property tests on the system's invariants."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="hypothesis not installed in this container; property tests "
           "are exercised where it is available")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro import algebra
from repro.core import algorithms as A
from repro.core import cluster as C
from repro.core import graph as G
from repro.core import oracles as O
from repro.kernels import ops
from repro.train import compress

graphs = st.builds(
    lambda n, d, seed: G.rmat(n, n * d, seed=seed),
    n=st.integers(24, 120), d=st.integers(2, 6), seed=st.integers(0, 99))


@settings(max_examples=12, deadline=None)
@given(graphs, st.integers(2, 8), st.integers(4, 16))
def test_cluster_perm_is_permutation_and_balanced(g, k, b):
    c = C.cluster_graph(g, k)
    assert sorted(c.perm.tolist()) == list(range(g.n))
    assert c.sizes.sum() == g.n
    assert c.balance() <= 2.0  # contiguous-chunk clustering is balanced
    assert sorted(c.schedule.tolist()) == list(range(c.num_clusters))
    _ = b


@settings(max_examples=10, deadline=None)
@given(graphs, st.integers(0, 10))
def test_sssp_async_matches_dijkstra(g, src_seed):
    src = src_seed % g.n
    r = A.sssp(g, src, mode="async", b=8, num_clusters=6)
    np.testing.assert_allclose(r.values, O.sssp_oracle(g, src),
                               rtol=1e-5, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(graphs)
def test_pagerank_l1_and_engines_agree(g):
    ra = A.pagerank(g, tol=1e-10, mode="async", b=8, num_clusters=6)
    rs = A.pagerank(g, tol=1e-10, mode="sync", b=8, num_clusters=6)
    assert abs(ra.values.sum() - 1.0) < 1e-4
    np.testing.assert_allclose(ra.values, rs.values, rtol=1e-3, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(graphs)
def test_async_work_never_exceeds_sync(g):
    """The self-timed engine's edge work ≤ bulk-synchronous edge work —
    the paper's core efficiency claim, as an invariant."""
    ra = A.sssp(g, 0, mode="async", b=8, num_clusters=6)
    rs = A.sssp(g, 0, mode="sync", b=8, num_clusters=6)
    assert ra.stats.edge_work <= rs.stats.edge_work + 1e-6
    np.testing.assert_allclose(ra.values, rs.values, rtol=1e-5, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(graphs, st.sampled_from(["plus_times", "min_plus", "max_min"]))
def test_spmv_invariant_under_clustering_permutation(g, semi):
    """SpMV commutes with vertex relabeling — clustering cannot change
    results, only locality."""
    rng = np.random.default_rng(1)
    x = rng.random(g.n).astype(np.float32)
    if semi == "max_min":
        x = (x > 0.5).astype(np.float32)
    z = float(algebra.get(semi).zero)

    def spmv(graph, xv):
        bsr = G.to_bsr(graph, b=8, pad_value=z)
        xb = np.full(bsr.n_pad, z, np.float32)
        xb[: graph.n] = xv
        y = ops.bsr_spmv(jnp.asarray(bsr.block_vals),
                         jnp.asarray(bsr.block_cols),
                         jnp.asarray(bsr.block_nnz),
                         jnp.asarray(xb.reshape(bsr.r, bsr.b)),
                         semiring=semi, impl="ref")
        return np.asarray(y).reshape(-1)[: graph.n]

    c = C.cluster_graph(g, 6)
    g2 = g.permute(c.perm.astype(np.int32))
    y1 = spmv(g, x)
    x2 = np.empty_like(x)
    x2[c.perm] = x  # new-id layout
    y2 = spmv(g2, x2)
    # old vertex v lives at new id perm[v]
    np.testing.assert_allclose(y1, y2[c.perm], rtol=1e-4, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.floats(-1e3, 1e3), min_size=4, max_size=64))
def test_int8_compression_error_bound(xs):
    x = jnp.asarray(np.array(xs, np.float32))
    q, s = compress.quantize(x)
    err = np.abs(np.asarray(compress.dequantize(q, s)) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-6


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 40))
def test_error_feedback_mean_converges(seed):
    """EF-quantized repeated transmission of a constant tensor: the
    running mean of decoded values converges to the true value."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(32).astype(np.float32))
    err = jnp.zeros(32, jnp.float32)
    acc = np.zeros(32)
    n = 24
    for _ in range(n):
        q, s, err = compress.compress_tree(x, err)
        acc += np.asarray(compress.dequantize(q, s))
    np.testing.assert_allclose(acc / n, np.asarray(x), atol=2e-2)


@settings(max_examples=30, deadline=None)
@given(st.sampled_from(list(algebra.SEMIRINGS)), st.data())
def test_semiring_axioms(name, data):
    s = algebra.get(name)
    # the carrier holds normal float32 values and zero: XLA flushes
    # subnormals to zero.  max_min's is {0..1}.
    hi = 1.0 if s is algebra.MAX_MIN else 100.0
    vals = data.draw(st.lists(
        st.floats(0.0, hi, width=32, allow_subnormal=False),
        min_size=3, max_size=3))
    a, b, c = [jnp.float32(v) for v in vals]
    zero = jnp.float32(s.zero)
    # ⊕ associative + commutative; zero is ⊕-identity
    np.testing.assert_allclose(s.add(a, s.add(b, c)),
                               s.add(s.add(a, b), c), rtol=1e-6)
    np.testing.assert_allclose(s.add(a, b), s.add(b, a), rtol=1e-6)
    np.testing.assert_allclose(s.add(a, zero), a, rtol=1e-6)
    # ⊗: one is ⊗-identity (w side); zero absorbs (the register()
    # contract that makes identity-padded tiles no-ops)
    np.testing.assert_allclose(s.mul(jnp.float32(s.one), a), a, rtol=1e-6)
    np.testing.assert_array_equal(s.mul(zero, a), zero)
