"""The algorithm catalog vs. reference oracles.

Part 1: the paper's six algorithms on all three workload families
(road / power-law / ring), both local engines.

Part 2 (PR 9): the AlgorithmSpec registry — parity grid for the four
new families (pagerank_delta / cc / kcore / tricount) across every
engine flavor (sync × async × distributed sync/async × ref/fused
kernels), bit-identical where the update rule is exact and
tolerance-bounded for the accumulation family, plus regression tests
for registry-driven dispatch (custom semirings, construction-time
QuerySpec validation, the removed PageRank ValueError)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro import algebra, api
from repro.core import algorithms as A
from repro.core import graph as G
from repro.core import oracles as O

GRAPHS = {
    "road": lambda: G.road_network(14, seed=1),
    "rmat": lambda: G.rmat(250, 1200, seed=2),
    "ring": lambda: G.ring(64),
}


def _partition(labels):
    m = {}
    for i, l_ in enumerate(labels):
        m.setdefault(round(float(l_), 4), set()).add(i)
    return sorted(map(frozenset, m.values()))


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_sssp(gname, mode):
    g = GRAPHS[gname]()
    r = A.sssp(g, 0, mode=mode, b=16, num_clusters=8)
    np.testing.assert_allclose(r.values, O.sssp_oracle(g, 0), rtol=1e-5,
                               atol=1e-4)
    assert r.stats.converged


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_bfs(gname, mode):
    g = GRAPHS[gname]()
    r = A.bfs(g, 0, mode=mode, b=16, num_clusters=8)
    np.testing.assert_array_equal(r.values, O.bfs_oracle(g, 0))


@pytest.mark.parametrize("gname", ["road", "rmat"])
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_pagerank(gname, mode):
    g = GRAPHS[gname]()
    r = A.pagerank(g, tol=1e-9, mode=mode, b=16, num_clusters=8)
    pr = O.pagerank_oracle(g, tol=1e-12)
    assert np.max(np.abs(r.values - pr)) < 1e-5
    assert abs(r.values.sum() - 1.0) < 1e-5


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_connected_components(gname, mode):
    g = GRAPHS[gname]()
    r = A.connected_components(g, mode=mode, b=16, num_clusters=8)
    assert _partition(r.values) == _partition(O.cc_oracle(g))


@pytest.mark.parametrize("gname", ["road", "rmat"])
def test_minitri(gname):
    g = GRAPHS[gname]()
    r = A.minitri(g)
    assert r.extra["triangles"] == O.triangles_oracle(g)


@pytest.mark.parametrize("gname", list(GRAPHS))
def test_dfs(gname):
    g = GRAPHS[gname]()
    r = A.dfs(g, 0)
    order, parent = O.dfs_oracle(g, 0)
    nv = r.extra["visited_count"]
    assert nv == len(order)
    np.testing.assert_array_equal(r.values[:nv], order)
    np.testing.assert_array_equal(r.extra["parent"], parent)


def test_reachability():
    g = GRAPHS["rmat"]()
    r = A.reachability(g, 0, mode="sync", b=16, num_clusters=8)
    np.testing.assert_array_equal(r.values > 0,
                                  np.isfinite(O.bfs_oracle(g, 0)))


def test_async_beats_sync_on_road():
    """Paper claim (directional): data-driven execution does less work
    than bulk-synchronous on high-diameter graphs."""
    g = GRAPHS["road"]()
    ra = A.sssp(g, 0, mode="async", b=16, num_clusters=16)
    rs = A.sssp(g, 0, mode="sync", b=16, num_clusters=16)
    assert ra.stats.edge_work < rs.stats.edge_work
    assert ra.stats.sweeps <= rs.stats.sweeps


def test_clustering_improves_tile_density():
    g = G.rmat(400, 2000, seed=7)
    from repro.core.cluster import cluster_graph, tile_stats_after
    c = cluster_graph(g, 16)
    st = tile_stats_after(g, c, b=16)
    assert st["fill_clustered"] >= st["fill_identity"]


# ---------------------------------------------------------------------------
# PR 9 — parity grid: the four new families through every engine flavor
# ---------------------------------------------------------------------------

# Every engine flavor the relaxation path can run under.  Distributed
# flavors degrade gracefully to a 1×1 mesh on a single device and widen
# to real meshes under the DEVICES=8 CI lane.
FLAVORS = {
    "sync-ref": api.ExecutionPolicy(mode="sync"),
    "sync-fused": api.ExecutionPolicy(
        mode="sync",
        kernel=api.KernelSpec(impl="pallas", fuse_frontier=True)),
    "async-ref": api.ExecutionPolicy(mode="async"),
    "async-fused": api.ExecutionPolicy(
        mode="async",
        kernel=api.KernelSpec(impl="pallas", fuse_frontier=True)),
    "dist-sync": api.ExecutionPolicy(mode="distributed"),
    "dist-async": api.ExecutionPolicy(mode="distributed",
                                      dist_flavor="async", local_sweeps=2),
}

PARITY_GRAPHS = {
    "road": lambda: G.road_network(8, seed=1),
    "rmat": lambda: G.rmat(96, 520, seed=5),
}

_PROCS = {}


def _proc(gname):
    if gname not in _PROCS:
        _PROCS[gname] = api.GraphProcessor(PARITY_GRAPHS[gname](),
                                           b=16, num_clusters=8)
    return _PROCS[gname]


@pytest.mark.parametrize("flavor", list(FLAVORS))
@pytest.mark.parametrize("gname", list(PARITY_GRAPHS))
def test_pagerank_delta_parity(gname, flavor):
    """Delta-accumulating PageRank is flavor-eligible everywhere —
    including dist_flavor='async', which rejected classic pagerank —
    and lands within the tol/(1-d) accumulation bound of the oracle."""
    proc = _proc(gname)
    pol = FLAVORS[flavor].but(tol=1e-10, max_sweeps=3000)
    r = proc.pagerank_delta(policy=pol)
    pr = O.pagerank_oracle(proc.g, tol=1e-12)
    assert np.max(np.abs(np.asarray(r.values) - pr)) < 1e-5
    assert abs(float(np.asarray(r.values).sum()) - 1.0) < 1e-5
    assert r.stats.converged


@pytest.mark.parametrize("flavor", list(FLAVORS))
@pytest.mark.parametrize("gname", list(PARITY_GRAPHS))
def test_cc_parity(gname, flavor):
    """min_select label propagation is idempotent ⇒ every flavor lands
    on the identical fixpoint, bit-for-bit."""
    proc = _proc(gname)
    r = proc.run(api.QuerySpec(algo="cc", policy=FLAVORS[flavor]))
    baseline = proc.run(api.QuerySpec(algo="cc", policy=FLAVORS["sync-ref"]))
    np.testing.assert_array_equal(np.asarray(r.values),
                                  np.asarray(baseline.values))
    assert _partition(np.asarray(r.values)) == _partition(O.cc_oracle(proc.g))


@pytest.mark.parametrize("k", [2, 3])
@pytest.mark.parametrize("flavor", list(FLAVORS))
def test_kcore_parity(flavor, k):
    """k-core peeling is monotone-decreasing and exact: bit-identical
    membership across every flavor, equal to the numpy peeling oracle."""
    proc = _proc("rmat")
    r = proc.kcore(k, policy=FLAVORS[flavor])
    np.testing.assert_array_equal(np.asarray(r.values),
                                  O.kcore_oracle(proc.g, k))
    assert r.stats.converged


def test_kcore_isolated_vertices_die():
    """bias=True regression: rows with no undirected neighbors must be
    touched once so they leave the core (fused sweep-0 / async
    first-touch both honor UpdateRule.bias)."""
    g = G.rmat(64, 150, seed=9)
    proc = api.GraphProcessor(g, b=16, num_clusters=4)
    for flavor in ("sync-fused", "async-ref"):
        r = proc.kcore(1, policy=FLAVORS[flavor])
        np.testing.assert_array_equal(np.asarray(r.values),
                                      O.kcore_oracle(g, 1))


@pytest.mark.parametrize("gname", ["road", "rmat"])
def test_tricount(gname):
    """Per-vertex triangle counts: exact match against the dense
    oracle, and the global total agrees with minitri's."""
    proc = _proc(gname)
    r = proc.tricount()
    np.testing.assert_array_equal(np.asarray(r.values),
                                  O.tricount_oracle(proc.g))
    assert r.extra["triangles"] == O.triangles_oracle(proc.g)
    assert int(np.asarray(r.values).sum()) == 3 * r.extra["triangles"]


def test_tricount_free_function():
    g = PARITY_GRAPHS["rmat"]()
    r = A.tricount(g)
    assert r.extra["triangles"] == O.triangles_oracle(g)


# ---------------------------------------------------------------------------
# PR 9 — registry-driven dispatch regressions
# ---------------------------------------------------------------------------


def test_classic_pagerank_still_rejected_by_async_dist():
    """The order-sensitive accumulation rule stays ineligible for the
    self-timed distributed schedule; the error now names the delta form."""
    proc = _proc("rmat")
    pol = api.ExecutionPolicy(mode="distributed", dist_flavor="async",
                              local_sweeps=2)
    with pytest.raises(ValueError, match="pagerank_delta"):
        proc.run(api.QuerySpec(algo="pagerank", policy=pol))


def test_unknown_algorithm_fails_at_construction():
    """QuerySpec validates against the registry at construction time and
    lists what is registered."""
    with pytest.raises(ValueError, match="unknown algorithm"):
        api.QuerySpec(algo="warp", sources=(0,))
    with pytest.raises(ValueError, match="pagerank_delta"):
        api.QuerySpec(algo="warp", sources=(0,))


def test_kcore_requires_k():
    proc = _proc("rmat")
    with pytest.raises(ValueError, match="requires params"):
        proc.run(api.QuerySpec(algo="kcore"))


def test_registry_introspection():
    names = api.registered_algorithms()
    for want in ("sssp", "bfs", "pagerank", "pagerank_delta", "cc",
                 "kcore", "tricount", "minitri", "reachability", "dfs"):
        assert want in names
    spec = api.get_algorithm("pagerank_delta")
    assert spec.semiring == "plus_times"
    assert algebra.rule(spec.update).monotone
    assert not algebra.rule("pagerank").monotone
    with pytest.raises(ValueError, match="unknown algorithm"):
        api.get_algorithm("warp")


# ---------------------------------------------------------------------------
# PR 9 — custom semirings: reduce() field + generic kernel fallback
# ---------------------------------------------------------------------------


def _max_times_ring():
    """Best-reliability ring over [0, 1] weights: ⊕ = max, ⊗ = ×.
    zero=0.0 absorbs under ⊗ (the register() contract)."""
    name = "test_max_times"
    if name not in algebra.SEMIRINGS:
        algebra.register(algebra.Semiring(
            name=name,
            add=jnp.maximum,
            mul=jnp.multiply,
            zero=0.0,
            one=1.0,
            improves=lambda new, old: new > old,
            reduce_fn=lambda x, axis=None: jnp.max(x, axis=axis),
        ))
    return algebra.get(name)


def test_custom_semiring_reduce_is_a_field():
    """Satellite 1: Semiring.reduce dispatches through the dataclass
    field (or the generic ⊕-fold), not a name switch — a freshly
    registered ring must reduce without touching builtin names."""
    ring = _max_times_ring()
    x = jnp.asarray(np.random.default_rng(0).random((3, 4, 5)),
                    dtype=jnp.float32)
    np.testing.assert_allclose(ring.reduce(x, axis=(0, 2)),
                               np.max(np.asarray(x), axis=(0, 2)))
    # a ring registered with reduce_fn=None gets the generic ⊕-fold
    noname = algebra.Semiring(name="test_fold", add=jnp.maximum,
                              mul=jnp.multiply, zero=0.0, one=1.0,
                              improves=lambda new, old: new > old)
    np.testing.assert_allclose(np.asarray(noname.reduce(x, axis=(1,))),
                               np.max(np.asarray(x), axis=1), rtol=1e-6)
    np.testing.assert_allclose(float(noname.reduce(x)),
                               float(np.max(np.asarray(x))), rtol=1e-6)


def test_custom_semiring_ref_kernel_fallback():
    """bsr_spmv_ref must handle any registered ring via the generic
    ⊗-then-⊕ path (it used to raise ValueError off the builtin list)."""
    from repro.kernels.ref import bsr_spmv_ref
    ring = _max_times_ring()
    rng = np.random.default_rng(3)
    r_, k_, b_, c_ = 3, 2, 4, 5
    vals = rng.random((r_, k_, b_, b_)).astype(np.float32)
    cols = rng.integers(0, c_, size=(r_, k_)).astype(np.int32)
    x = rng.random((c_, b_)).astype(np.float32)
    # destination-major tile image: [r, i, k*b+j] = vals[r, k, i, j]
    image = vals.transpose(0, 2, 1, 3).reshape(r_, b_, k_ * b_)
    y = np.asarray(bsr_spmv_ref(jnp.asarray(image), jnp.asarray(cols),
                                jnp.asarray(x), semiring=ring.name))
    want = (vals * x[cols][:, :, None, :]).max(axis=(1, 3))
    np.testing.assert_allclose(y, want, rtol=1e-6)


def test_custom_algorithm_end_to_end():
    """Registering a ring + AlgorithmSpec is all it takes to run through
    GraphProcessor.run — no engine/kernel edits (the tentpole claim)."""
    _max_times_ring()
    name = "test_reliability"
    if name not in api.registered_algorithms():
        api.register_algorithm(api.AlgorithmSpec(
            name=name,
            semiring="test_max_times",
            update="relax",
            variant="base",
            source_required=True,
            init=lambda p, src, pol: np.where(
                np.arange(p.n) == src, 1.0, 0.0).astype(np.float32),
            default_policy=(("max_sweeps", 10_000),),
        ))
    g = G.rmat(80, 400, seed=11)
    # squash weights into (0, 1] so products are path reliabilities
    g = G.Graph(n=g.n, indptr=g.indptr, indices=g.indices,
                weights=(1.0 / (1.0 + g.weights)).astype(np.float32))

    def oracle(g, src):
        x = np.zeros(g.n, dtype=np.float64)
        x[src] = 1.0
        srcs = np.repeat(np.arange(g.n), np.diff(g.indptr))
        for _ in range(g.n):
            cand = x[srcs] * g.weights
            x_new = x.copy()
            np.maximum.at(x_new, g.indices, cand)
            if np.array_equal(x_new, x):
                break
            x = x_new
        return x.astype(np.float32)

    proc = api.GraphProcessor(g, b=16, num_clusters=8)
    for mode in ("sync", "async"):
        r = proc.run(api.QuerySpec(algo=name, sources=(0,),
                                   policy=api.ExecutionPolicy(mode=mode)))
        np.testing.assert_allclose(np.asarray(r.values), oracle(g, 0),
                                   rtol=1e-5, atol=1e-6)


def _within_radius_rule():
    """Shortest paths no longer than a radius, which rides ``damping``;
    vertices farther away stay unreached (+inf).  Monotone and exact: a
    distance inside the radius only shrinks, so it never leaves it."""
    name = "test_within_radius"
    if name not in algebra.UPDATE_RULES:
        def within(ring, y, x, valid, radius, inv_n, tol):
            x_new = ring.add(y, x)
            x_new = jnp.where(x_new <= radius, x_new, jnp.inf)
            return x_new, ring.improves(x_new, x)
        algebra.register_rule(algebra.UpdateRule(
            name, within, bias=False, monotone=True, exact=True))
    return name


@pytest.mark.parametrize("engine", ["sync", "async", "wave", "fused"])
def test_registered_rule_runs_through_every_engine(engine):
    """A rule registered with ``register_rule`` runs on the sync and
    async engines, the batched wave loop and the fused Pallas kernel,
    and equals a NumPy Bellman-Ford cut at the radius."""
    from repro.core import engine as eng
    from repro.kernels.spec import KernelSpec
    rule = _within_radius_rule()
    g = G.rmat(120, 600, seed=5)
    # whole weights: every path length is exact in float32
    g = G.Graph(n=g.n, indptr=g.indptr, indices=g.indices,
                weights=np.floor(g.weights))
    radius = 6.0
    heads = np.repeat(np.arange(g.n), np.diff(g.indptr))

    def oracle(src):
        d = np.full(g.n, np.inf, np.float32)
        d[src] = 0.0
        while True:
            nxt = d.copy()
            np.minimum.at(nxt, g.indices, d[heads] + g.weights)
            nxt[nxt > radius] = np.inf
            if np.array_equal(nxt, d):
                return d
            d = nxt

    sources = [int(np.argmax(np.diff(g.indptr))), 1]
    want = [oracle(s) for s in sources]
    # the radius cuts off vertices the source reaches
    assert (np.isfinite(O.sssp_oracle(g, sources[0]))
            & ~np.isfinite(want[0])).any()
    assert np.isfinite(want[0]).sum() > 1
    p = eng.prepare(g, "min_plus", b=8, num_clusters=4)
    x0 = [p.to_blocks(np.where(np.arange(g.n) == s, 0.0, np.inf)
                      .astype(np.float32), np.inf) for s in sources]
    if engine == "wave":
        x, _ = eng.run_async_batched(p, jnp.stack(x0), rule, damping=radius)
        got = [p.from_blocks(xq) for xq in x]
    else:
        run = eng.run_sync if engine == "sync" else eng.run_async
        kernel = (KernelSpec(impl="pallas", fuse_frontier=True)
                  if engine == "fused" else None)
        got = [p.from_blocks(run(p, x, rule, damping=radius,
                                 kernel=kernel)[0]) for x in x0]
    for g_, w_ in zip(got, want):
        np.testing.assert_array_equal(g_, w_)
