"""Self-timed asynchronous distributed engine (core/async_dist.py).

The contract under test: ``dist_flavor="async"`` reaches the SAME
fixpoint as the bulk-synchronous distributed engine — bit-identical
converged state on every mesh factorization and every k — while
``DistStats.halo_exchanges`` strictly drops for k > 1 on multi-sweep
fixpoints.  The factorization parity cases read one subprocess on four
virtual CPU devices (``tests/dist_parity.py``), as
tests/test_distribution.py does.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import api
from repro.core import async_dist as AD
from repro.core import engine as eng
from repro.core import graph as G
from repro.core import placement as PL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (num_devices, query_axis): meshes 1x1, 4x1, 2x2 and 1x4
FACTORIZATIONS = [(1, 1), (4, 1), (4, 2), (4, 4)]
SEMIRINGS = ["min_plus", "min_select", "max_min", "plus_times"]
KS = [1, 2, 4]


def _batched_fixture(semiring):
    """(Prepared, stacked x0, sync-batched reference) for one semiring."""
    g = G.rmat(200, 900, seed=6)
    sources = [0, 5, 9, 13, 17]
    p = eng.prepare(g, semiring, b=8, num_clusters=8)
    if semiring == "max_min":
        def x0f(s):
            x = np.zeros(g.n, dtype=np.float32)
            x[s] = 1.0
            return np.asarray(p.to_blocks(x, 0.0))
    else:
        def x0f(s):
            x = np.full(g.n, np.inf, dtype=np.float32)
            x[s] = 0.0
            return np.asarray(p.to_blocks(x, np.inf))
    x0 = np.stack([x0f(s) for s in sources])
    ref, _ = eng.run_sync_batched(p, x0, max_sweeps=100_000)
    return p, x0, np.asarray(ref)


# -- parity + exchange reduction ----------------------------------------


@pytest.fixture(scope="module")
def parity():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "dist_parity.py"),
         "async"], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=900)
    lines = [ln for ln in run.stdout.splitlines() if ln.startswith("RESULT")]
    assert lines, run.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT"):])


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("ndev,qaxis", FACTORIZATIONS)
def test_async_parity_across_factorizations(parity, semiring, k, ndev,
                                            qaxis):
    """Async == the per-source sequential (bulk-synchronous) distributed
    engine, BIT-identical, on every factorization × k, for a wave of 5
    queries (padding queries on 2x2 and 1x4); on four virtual devices
    (``tests/dist_parity.py``)."""
    r = parity[f"{semiring}/{ndev // qaxis}x{qaxis}/k{k}"]
    assert r["equal"] and r["converged"]
    assert r["mesh"] == [ndev // qaxis, qaxis]
    assert r["local_sweeps"] == k
    assert len(r["query_sweeps"]) == 5
    assert r["sweeps"] == max(r["query_sweeps"])
    # per-shard self-timed sweep counters, one per "graph" shard
    assert len(r["shard_sweeps"]) == ndev // qaxis
    assert max(r["shard_sweeps"]) >= r["sweeps"]


@pytest.mark.parametrize("semiring", ["min_plus", "max_min"])
def test_k_strictly_reduces_halo_exchanges(semiring):
    """The acceptance criterion: k > 1 reaches the same fixpoint with
    STRICTLY fewer halo exchanges than the bulk-synchronous engine (which
    exchanges once per sweep)."""
    p, x0, ref = _batched_fixture(semiring)
    _, ds_sync = PL.distributed_sync_run_batched(
        p, x0, "relax", max_sweeps=100_000)
    assert ds_sync.halo_exchanges == ds_sync.sweeps  # BSP: 1 per sweep
    assert ds_sync.sweeps >= 3, "fixture too shallow to show reduction"
    exchanges = {}
    for k in (1, 2, 4):
        x, ds = AD.distributed_async_run_batched(
            p, x0, max_sweeps=100_000, local_sweeps=k)
        assert np.array_equal(np.asarray(x), ref)
        assert ds.converged
        if k > 1:
            assert ds.halo_exchanges < ds_sync.halo_exchanges
        exchanges[k] = ds.halo_exchanges
    # more local sweeps never needs more exchanges
    assert exchanges[4] <= exchanges[2] <= exchanges[1]


def test_single_source_wrapper_parity():
    """Exchange reduction needs intra-shard propagation to dominate, so
    pin a modest "graph" extent — at d_g=8 on this 200-vertex graph the
    cross-shard hop count (which no k can beat) is the whole fixpoint."""
    g = G.rmat(200, 900, seed=6)
    p = eng.prepare(g, "min_plus", b=8, num_clusters=8)
    x0 = np.full(g.n, np.inf, dtype=np.float32)
    x0[3] = 0.0
    xb = np.asarray(p.to_blocks(x0, np.inf))
    ndev = 2 if len(jax.devices()) >= 2 else 1
    mesh = PL.make_graph_mesh(ndev)
    xs, ds_sync = PL.distributed_sync_run(p, xb, "relax",
                                          max_sweeps=100_000, mesh=mesh)
    xa, ds = AD.distributed_async_run(p, xb, max_sweeps=100_000,
                                      mesh=mesh, local_sweeps=4)
    assert np.array_equal(np.asarray(xa), np.asarray(xs))
    assert ds.converged
    assert ds.halo_exchanges < ds_sync.halo_exchanges


# -- engine guards ------------------------------------------------------


def test_async_engine_rejects_non_relax():
    """PageRank's damped affine update is not idempotent — the k-local-
    sweep schedule would change its fixpoint, so the engine refuses."""
    p, x0, _ = _batched_fixture("min_plus")
    with pytest.raises(ValueError, match="relax"):
        AD.distributed_async_run_batched(p, x0, apply_kind="pagerank")


def test_async_engine_rejects_bad_k():
    p, x0, _ = _batched_fixture("min_plus")
    with pytest.raises(ValueError, match="local_sweeps"):
        AD.distributed_async_run_batched(p, x0, local_sweeps=0)


# -- policy plumbing (API level) ----------------------------------------


def test_policy_routes_async_flavor():
    """End-to-end through GraphProcessor: async flavor is bit-identical
    to the sync flavor and DistStats lands in Result.extra."""
    g = G.rmat(150, 600, seed=3)
    proc = api.GraphProcessor(g, b=8, num_clusters=8)
    pol_s = api.ExecutionPolicy(mode="distributed")
    pol_a = pol_s.but(dist_flavor="async", local_sweeps=4)
    for sources in (0, [0, 3, 7]):
        rs = proc.sssp(sources, policy=pol_s)
        ra = proc.sssp(sources, policy=pol_a)
        assert np.array_equal(rs.values, ra.values)
        ds = ra.extra["dist"]
        assert ds.local_sweeps == 4
        assert ds.halo_exchanges <= rs.extra["dist"].halo_exchanges
        # halo accounting follows exchanges, not sweeps, for the async
        # flavor (engine.dist_run_stats)
        if ds.halo_exchanges < rs.extra["dist"].halo_exchanges:
            assert ra.stats.halo_tiles < rs.stats.halo_tiles


def test_policy_async_pagerank_raises():
    g = G.rmat(150, 600, seed=3)
    proc = api.GraphProcessor(g, b=8, num_clusters=8)
    pol = api.ExecutionPolicy(mode="distributed", dist_flavor="async",
                              local_sweeps=2)
    with pytest.raises(ValueError, match="relax"):
        proc.pagerank(policy=pol)


def test_service_wave_uses_async_engine():
    """Coalesced GraphService waves dispatch through the async engine
    when the policy asks for it, bit-identical to sequential runs."""
    g = G.rmat(150, 600, seed=3)
    pol = api.ExecutionPolicy(mode="distributed", dist_flavor="async",
                              local_sweeps=4, max_sweeps=100_000)
    svc = api.GraphService()
    svc.register("g", g, b=8, num_clusters=8)
    sources = (0, 3, 7)
    tickets = [svc.submit("g", api.QuerySpec(algo="sssp", sources=(s,),
                                             policy=pol))
               for s in sources]
    out = svc.gather()
    proc = api.GraphProcessor(g, b=8, num_clusters=8)
    for t, s in zip(tickets, sources):
        res = out[t]
        assert not isinstance(res, Exception), res
        assert res.extra["coalesced"] == len(sources)
        assert res.extra["dist_flavor"] == "async"
        assert res.extra["dist"].local_sweeps == 4
        seq = proc.sssp(s, policy=pol)
        assert np.array_equal(res.values, seq.values)


# -- subprocess sweep for single-device hosts ---------------------------


_SUBPROCESS_8DEV_ASYNC = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"  # 8 fake host devices; never a chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
from repro.core import async_dist as AD, engine as E, graph as G, \
    placement as PL
g = G.rmat(200, 900, seed=6)
p = E.prepare(g, "min_plus", b=8, num_clusters=8)
sources = [0, 5, 9, 13, 17]
X0 = np.stack([np.asarray(p.to_blocks(
    np.where(np.arange(g.n) == s, 0, np.inf).astype(np.float32),
    np.inf)) for s in sources])
ref, _ = E.run_sync_batched(p, X0, max_sweeps=100_000)
ref = np.asarray(ref)
_, ds_sync = PL.distributed_sync_run_batched(
    p, X0, "relax", max_sweeps=100_000, mesh=PL.make_graph_mesh(8, 1))
for nd, qa in [(1, 1), (4, 2), (8, 1), (8, 8)]:
    for k in (1, 2, 4):
        m = PL.make_graph_mesh(nd, qa)
        x, ds = AD.distributed_async_run_batched(
            p, X0, max_sweeps=100_000, mesh=m, local_sweeps=k)
        assert np.array_equal(np.asarray(x), ref), (nd, qa, k)
        assert ds.converged and ds.mesh_shape == (nd // qa, qa)
        if k == 4:
            assert ds.halo_exchanges < ds_sync.halo_exchanges, (nd, qa)
print("OK8-ASYNC")
"""


def test_async_distributed_8_fake_devices():
    if len(jax.devices()) >= 8:
        pytest.skip("in-process factorization grid already covers this "
                    "on the multi-device lane")
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_8DEV_ASYNC],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert "OK8-ASYNC" in out.stdout, out.stderr[-2000:]
