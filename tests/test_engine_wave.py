"""The batched async engine's wave loop: a wave of Q queries carried
row-major, (r_pad, Q*B), so the SpMV gathers each tile's source block once
for the whole wave.  Its reference is the loop it replaced for the
unfused ref kernel, ``jax.vmap(_async_loop)``: values bit-identical,
per-query sweeps and every work counter equal."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import algebra, api
from repro.core import engine as eng
from repro.core import graph as G
from repro.kernels import ops, ref
from repro.kernels.spec import KernelSpec

REF = KernelSpec()


@pytest.fixture(scope="module")
def road():
    return G.road_network(20, seed=1)


def vmapped(p, x0, ch0, apply_kind, damping=0.85, max_sweeps=10_000):
    """The batched async engine as it runs the fused and Pallas kernels
    (and ran the ref kernel before the wave loop): vmap over queries."""
    inv_n = jnp.float32(1.0 / p.n)

    def one(x0q, ch0q):
        return eng._async_loop(
            p.vals, p.cols, p.nnz, p.valid, p.dangling, p.group_tiles,
            p.group_edges, p.group_ext_tiles, p.row_edges, p.row_ext, x0q,
            ch0q, jnp.float32(damping), jnp.float32(1e-6), inv_n,
            p.semiring, apply_kind, max_sweeps, p.gb, p.s, REF)

    return jax.vmap(one)(x0, ch0)


def wave(p, x0, ch0, apply_kind, damping=0.85, max_sweeps=10_000):
    return eng._async_wave_loop(
        p.vals, p.cols, p.nnz, p.valid, p.group_tiles, p.group_edges,
        p.group_ext_tiles, x0, ch0, jnp.float32(damping),
        jnp.float32(1e-6), jnp.float32(1.0 / p.n), p.semiring, apply_kind,
        max_sweeps, p.gb, p.s, REF)


def assert_same(a, b):
    for name, u, v in zip(("sweeps", "x", "done"), a[:3], b[:3]):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v),
                                      err_msg=name)
    assert sorted(a[3]) == sorted(b[3])
    for k in a[3]:
        np.testing.assert_array_equal(np.asarray(a[3][k]),
                                      np.asarray(b[3][k]), err_msg=k)


def wave_inputs(g, p, nq, semiring, seed):
    """Per-query starts: point sources (one query with none, so it
    converges after its first sweep while the others run on) and ragged
    frontiers (the source's row-block plus a random few)."""
    rng = np.random.default_rng(seed)
    if semiring == "min_select":      # connected-component labels
        x0 = [rng.permutation(g.n).astype(np.float32) for _ in range(nq)]
        pad = np.inf
    else:
        zero, src = ((-np.inf, np.inf) if semiring == "max_min"
                     else (np.inf, 0.0))
        pad = zero
        x0 = []
        for q in range(nq):
            v = np.full(g.n, zero, np.float32)
            if q != 1:
                v[rng.integers(g.n)] = src
            x0.append(v)
    ch0 = rng.random((nq, p.r_pad)) < 0.1
    for q, v in enumerate(x0):
        hot = np.flatnonzero(np.isfinite(v) if semiring != "max_min"
                             else v > -np.inf)
        ch0[q, p.perm[hot] // p.b] = True
    return (jnp.stack([p.to_blocks(v, pad) for v in x0]),
            jnp.asarray(ch0))


@pytest.mark.parametrize("b", [16, 32])
@pytest.mark.parametrize("nq", [1, 3, 8])
@pytest.mark.parametrize("semiring", ["min_plus", "min_select",
                                      "max_min"])
def test_wave_loop_matches_vmapped_loop_bit_for_bit(road, semiring, nq, b):
    p = eng.prepare(road, semiring, b=b, num_clusters=8)
    x0, ch0 = wave_inputs(road, p, nq, semiring, seed=nq * b)
    ref = vmapped(p, x0, ch0, "relax")
    got = wave(p, x0, ch0, "relax")
    assert_same(got, ref)
    if nq >= 3 and semiring != "min_select":
        # the query with no source froze long before the straggler
        sweeps = np.asarray(got[0])
        assert sweeps[1] == 1 and sweeps.max() > 5
    x, stats = eng.run_async_batched(p, x0, changed0=ch0)
    i, xr, done, c = ref
    np.testing.assert_array_equal(np.asarray(x), np.asarray(xr))
    assert stats == eng._counter_stats(
        p, int(np.asarray(i).max()), bool(np.all(done)), c, "async")


def test_wave_loop_first_touch_and_max_sweeps(road):
    """k-core peeling: a bias rule (every group touched on sweep 0)
    whose plus-times sums of 0/1 are exact in any order; and a sweep
    budget that stops the wave before it converges."""
    unit = G.Graph(n=road.n, indptr=road.indptr, indices=road.indices,
                   weights=np.ones_like(road.weights))
    p = eng.prepare(unit, "plus_times", b=16, num_clusters=8)
    rng = np.random.default_rng(3)
    x0 = jnp.stack([p.to_blocks((rng.random(road.n) < f)
                                .astype(np.float32), 0.0)
                    for f in (1.0, 0.9, 0.6)])
    ch0 = jnp.ones((3, p.r_pad), bool)
    for budget in (10_000, 2):
        assert_same(wave(p, x0, ch0, "kcore", damping=2.0,
                         max_sweeps=budget),
                    vmapped(p, x0, ch0, "kcore", damping=2.0,
                            max_sweeps=budget))


@pytest.mark.parametrize("k", [4, 1030])   # 1030: gathered as 1152 slots
@pytest.mark.parametrize("semiring", ["min_plus", "max_min", "min_select",
                                      "plus_times", "test_wave_max_times"])
def test_wave_kernel_is_the_per_query_kernel_per_query(semiring, k):
    if semiring not in algebra.SEMIRINGS:  # no reduce_fn: a generic ⊕-fold
        algebra.register(algebra.Semiring(
            name=semiring, add=jnp.maximum, mul=jnp.multiply, zero=0.0,
            one=1.0, improves=lambda new, old: new > old))
    rng = np.random.default_rng(5)
    r, c, nq, b = 5, 7, 3, 16
    vals = rng.uniform(0.1, 1.0, (r, b, k * b)).astype(np.float32)
    vals[rng.random(vals.shape) < 0.5] = algebra.get(semiring).zero
    cols = jnp.asarray(rng.integers(0, c, (r, k)), jnp.int32)
    x = rng.uniform(0.0, 1.0, (c, nq, b)).astype(np.float32)
    x[rng.random(x.shape) < 0.2] = np.inf if semiring in (
        "min_plus", "min_select") else 0.0
    y = ref.bsr_spmv_wave_ref(jnp.asarray(vals), cols, jnp.asarray(x),
                              semiring)
    for q in range(nq):
        want = ref.bsr_spmv_ref(jnp.asarray(vals), cols,
                                jnp.asarray(x[:, q]), semiring)
        if semiring == "plus_times":   # the einsum may sum in any order
            np.testing.assert_allclose(y[:, q], want, rtol=1e-6)
        else:
            np.testing.assert_array_equal(y[:, q], want)


def test_wave_path_is_the_unfused_ref_kernel_only():
    assert eng.wave_path(KernelSpec())
    assert not eng.wave_path(KernelSpec(impl="pallas"))
    assert not eng.wave_path(KernelSpec(impl="pallas", fuse_frontier=True))
    assert ops.has_kernel("bsr_spmv", KernelSpec(impl="pallas"))


@pytest.mark.parametrize("nq,b", [(8, 16), (3, 16), (2, 32)])
def test_wave_gather_is_one_contiguous_row_per_tile(road, nq, b):
    """The layout the wave's gain rests on: the compiled source gather
    takes one row of Q*B values per tile, where the vmapped loop takes
    Q strided rows of B."""
    p = eng.prepare(road, "min_plus", b=b, num_clusters=4)
    x0 = jnp.zeros((nq, p.r_pad, b), jnp.float32)
    ch0 = jnp.ones((nq, p.r_pad), bool)

    def slices(fn):
        text = jax.jit(fn).lower(x0, ch0).compile().as_text()
        return [re.search(r"slice_sizes=\{([\d,]+)\}", ln).group(1)
                for ln in text.splitlines()
                if "sweep.spmv" in ln and " gather(" in ln]

    assert slices(lambda a, c: wave(p, a, c, "relax")) == [f"1,{nq * b}"]
    old = slices(lambda a, c: vmapped(p, a, c, "relax"))
    assert old and all(
        np.prod([int(n) for n in s.split(",")]) == b for s in old)


def test_coalesced_server_waves_bit_identical_to_sequential(road):
    svc = api.GraphService()
    svc.register("roads", road, b=16, num_clusters=8)
    srcs = list(range(0, 400, 50))
    server = api.GraphServer(service=svc, autostart=False,
                             wave=api.WavePolicy(max_wait_s=0.005,
                                                 max_wave=8))
    futs = {s: server.submit("roads", api.QuerySpec(algo="sssp",
                                                    sources=(s,)))
            for s in srcs}
    server.start()
    for s, f in futs.items():
        assert f.result(120).extra["coalesced"] == len(srcs)
        np.testing.assert_array_equal(
            f.result().values,
            svc.run("roads", api.QuerySpec(algo="sssp",
                                           sources=(s,))).values)
    server.close()
