"""KernelSpec policy surface: validation, dispatch registry, the
deprecated ``impl="pallas"`` spelling, engine-level fused-vs-ref
identity, and measured-tuning determinism + PlanStore persistence."""

import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import engine as eng
from repro.core import graph as G
from repro.kernels import ops
from repro.kernels import autotune as at
from repro.kernels.spec import KernelSpec, as_kernel_spec
from repro.launch.roofline import PEAKS

FUSED = KernelSpec(impl="pallas", fuse_frontier=True)


@pytest.fixture(scope="module")
def graph():
    return G.erdos(200, 0.03, seed=2, weighted=True)


@pytest.fixture(scope="module")
def proc(graph):
    return api.GraphProcessor(graph, b=16, num_clusters=16)


# -- KernelSpec validation --------------------------------------------------

def test_spec_rejects_ref_with_pallas_knobs():
    with pytest.raises(ValueError, match="impl='pallas'"):
        KernelSpec(impl="ref", block_size=8)
    with pytest.raises(ValueError, match="impl='pallas'"):
        KernelSpec(impl="ref", fuse_frontier=True)
    with pytest.raises(ValueError, match="impl='pallas'"):
        KernelSpec(impl="ref", autotune=True)


def test_spec_rejects_incoherent_combos():
    with pytest.raises(ValueError, match="one of"):
        KernelSpec(impl="mosaic")
    with pytest.raises(ValueError, match="positive int"):
        KernelSpec(impl="pallas", block_size=0)
    with pytest.raises(ValueError, match="rows_per_step"):
        KernelSpec(impl="pallas", fuse_frontier=True, rows_per_step=2)
    with pytest.raises(ValueError, match="nothing to tune"):
        KernelSpec(impl="pallas", autotune=True, block_size=8,
                   rows_per_step=2)
    with pytest.raises(ValueError, match="nothing to tune"):
        KernelSpec(impl="pallas", autotune=True, fuse_frontier=True,
                   block_size=8)


def test_spec_concrete_fills_knobs():
    s = KernelSpec(impl="pallas", autotune=True)
    c = s.concrete({"block_size": 4, "rows_per_step": 2})
    assert (c.block_size, c.rows_per_step, c.autotune) == (4, 2, False)
    assert KernelSpec(impl="pallas").concrete() == KernelSpec(
        impl="pallas", block_size=8, rows_per_step=1)
    f = FUSED.concrete({"block_size": 16, "rows_per_step": 4})
    assert (f.block_size, f.rows_per_step) == (16, 1)  # fused pins rs=1


def test_as_kernel_spec_coercions():
    assert as_kernel_spec(None) == KernelSpec()
    assert as_kernel_spec("pallas") == KernelSpec(impl="pallas")
    assert as_kernel_spec(FUSED) is FUSED
    with pytest.raises(TypeError):
        as_kernel_spec(42)


# -- dispatch registry ------------------------------------------------------

def test_select_kernel_registry():
    assert callable(ops.select_kernel("bsr_spmv", KernelSpec()))
    assert callable(ops.select_kernel("bsr_spmv", FUSED))
    with pytest.raises(KeyError, match="registered"):
        ops.select_kernel("conv2d", KernelSpec())
    with pytest.raises(KeyError, match="registered"):
        # attention has no fused variant; the registry fails loudly
        # instead of silently dropping the fuse_frontier request
        ops.select_kernel("attention", FUSED)


def test_platform_guard():
    assert ops.use_interpret("cpu") and not ops.use_interpret("tpu")


# -- ExecutionPolicy surface ------------------------------------------------

def test_impl_pallas_deprecated_but_equal():
    with pytest.warns(DeprecationWarning, match="KernelSpec"):
        old = api.ExecutionPolicy(impl="pallas")
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        new = api.ExecutionPolicy(kernel=KernelSpec(impl="pallas"))
        ref = api.ExecutionPolicy(impl="ref")
        dflt = api.ExecutionPolicy()
    assert old == new and old.kernel == KernelSpec(impl="pallas")
    assert ref == dflt and dflt.kernel == KernelSpec(impl="ref")


def test_policy_rejects_conflicts():
    with pytest.raises(ValueError):
        api.ExecutionPolicy(impl="ref", kernel=KernelSpec(impl="pallas"))
    with pytest.raises(ValueError, match="distributed"):
        api.ExecutionPolicy(mode="distributed",
                            kernel=KernelSpec(impl="pallas"))


def test_policy_but_rederives_the_other_spelling():
    pol = api.ExecutionPolicy(kernel=KernelSpec(impl="pallas",
                                                block_size=4))
    assert pol.but(impl="ref").kernel == KernelSpec(impl="ref")
    assert api.ExecutionPolicy().but(kernel=FUSED).impl == "pallas"
    assert pol.but(tol=1e-3).kernel == pol.kernel  # untouched knobs ride


# -- engine-level fused vs ref identity -------------------------------------

@pytest.mark.parametrize("mode", ["sync", "async"])
@pytest.mark.parametrize("algo", ["sssp", "bfs", "reachability", "cc"])
def test_engine_fused_bit_identical(proc, mode, algo, rng):
    # degrade=False: a kernel failure must fail the test, not re-run
    # the query on ref and compare ref with ref
    pol = api.ExecutionPolicy(mode=mode, max_sweeps=10_000, degrade=False)
    polf = pol.but(kernel=FUSED)
    run = {"sssp": lambda pl: proc.sssp(3, policy=pl),
           "bfs": lambda pl: proc.bfs(3, policy=pl),
           "reachability": lambda pl: proc.reachability(3, policy=pl),
           "cc": lambda pl: proc.connected_components(policy=pl)}[algo]
    r0, r1 = run(pol), run(polf)
    np.testing.assert_array_equal(r0.values, r1.values)
    assert r0.stats.sweeps == r1.stats.sweeps
    assert r0.stats.converged and r1.stats.converged


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_engine_fused_pagerank(proc, mode):
    # plus_times accumulates in a different grouping inside the fused
    # kernel; over a full damped-iteration trajectory the drift stays
    # below the convergence tolerance but is not bitwise
    pol = api.ExecutionPolicy(mode=mode, degrade=False)
    r0 = proc.pagerank(policy=pol)
    r1 = proc.pagerank(policy=pol.but(kernel=FUSED))
    np.testing.assert_allclose(r0.values, r1.values, atol=1e-6)
    assert r0.stats.sweeps == r1.stats.sweeps


def test_engine_fused_batched(proc):
    pol = api.ExecutionPolicy(mode="sync", max_sweeps=10_000,
                              degrade=False)
    r0 = proc.sssp(sources=[0, 5, 9], policy=pol)
    r1 = proc.sssp(sources=[0, 5, 9], policy=pol.but(kernel=FUSED))
    np.testing.assert_array_equal(r0.values, r1.values)
    assert r0.stats.sweeps == r1.stats.sweeps


def test_fused_all_converged_early_exit(graph):
    """A dead frontier must cost exactly one (empty) sweep and pass the
    state through untouched."""
    p = eng.prepare(graph, "min_plus", b=16, num_clusters=16)
    x0 = p.to_blocks(np.zeros(graph.n, np.float32), 0.0)
    x, stats = eng.run_sync(p, x0, "relax", kernel=FUSED.concrete(),
                            changed0=jnp.zeros(p.r_pad, bool))
    assert stats.sweeps == 1 and stats.converged
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x0))
    assert stats.tile_work == 0.0


# -- measured autotuner -----------------------------------------------------

def _fake_measure(calls):
    def measure(call, config, iters):
        calls.append(config)
        # deterministic synthetic cost: favour bk=4, rs=2
        return (abs(config.block_size - 4) + 1) * \
            (abs((config.rows_per_step or 1) - 2) + 1) * 1e-6
    return measure


def test_autotune_deterministic(proc):
    p = proc.prepare("min_plus")
    spec = KernelSpec(impl="pallas", autotune=True)
    calls = []
    rec1 = at.autotune_spmv(p, spec, seed=0, measure=_fake_measure(calls))
    rec2 = at.autotune_spmv(p, spec, seed=0, measure=_fake_measure([]))
    assert rec1 == rec2
    assert (rec1["block_size"], rec1["rows_per_step"]) == (4, 2)
    assert rec1["seed"] == 0
    assert len(calls) == len(rec1["candidates"])
    assert rec1["measured_s"] > 0
    # the roofline cross-check exists only on chips with published peaks
    assert (rec1["modeled_s"] is None) == (rec1["device_kind"] not in PEAKS)
    # pinned fields shrink the sweep
    pinned = at.autotune_spmv(
        p, KernelSpec(impl="pallas", autotune=True, block_size=8),
        seed=0, measure=_fake_measure([]))
    assert all(c["block_size"] == 8 for c in pinned["candidates"])
    with pytest.raises(ValueError):
        at.autotune_spmv(p, KernelSpec(impl="ref"), seed=0)


def test_autotune_cached_per_plan(graph):
    proc = api.GraphProcessor(graph, b=16, num_clusters=16)
    spec = KernelSpec(impl="pallas", fuse_frontier=True, autotune=True)
    pol = api.ExecutionPolicy(mode="sync", kernel=spec, degrade=False)
    r1 = proc.sssp(3, policy=pol)
    r2 = proc.sssp(5, policy=pol)
    info = proc.cache_info()
    assert info["autotune_calls"] == 1 and info["tunings"] == 1
    # tuning must not change results vs the untuned fused path
    r0 = proc.sssp(3, policy=api.ExecutionPolicy(mode="sync"))
    np.testing.assert_array_equal(r0.values, r1.values)
    assert r2.stats.converged


def test_tunings_survive_plan_store_restart(graph, tmp_path):
    spec = KernelSpec(impl="pallas", autotune=True)
    pol = api.ExecutionPolicy(mode="sync", kernel=spec, degrade=False)

    svc = api.GraphService(cache_dir=str(tmp_path))
    proc = svc.register("g", graph, b=16, num_clusters=16)
    proc.sssp(3, policy=pol)
    assert proc.cache_info()["autotune_calls"] == 1
    assert svc.store.stats()["tunings"] == 1

    # cold process, same cache_dir: tuning record comes off disk, the
    # calibration sweep is NOT re-run
    svc2 = api.GraphService(cache_dir=str(tmp_path))
    assert svc2.store.stats()["tunings"] == 1
    proc2 = svc2.register("g", graph, b=16, num_clusters=16)
    r = proc2.sssp(3, policy=pol)
    assert proc2.cache_info()["autotune_calls"] == 0
    assert r.stats.converged

    key = proc2.plan_key("min_plus")
    tkey = dataclasses.replace(key, kernel=spec)
    rec = svc2.store.get_tuning(graph.fingerprint(), tkey)
    assert rec is not None and rec["block_size"] >= 1
