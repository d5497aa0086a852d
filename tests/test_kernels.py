"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py
oracles vs dense numpy ground truth."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import algebra
from repro.core import graph as G
from repro.kernels import ops, ref

# a ring registered as a user would: best reliability, ⊕ = max, ⊗ = ×
MAX_TIMES = "test_kernels_max_times"
if MAX_TIMES not in algebra.SEMIRINGS:
    algebra.register(algebra.Semiring(
        name=MAX_TIMES, add=jnp.maximum, mul=jnp.multiply, zero=0.0,
        one=1.0, improves=lambda new, old: new > old,
        reduce_fn=lambda x, axis=None: jnp.max(x, axis=axis)))

SEMIRINGS = ["plus_times", "min_plus", "max_min", "min_select", "or_and",
             MAX_TIMES]
BOOLEAN = ("max_min", "or_and")   # the {0,1} carrier


def _dense_spmv(a, x, name):
    if name == "plus_times":
        return a @ x
    if name == "min_plus":
        return np.min(a + x[None, :], axis=1)
    if name in BOOLEAN:
        return np.max(np.minimum(a, x[None, :]), axis=1)
    if name == MAX_TIMES:
        return np.max(a * x[None, :], axis=1)
    return np.min(np.where(np.isfinite(a), x[None, :], np.inf), axis=1)


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("n,e,b,bk", [(64, 256, 8, 2), (200, 800, 16, 4),
                                      (120, 900, 32, 8)])
def test_bsr_spmv_sweep(semiring, n, e, b, bk, rng):
    g = G.rmat(n, e, seed=n + e)
    bsr = G.to_bsr(g, b=b, pad_value=float(algebra.get(semiring).zero))
    x = rng.random((bsr.r, bsr.b)).astype(np.float32)
    if semiring in BOOLEAN:
        x = (x > 0.5).astype(np.float32)
    args = (jnp.asarray(bsr.block_vals), jnp.asarray(bsr.block_cols),
            jnp.asarray(bsr.block_nnz), jnp.asarray(x))
    y_ref = ops.bsr_spmv(*args, semiring=semiring, impl="ref")
    y_pal = ops.bsr_spmv(*args, semiring=semiring, impl="pallas", bk=bk)
    dense = _dense_spmv(G.bsr_to_dense(bsr), x.reshape(-1), semiring)
    np.testing.assert_allclose(np.asarray(y_ref).reshape(-1), dense,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_pal).reshape(-1), dense,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 64)])
@pytest.mark.parametrize("b,h,kv,s,d", [(2, 4, 2, 256, 64),
                                        (1, 2, 1, 128, 32)])
def test_flash_attention_sweep(dtype, causal, window, b, h, kv, s, d, rng):
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, kv, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, kv, s, d)), dtype)
    o_ref = ops.attention(q, k, v, causal=causal, window=window,
                          impl="ref")
    o_pal = ops.attention(q, k, v, causal=causal, window=window,
                          impl="pallas", bq=64, bk=64)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_ref, np.float32),
                               np.asarray(o_pal, np.float32),
                               rtol=tol, atol=tol)


def test_chunked_attention_matches_exact(rng):
    b, h, s, d = 1, 2, 2048, 32
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    exact = ref.mha_ref(q, k, v, causal=True)
    chunk = ref.mha_chunked(q, k, v, causal=True, q_chunk=256)
    np.testing.assert_allclose(np.asarray(exact), np.asarray(chunk),
                               rtol=2e-5, atol=2e-5)


def test_bsr_padding_is_noop(rng):
    """Padding tiles hold ⊕-identities: adding empty tiles never changes
    the result (the kernel's 'empty FIFO slot' invariant)."""
    g = G.rmat(50, 200, seed=3)
    for name in SEMIRINGS:
        z = float(algebra.get(name).zero)
        bsr = G.to_bsr(g, b=8, pad_value=z)
        x = rng.random((bsr.r, bsr.b)).astype(np.float32)
        y0 = ops.bsr_spmv(jnp.asarray(bsr.block_vals),
                          jnp.asarray(bsr.block_cols),
                          jnp.asarray(bsr.block_nnz), jnp.asarray(x),
                          semiring=name, impl="ref")
        # append 2 extra all-padding tile slots per row
        pad_v = np.full((bsr.r, 8, 2 * 8), z, np.float32)
        vals = np.concatenate([bsr.block_vals, pad_v], axis=2)
        cols = np.concatenate([bsr.block_cols,
                               np.zeros((bsr.r, 2), np.int32)], axis=1)
        y1 = ops.bsr_spmv(jnp.asarray(vals), jnp.asarray(cols),
                          jnp.asarray(bsr.block_nnz), jnp.asarray(x),
                          semiring=name, impl="ref")
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=1e-6)


@pytest.mark.parametrize("rows_per_step", [2, 4])
def test_rows_per_step_matches_single_row(rows_per_step, rng):
    """Grid coarsening only regroups row-blocks per step — each row's
    accumulation order is untouched, so the result is unchanged."""
    g = G.rmat(100, 500, seed=9)
    for name in SEMIRINGS:
        bsr = G.to_bsr(g, b=8, pad_value=float(algebra.get(name).zero))
        x = rng.random((bsr.r, bsr.b)).astype(np.float32)
        from repro.kernels.spec import KernelSpec
        args = (jnp.asarray(bsr.block_vals), jnp.asarray(bsr.block_cols),
                jnp.asarray(bsr.block_nnz), jnp.asarray(x))
        y1 = ops.bsr_spmv(*args, semiring=name, impl="pallas", bk=4)
        spmv = ops.select_kernel("bsr_spmv", KernelSpec(
            impl="pallas", block_size=4, rows_per_step=rows_per_step))
        yr = spmv(*args, semiring=name)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(yr))


# -- fused relax + frontier-select + convergence-reduce ---------------------

def _fused_oracle(bsr, x, valid, act, semiring, apply_kind="relax",
                  damping=0.85, tol=1e-6, inv_n=1e-2):
    """Unfused reference composition: ref SpMV -> engine apply rule ->
    frontier mask.  Rows outside ``act`` pass through bitwise."""
    y = ops.bsr_spmv(jnp.asarray(bsr.block_vals),
                     jnp.asarray(bsr.block_cols),
                     jnp.asarray(bsr.block_nnz), jnp.asarray(x),
                     semiring=semiring, impl="ref")
    x_new, imp = algebra.apply_rule(
        apply_kind, algebra.get(semiring), y, jnp.asarray(x),
        jnp.asarray(valid), jnp.float32(damping), jnp.float32(inv_n),
        jnp.float32(tol))
    x_exp = np.where(act[:, None], np.asarray(x_new), x)
    ch_exp = act & np.any(np.asarray(imp), axis=1)
    return x_exp, ch_exp


def _fused_call(bsr, x, valid, act, semiring, apply_kind="relax", bk=4,
                vals=None):
    from repro.kernels.bsr_spmv import bsr_spmv_fused
    xj = jnp.asarray(x)
    return bsr_spmv_fused(
        jnp.asarray(vals if vals is not None else bsr.block_vals),
        jnp.asarray(bsr.block_cols), jnp.asarray(bsr.block_nnz),
        xj, xj, jnp.asarray(valid), jnp.asarray(act),
        jnp.float32(0.85), jnp.float32(1e-6), jnp.float32(1e-2),
        semiring=semiring, apply_kind=apply_kind, bk=bk)


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("frontier", ["empty", "sparse", "dense"])
def test_fused_matches_unfused_composition(semiring, frontier, rng):
    """The fused kernel must equal ref-SpMV + engine apply + frontier
    mask: EXACT for the comparison semirings, float-accumulation
    tolerance for plus_times (different reduction grouping)."""
    g = G.rmat(120, 700, seed=11)
    bsr = G.to_bsr(g, b=8, pad_value=float(algebra.get(semiring).zero))
    x = rng.random((bsr.r, bsr.b)).astype(np.float32)
    if semiring in BOOLEAN:
        x = (x > 0.5).astype(np.float32)
    valid = np.ones((bsr.r, bsr.b), bool)
    act = {"empty": np.zeros(bsr.r, bool),
           "sparse": rng.random(bsr.r) < 0.15,
           "dense": np.ones(bsr.r, bool)}[frontier]
    x_exp, ch_exp = _fused_oracle(bsr, x, valid, act, semiring)
    x_new, changed, conv = _fused_call(bsr, x, valid, act, semiring)
    if semiring == "plus_times":
        np.testing.assert_allclose(np.asarray(x_new), x_exp, rtol=2e-6)
    else:
        np.testing.assert_array_equal(np.asarray(x_new), x_exp)
    np.testing.assert_array_equal(np.asarray(changed), ch_exp)
    assert bool(conv) == bool(ch_exp.any())
    if frontier == "empty":
        # all-converged early exit: pure passthrough, nothing changed
        np.testing.assert_array_equal(np.asarray(x_new), x)
        assert not bool(conv)


def test_fused_pagerank_apply(rng):
    g = G.rmat(80, 400, seed=13)
    bsr = G.to_bsr(g, b=8, pad_value=0.0)
    x = rng.random((bsr.r, bsr.b)).astype(np.float32)
    valid = np.ones((bsr.r, bsr.b), bool)
    act = np.ones(bsr.r, bool)
    x_exp, ch_exp = _fused_oracle(bsr, x, valid, act, "plus_times",
                                  apply_kind="pagerank")
    x_new, changed, conv = _fused_call(bsr, x, valid, act, "plus_times",
                                       apply_kind="pagerank")
    np.testing.assert_allclose(np.asarray(x_new), x_exp, rtol=2e-6)
    np.testing.assert_array_equal(np.asarray(changed), ch_exp)


def test_fused_respects_nnz_bound(rng):
    """Garbage tiles beyond block_nnz must not leak into the fused
    result either (same self-timed bound as the unfused kernel)."""
    g = G.rmat(60, 240, seed=4)
    bsr = G.to_bsr(g, b=8, pad_value=np.inf)  # min_plus
    vals = bsr.block_vals.copy()
    lane = np.arange(bsr.k_max * bsr.b)[None, :] // bsr.b
    trash = lane >= bsr.block_nnz[:, None]
    vals[np.broadcast_to(trash[:, None, :], vals.shape)] = -123.0
    x = rng.random((bsr.r, bsr.b)).astype(np.float32)
    valid = np.ones((bsr.r, bsr.b), bool)
    act = np.ones(bsr.r, bool)
    x_exp, ch_exp = _fused_oracle(bsr, x, valid, act, "min_plus")
    x_new, changed, _ = _fused_call(bsr, x, valid, act, "min_plus",
                                    vals=vals)
    np.testing.assert_array_equal(np.asarray(x_new), x_exp)
    np.testing.assert_array_equal(np.asarray(changed), ch_exp)


def test_pallas_respects_nnz_bound(rng):
    """Garbage beyond block_nnz must not affect the Pallas result
    (self-timed execution: only true tiles are combined)."""
    g = G.rmat(60, 240, seed=4)
    bsr = G.to_bsr(g, b=8, pad_value=np.inf)  # min_plus
    vals = bsr.block_vals.copy()
    lane = np.arange(bsr.k_max * bsr.b)[None, :] // bsr.b
    trash = lane >= bsr.block_nnz[:, None]
    vals[np.broadcast_to(trash[:, None, :], vals.shape)] = -123.0
    x = rng.random((bsr.r, bsr.b)).astype(np.float32)
    y_pal = ops.bsr_spmv(jnp.asarray(vals), jnp.asarray(bsr.block_cols),
                         jnp.asarray(bsr.block_nnz), jnp.asarray(x),
                         semiring="min_plus", impl="pallas", bk=4)
    dense = _dense_spmv(G.bsr_to_dense(bsr), x.reshape(-1), "min_plus")
    np.testing.assert_allclose(np.asarray(y_pal).reshape(-1), dense,
                               rtol=1e-5, atol=1e-5)


_ = jax
