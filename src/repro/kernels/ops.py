"""Public jit'd kernel entry points behind one ``select_kernel`` registry.

Pallas-Mosaic lowers only on TPU, so:
  * default path (``KernelSpec(impl="ref")``) is the pure-jnp oracle,
    which XLA fuses — this is also what the multi-pod dry-run lowers
    (Pallas calls cannot be SPMD-partitioned across a 512-device host
    mesh);
  * ``impl="pallas"`` runs the kernel (interpret=True off-TPU, compiled
    on TPU) — tests sweep it against the reference.

Engines no longer string-match ``impl`` inline: they resolve a callable
once per trace via ``select_kernel(op, spec)``, where ``spec`` is a
``KernelSpec`` (kernels/spec.py).  Every registered builder receives the
resolved platform, so the interpret-mode fallback off-TPU is decided in
exactly one place (``use_interpret``) for the graph kernels AND
attention.

Registered call signatures (one contract per (op, fused) pair):

  ("bsr_spmv", fused=False)  fn(vals, cols, nnz, x, semiring=...)
                             -> y (R, B)
  ("bsr_spmv_wave", fused=False)
                             fn(vals, cols, nnz, x, semiring=...)
                             -> y (R, Q, B), x (C, Q, B): a wave of Q
                             vectors, queries inside each row-block
  ("bsr_spmv", fused=True)   fn(vals, cols, nnz, x, xg, valid, act_rows,
                                damping, tol, inv_n, semiring=...,
                                apply_kind=...)
                             -> (x_new, changed, improved_any)
  ("attention", fused=False) fn(q, k, v, causal, window, scale, bq, bk)
                             -> o   (kv heads already GQA-repeated)

The legacy ``bsr_spmv(..., impl=...)`` / ``attention(..., impl=...)``
wrappers below keep the historical signatures and route through the same
registry.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as _ref
from .. import resilience
from .bsr_spmv import bsr_spmv as _bsr_spmv_pallas
from .bsr_spmv import bsr_spmv_fused as _bsr_spmv_fused
from .flash_attention import flash_attention as _flash_pallas
from .spec import DEFAULT_BLOCK_SIZE, KernelSpec, as_kernel_spec


# ---------------------------------------------------------------------------
# platform guard — the one place that decides interpret-mode fallback
# ---------------------------------------------------------------------------


def resolve_platform(platform: Optional[str] = None) -> str:
    """The platform kernels are built for: ``platform`` when given, else
    JAX's default backend.  A backend that fails to initialize raises —
    it is never reported as the CPU."""
    return platform if platform is not None else jax.default_backend()


def use_interpret(platform: Optional[str] = None) -> bool:
    """Mosaic lowers only on TPU; every other backend (CPU, GPU) runs
    Pallas kernels in interpret mode."""
    return resolve_platform(platform) != "tpu"


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_KERNELS = {}


def register_kernel(op: str, impl: str, fused: bool = False):
    def deco(builder):
        _KERNELS[(op, impl, fused)] = builder
        return builder
    return deco


def select_kernel(op: str, spec=None, platform: Optional[str] = None):
    """Resolve one kernel callable for (op, spec) on a platform.

    ``spec`` may be a ``KernelSpec``, a bare impl string, or None
    (defaults).  Raises ``KeyError`` naming the available registrations
    when the combination has no kernel.

    Fault site ``kernel.select`` fires here (ctx: op/impl/fused) — the
    dispatch/trace-time failure the ``ExecutionPolicy`` degradation
    ladder absorbs by re-running on the ``ref`` kernel.  Note jit
    caching: engines resolve kernels while tracing, so the site is hit
    once per (engine, kernel, shape) compilation, not once per query.
    """
    spec = as_kernel_spec(spec)
    resilience.fire("kernel.select", op=op, impl=spec.impl,
                    fused=spec.fuse_frontier)
    key = (op, spec.impl, spec.fuse_frontier)
    try:
        builder = _KERNELS[key]
    except KeyError:
        raise KeyError(
            f"no kernel registered for op={op!r} impl={spec.impl!r} "
            f"fused={spec.fuse_frontier}; have {sorted(_KERNELS)}"
        ) from None
    return builder(spec, resolve_platform(platform))


def has_kernel(op: str, spec=None) -> bool:
    """Whether (op, spec) has a registered kernel; fires no fault site."""
    spec = as_kernel_spec(spec)
    return (op, spec.impl, spec.fuse_frontier) in _KERNELS


# ---------------------------------------------------------------------------
# bsr_spmv
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("semiring",))
def _bsr_spmv_ref_jit(block_vals, block_cols, x, semiring):
    return _ref.bsr_spmv_ref(block_vals, block_cols, x, semiring)


@register_kernel("bsr_spmv", "ref")
def _build_bsr_spmv_ref(spec: KernelSpec, platform: str):
    del spec, platform  # XLA path: no tiling knobs, any backend

    def fn(block_vals, block_cols, block_nnz, x, semiring="plus_times"):
        del block_nnz  # identity padding makes the bound implicit
        return _bsr_spmv_ref_jit(block_vals, block_cols, x, semiring)

    return fn


@functools.partial(jax.jit, static_argnames=("semiring",))
def _bsr_spmv_wave_ref_jit(block_vals, block_cols, x, semiring):
    return _ref.bsr_spmv_wave_ref(block_vals, block_cols, x, semiring)


@register_kernel("bsr_spmv_wave", "ref")
def _build_bsr_spmv_wave_ref(spec: KernelSpec, platform: str):
    del spec, platform

    def fn(block_vals, block_cols, block_nnz, x, semiring="plus_times"):
        del block_nnz
        return _bsr_spmv_wave_ref_jit(block_vals, block_cols, x, semiring)

    return fn


@register_kernel("bsr_spmv", "pallas")
def _build_bsr_spmv_pallas(spec: KernelSpec, platform: str):
    interpret = use_interpret(platform)
    bk = spec.block_size or DEFAULT_BLOCK_SIZE
    rs = spec.rows_per_step or 1

    def fn(block_vals, block_cols, block_nnz, x, semiring="plus_times"):
        return _bsr_spmv_pallas(block_vals, block_cols, block_nnz, x,
                                semiring=semiring, bk=bk, rows_per_step=rs,
                                interpret=interpret)

    return fn


@register_kernel("bsr_spmv", "pallas", fused=True)
def _build_bsr_spmv_fused(spec: KernelSpec, platform: str):
    interpret = use_interpret(platform)
    bk = spec.block_size or DEFAULT_BLOCK_SIZE

    def fn(block_vals, block_cols, block_nnz, x, xg, valid, act_rows,
           damping, tol, inv_n, semiring="min_plus", apply_kind="relax"):
        return _bsr_spmv_fused(block_vals, block_cols, block_nnz, x, xg,
                               valid, act_rows, damping, tol, inv_n,
                               semiring=semiring, apply_kind=apply_kind,
                               bk=bk, interpret=interpret)

    return fn


def bsr_spmv(block_vals, block_cols, block_nnz, x, semiring="plus_times",
             impl="ref", bk=8):
    """Block-sparse semiring SpMV.  See kernels/bsr_spmv.py for layout.

    Legacy entry point: ``impl``/``bk`` build a ``KernelSpec``; engines
    use ``select_kernel`` directly.
    """
    spec = KernelSpec(impl=impl, block_size=bk if impl == "pallas"
                      else None)
    fn = select_kernel("bsr_spmv", spec)
    return fn(block_vals, block_cols, block_nnz, x, semiring=semiring)


def bsr_spmv_fused(block_vals, block_cols, block_nnz, x, xg, valid,
                   act_rows, damping, tol, inv_n, semiring="min_plus",
                   apply_kind="relax", spec: Optional[KernelSpec] = None):
    """Fused frontier-masked sweep (see bsr_spmv.bsr_spmv_fused)."""
    spec = spec or KernelSpec(impl="pallas", fuse_frontier=True)
    fn = select_kernel("bsr_spmv", spec)
    return fn(block_vals, block_cols, block_nnz, x, xg, valid, act_rows,
              damping, tol, inv_n, semiring=semiring,
              apply_kind=apply_kind)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


CHUNKED_THRESHOLD = 16384


def _attention_ref(q, k, v, causal, window, scale, bq, bk):
    del bq, bk
    if q.shape[2] >= CHUNKED_THRESHOLD:
        return _ref.mha_chunked(q, k, v, causal=causal, window=window,
                                scale=scale)
    return _ref.mha_ref(q, k, v, causal=causal, window=window, scale=scale)


@register_kernel("attention", "ref")
def _build_attention_ref(spec: KernelSpec, platform: str):
    del spec, platform
    return _attention_ref


@register_kernel("attention", "pallas")
def _build_attention_pallas(spec: KernelSpec, platform: str):
    del spec
    interpret = use_interpret(platform)

    def fn(q, k, v, causal, window, scale, bq, bk):
        s, d = q.shape[2], q.shape[3]
        # The flash kernel assumes S == Skv (train/prefill) and
        # d_v == d_qk; decode and MLA shapes use the XLA path.
        if s == k.shape[2] and s > 1 and v.shape[-1] == d:
            return _flash_pallas(q, k, v, causal=causal, window=window,
                                 scale=scale, bq=bq, bk=bk,
                                 interpret=interpret)
        return _attention_ref(q, k, v, causal, window, scale, bq, bk)

    return fn


def attention(q, k, v, causal=True, window=None, scale=None, impl="ref",
              bq=128, bk=128):
    """Multi-head attention; q (B,H,S,D), k/v (B,Hkv,Skv,D).

    Repeats kv heads for GQA, then dispatches through the kernel
    registry — the Pallas path shares the graph kernels' platform guard
    (interpret off-TPU), falling back to the XLA path for shapes the
    flash kernel does not support.  Long sequences take the chunked-exact
    XLA path so the score tensor never materializes at (S, S).
    """
    h = q.shape[1]
    hkv = k.shape[1]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    fn = select_kernel("attention", KernelSpec(impl=impl))
    return fn(q, k, v, causal, window, scale, bq, bk)
