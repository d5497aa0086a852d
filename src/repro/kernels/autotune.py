"""Measured tiling autotuner for the Pallas SpMV kernels.

The paper's NALE array is self-timed — throughput follows the data, not
a static worst-case schedule.  The software analogue of picking FIFO
depths is picking the Pallas tiling knobs (``block_size`` bk,
``rows_per_step``), and the honest way to pick them is to *measure* a
small calibration sweep on the actual plan's tile structure, not to
trust a model: interpret mode (off-TPU), VMEM residency, and grid
overhead are all invisible to an analytical roofline.

``autotune_spmv(p, spec)`` sweeps the free knobs of ``spec`` over the
plan ``p`` (duck-typed: any object with ``vals/cols/nnz/valid/k_max/
r_pad/b/semiring`` — ``core.engine.Prepared`` qualifies, but this module
must not import ``repro.core``), timing one representative sweep per
candidate on a seeded ~25%-dense calibration frontier.  The winner is
deterministic for a given seed and measurement function: ties break
toward the smallest (block_size, rows_per_step).

Each tuning record names the device it was measured on and carries a
roofline cross-check from ``launch.roofline.kernel_roofline`` on that
device's published peaks: ``roofline_agrees`` is True when the measured
time is at or above the modeled lower bound (a measurement *below* the
roofline means the harness mis-timed — flagged, never used to override
the measurement).  A device without published peaks (the CPU of the
interpret-mode tests) gets no model: ``modeled_s`` and
``roofline_agrees`` are None.

The caller (``core/api.GraphProcessor``) caches the returned record in
the PlanStore keyed by ``(fingerprint, PlanKey(kernel=spec))`` so warm
restarts reuse tunings instead of re-measuring.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import algebra
from ..launch.roofline import PEAKS, kernel_roofline
from . import ops
from .spec import KernelSpec

CALIBRATION_DENSITY = 0.25
BK_CANDIDATES = (2, 4, 8, 16)
RS_CANDIDATES = (1, 2, 4)


def default_measure(call: Callable[[], object], config: KernelSpec,
                    iters: int) -> float:
    """Wall-clock a candidate: one warm-up call (compile), then the best
    of ``iters`` synchronized runs.  Injectable for tests."""
    del config
    jax.block_until_ready(call())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        best = min(best, time.perf_counter() - t0)
    return best


def candidate_specs(spec: KernelSpec, k_max: int):
    """Concrete candidate grid for ``spec``'s free knobs.  Pinned fields
    stay pinned; bk candidates never exceed the padded tile-chunk axis."""
    if spec.block_size is not None:
        bks = [spec.block_size]
    else:
        cap = max(int(k_max), 2)
        bks = [c for c in BK_CANDIDATES if c <= cap] or [2]
    if spec.fuse_frontier:
        rss = [1]
    elif spec.rows_per_step is not None:
        rss = [spec.rows_per_step]
    else:
        rss = list(RS_CANDIDATES)
    return [
        KernelSpec(impl=spec.impl, block_size=bk, rows_per_step=rs,
                   fuse_frontier=spec.fuse_frontier)
        for bk in bks for rs in rss
    ]


def _calibration_inputs(p, seed: int, apply_kind: str):
    """Seeded synthetic state on the plan's real tile structure."""
    rng = np.random.default_rng(seed)
    r_pad, b = int(p.r_pad), int(p.b)
    zero = algebra.get(p.semiring).zero
    x = jnp.asarray(np.where(
        rng.random((r_pad, b)) < 0.5, rng.random((r_pad, b)), zero),
        jnp.float32)
    act = jnp.asarray(rng.random(r_pad) < CALIBRATION_DENSITY)
    damping = jnp.float32(0.85)
    tol = jnp.float32(1e-6)
    inv_n = jnp.float32(1.0 / max(int(getattr(p, "n", r_pad * b)), 1))
    return x, act, damping, tol, inv_n


def _modeled_seconds(p, act, fused: bool, device_kind: str) -> dict:
    """Roofline lower bound for one calibration sweep: bytes follow the
    tiles actually walked (active rows for the fused kernel, all rows
    unfused) plus the resident x image; flops are semiring MACs."""
    b = int(p.b)
    nnz = np.asarray(p.nnz, dtype=np.float64)
    if fused:
        tiles = float(nnz[np.asarray(act)].sum())
    else:
        tiles = float(nnz.sum())
    tile_bytes = b * b * 4 + 4 + 4          # vals + col index + nnz amort
    hbm = tiles * tile_bytes + float(p.r_pad) * b * 4 * 3  # x in, x/y out
    flops = tiles * 2.0 * b * b
    return kernel_roofline(flops, hbm, device_kind)


def autotune_spmv(p, spec: KernelSpec, seed: int = 0, iters: int = 3,
                  measure: Optional[Callable] = None,
                  apply_kind: str = "relax",
                  platform: Optional[str] = None) -> dict:
    """Measure ``spec``'s free tiling knobs on plan ``p``; return a
    JSON-serializable tuning record (see module docstring)."""
    if spec.impl != "pallas":
        raise ValueError(f"autotune targets the Pallas kernel, not "
                         f"impl={spec.impl!r}")
    measure = measure or default_measure
    x, act, damping, tol, inv_n = _calibration_inputs(p, seed, apply_kind)
    vals, cols, nnz, valid = p.vals, p.cols, p.nnz, p.valid

    results = []
    for cand in candidate_specs(spec, p.k_max):
        fn = ops.select_kernel("bsr_spmv", cand, platform=platform)
        if cand.fuse_frontier:
            def call(fn=fn):
                return fn(vals, cols, nnz, x, x, valid, act, damping,
                          tol, inv_n, semiring=p.semiring,
                          apply_kind=apply_kind)
        else:
            def call(fn=fn):
                return fn(vals, cols, nnz, x, semiring=p.semiring)
        t = float(measure(call, cand, iters))
        results.append((t, cand))

    t_best, best = min(
        results, key=lambda r: (r[0], r[1].block_size, r[1].rows_per_step))
    kind = jax.devices()[0].device_kind
    modeled = (_modeled_seconds(p, act, spec.fuse_frontier,
                                kind)["modeled_s"]
               if kind in PEAKS else None)
    return {
        "block_size": int(best.block_size),
        "rows_per_step": int(best.rows_per_step),
        "device_kind": kind,
        "measured_s": t_best,
        "modeled_s": modeled,
        "roofline_agrees": (None if modeled is None
                            else bool(t_best >= modeled)),
        "seed": int(seed),
        "candidates": [
            {"block_size": int(c.block_size),
             "rows_per_step": int(c.rows_per_step), "measured_s": t}
            for t, c in results
        ],
    }
