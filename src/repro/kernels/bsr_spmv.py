"""Block-sparse semiring SpMV Pallas kernel — the NALE array on TPU.

Paper mapping.  The NALE is a MAC-plus-comparator engine fed by FIFOs; a
NALE in *cluster mode* executes a whole node cluster.  After the clustering
pass densifies edges into B×B tiles (see ``core/cluster.py``), one tile is
exactly one cluster-mode NALE work item: a dense semiring MAC between a
tile of edges and a block of source-node values.  The array of NALEs
becomes the VPU, VMEM plays the NALE-local FIFO store, and the
*self-timed* property — work driven by actual data, not worst case — is
realized by bounding each row-block's tiles with its true tile count
(``block_nnz``): chunks past it are neither fetched nor combined, so
empty FIFO slots cost nothing.

Layout (ELL-of-tiles, destination-major; see ``core.graph.BsrGraph``):
  block_vals : (R, B, K*B)  tile values, padded with the ⊕-identity;
               [r, i, k*B+j] is source j of tile k into row i
  block_cols : (R, K) int32 col-block index per tile
  block_nnz  : (R,)   int32 true tile count per row-block
  x          : (C, B)       input node values (block layout)
  y          : (R, B)       output

Every blocked operand fits the TPU's (8, 128) f32 register tile: a grid
step takes a group of 8 row-blocks (one sublane tile) and a chunk of
tiles whose sources fill whole 128-lane columns.  The gather
``x[block_cols]`` is one XLA op in the wrapper, producing the lane-dense
(R, K*B) source operand, so ``x`` never has to fit in VMEM.  The
per-step tile bound is scalar-prefetched into SMEM: it steers the
``pl.when`` skip and clamps the chunk index of the block maps onto the
last live chunk, so dead chunks are not even fetched.  Rows whose own
count ends inside a chunk mask the rest of it to the ⊕-identity.

What ⊕, ⊗, the ⊕-identity and each update rule compute is read from
``repro.algebra``, so any registered ring or rule runs here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import algebra

SUBLANES = 8   # row-blocks per grid-step group: one f32 sublane tile
LANES = 128


def lane_tiles(b: int) -> int:
    """Fewest tiles of edge ``b`` whose sources fill whole 128-lane
    columns; plans pad their tile slots to a multiple of it."""
    return LANES // math.gcd(b, LANES)


def _chunk_tiles(bk: int, b: int, k: int) -> int:
    """Tiles per grid chunk: ``bk`` rounded up to whole 128-lane
    columns, or all ``k`` tiles when that is no more.  ``bk`` is a
    tiling knob, so rounding changes no value."""
    m = lane_tiles(b)
    c = -(-bk // m) * m
    return k if c >= k else c


def _combine(ring: algebra.Semiring, b: int, base, vals, xs, nnz):
    """One chunk of NALE MACs for a row group: (G, B, C) tile values ⊗
    (G, C) gathered sources, ⊕-reduced over the chunk's lanes -> (G, B).
    Lanes of tiles at or past a row's ``nnz`` (G, 1) — padding, or the
    ragged last chunk read past K — are masked to the ⊕-identity."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, xs.shape[1]), 1)
    live = (lane < (nnz - base) * b)[:, None, :]
    w = jnp.where(live, vals, ring.zero)
    return ring.reduce(ring.mul(w, xs[:, None, :]), axis=2)


def _last_chunk(n, ct: int):
    """Index of the last chunk holding a live tile (0 when none)."""
    return jnp.maximum((n + ct - 1) // ct - 1, 0)


def _operands(block_vals, block_cols, block_nnz, x, rows: int, bk: int):
    """Shared wrapper prologue: chunk geometry plus the small operands
    padded to whole row groups and chunks.  ``block_vals`` stays as it
    is — the ragged edge blocks read past it are masked by ``nnz``."""
    r, b, kb = block_vals.shape
    k = kb // b
    ct = _chunk_tiles(bk, b, k)
    nk = pl.cdiv(k, ct)
    rp = pl.cdiv(r, rows) * rows
    cols = jnp.pad(block_cols, ((0, rp - r), (0, nk * ct - k)))
    xs = x.astype(jnp.float32)[cols].reshape(rp, nk * ct * b)
    nnz = jnp.pad(block_nnz.astype(jnp.int32), (0, rp - r))
    return b, ct, nk, rp, xs, nnz


def _bsr_spmv_kernel(snnz_ref, vals_ref, xs_ref, nnz_ref, y_ref, *,
                     semiring: str, b: int, ct: int):
    ring = algebra.get(semiring)
    i, kc = pl.program_id(0), pl.program_id(1)

    @pl.when(kc == 0)
    def _():
        y_ref[...] = jnp.full(y_ref.shape, ring.zero, jnp.float32)

    base = kc * ct

    @pl.when(snnz_ref[i] > base)   # self-timed bound of the whole step
    def _():
        part = _combine(ring, b, base, vals_ref[...], xs_ref[...],
                        nnz_ref[...])
        y_ref[...] = ring.add(y_ref[...], part)


@functools.partial(jax.jit, static_argnames=(
    "semiring", "bk", "rows_per_step", "interpret"))
def bsr_spmv(block_vals: jnp.ndarray, block_cols: jnp.ndarray,
             block_nnz: jnp.ndarray, x: jnp.ndarray,
             semiring: str = "plus_times", bk: int = 8,
             rows_per_step: int = 1,
             interpret: bool = True) -> jnp.ndarray:
    """Pallas block-sparse semiring SpMV.  See module docstring for layout.

    ``rows_per_step`` coarsens the grid: each step stages (and relaxes)
    that many 8-row-block groups, trading grid-step overhead for VMEM
    residency.  ``bk`` is rounded by :func:`_chunk_tiles`.
    """
    r = block_vals.shape[0]
    rows = SUBLANES * max(int(rows_per_step), 1)
    b, ct, nk, rp, xs, nnz = _operands(block_vals, block_cols, block_nnz,
                                       x, rows, bk)
    step_nnz = jnp.max(nnz.reshape(rp // rows, rows), axis=1)

    def tiles(i, kc, snnz):
        return (i, 0, jnp.minimum(kc, _last_chunk(snnz[i], ct)))

    def srcs(*ix):   # the tile block's row group and chunk
        g, _, c = tiles(*ix)
        return g, c

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rp // rows, nk),
        in_specs=[
            pl.BlockSpec((rows, b, ct * b), tiles),
            pl.BlockSpec((rows, ct * b), srcs),
            pl.BlockSpec((rows, 1), lambda i, kc, snnz: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, b), lambda i, kc, snnz: (i, 0)))
    y = pl.pallas_call(
        functools.partial(_bsr_spmv_kernel, semiring=semiring, b=b, ct=ct),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, b), jnp.float32),
        interpret=interpret,
    )(step_nnz, block_vals.astype(jnp.float32), xs, nnz[:, None])
    return y[:r]


# ---------------------------------------------------------------------------
# fused relax + frontier-select + convergence-reduce with active-tile skip
# ---------------------------------------------------------------------------
#
# One kernel per sweep instead of SpMV + separate XLA apply/mask/reduce
# ops.  Active-tile skipping: the caller passes the active row-block mask
# (rows with at least one live tile reading a changed source block); the
# wrapper compacts the 8-row groups holding an active row into an index
# list prefetched as scalars, and the grid walks ONLY those groups — the
# paper's self-timed "empty FIFO slots cost nothing" at row-group
# granularity.  Grid steps beyond the active count are clamped onto the
# last active group's last block (same block index ⇒ Mosaic re-fetches
# nothing) and fully predicated off with ``pl.when``.
#
# In-place frontier semantics: the output x aliases a *copy* of the input
# row values, so groups absent from the active list pass through
# untouched, and inside a visited group the apply mask is ``valid & act``
# — inactive rows keep their old values and report no change.  The
# kernel reads old values from the separate, unaliased gathered operand —
# exact Jacobi, bit-identical to the unfused path.  Flags travel as
# int32, and every visited group writes all of its outputs, so no output
# block is read before it is written.


def _fused_kernel(na_ref, al_ref, gnnz_ref, par_ref, vals_ref, xs_ref,
                  nnz_ref, xg_ref, vg_ref, xa_ref, ch0_ref,
                  xo_ref, cho_ref, conv_ref, *,
                  semiring: str, apply_kind: str, b: int, ct: int, nk: int):
    ring = algebra.get(semiring)
    i, kc = pl.program_id(0), pl.program_id(1)
    del xa_ref, ch0_ref  # aliased output bases; never read in-kernel
    na = na_ref[0]

    @pl.when((i == 0) & (kc == 0))
    def _():
        conv_ref[...] = jnp.zeros(conv_ref.shape, jnp.int32)

    @pl.when(na == 0)
    def _():
        # nothing active: every step sits on one group, which must leave
        # the kernel exactly as it came in
        xo_ref[...] = xg_ref[...]
        cho_ref[...] = jnp.zeros(cho_ref.shape, jnp.int32)

    live_step = i < na

    # accumulate the ⊕-reduction in the aliased x-out block; the old row
    # values stay readable in the unaliased xg operand until the apply
    @pl.when(live_step & (kc == 0))
    def _():
        xo_ref[...] = jnp.full(xo_ref.shape, ring.zero, jnp.float32)

    base = kc * ct

    @pl.when(live_step & (gnnz_ref[al_ref[i]] > base))
    def _():
        part = _combine(ring, b, base, vals_ref[...], xs_ref[...],
                        nnz_ref[...])
        xo_ref[...] = ring.add(xo_ref[...], part)

    @pl.when(live_step & (kc == nk - 1))
    def _():
        x_new, imp = algebra.apply_rule(apply_kind, ring, xo_ref[...],
                                        xg_ref[...], vg_ref[...] != 0,
                                        par_ref[0], par_ref[2], par_ref[1])
        xo_ref[...] = x_new
        ch = jnp.max(imp.astype(jnp.int32), axis=1, keepdims=True)
        cho_ref[...] = ch
        conv_ref[...] = jnp.maximum(conv_ref[...],
                                    jnp.max(ch, axis=0, keepdims=True))


@functools.partial(jax.jit, static_argnames=(
    "semiring", "apply_kind", "bk", "interpret"))
def bsr_spmv_fused(block_vals: jnp.ndarray, block_cols: jnp.ndarray,
                   block_nnz: jnp.ndarray, x: jnp.ndarray,
                   xg: jnp.ndarray, valid: jnp.ndarray,
                   act_rows: jnp.ndarray, damping, tol, inv_n,
                   semiring: str = "min_plus", apply_kind: str = "relax",
                   bk: int = 8, interpret: bool = True):
    """One fused frontier-masked sweep over the active row-blocks.

    Args:
      block_vals/block_cols/block_nnz: (R, B, K*B)/(R, K)/(R,) BSR rows.
      x: (C, B) full source-node values (read-only, previous sweep).
      xg: (R, B) current values of THESE rows (``x`` itself for the
        whole-graph sync engine; the group slice for the async engine).
      valid: (R, B) bool — real (non-padding) vertices.
      act_rows: (R,) bool — rows to relax this sweep (the frontier rule:
        any live tile reads a changed source block).
      damping/tol/inv_n: apply-rule scalars (PageRank).
    Returns:
      x_new (R, B) — relaxed active rows, other rows passed through;
      changed (R,) bool — rows the apply rule improved (next frontier);
      improved_any () bool — fused convergence flag (``changed.any()``).
    """
    r = block_vals.shape[0]
    g = SUBLANES
    b, ct, nk, rp, xs, nnz = _operands(block_vals, block_cols, block_nnz,
                                       x, g, bk)
    ng = rp // g
    act = jnp.pad(act_rows.astype(bool), (0, rp - r))
    pad_rows = ((0, rp - r), (0, 0))
    xg = jnp.pad(xg.astype(jnp.float32), pad_rows)
    vg = jnp.pad(valid.astype(bool), pad_rows) & act[:, None]

    # compact active group list: active groups first (stable ⇒
    # deterministic), tail steps clamped onto the last active group and
    # predicated off; a group's tile bound counts its active rows only
    act_g = jnp.any(act.reshape(ng, g), axis=1)
    group_nnz = jnp.max(jnp.where(act, nnz, 0).reshape(ng, g), axis=1)
    order = jnp.argsort(~act_g, stable=True).astype(jnp.int32)
    na = jnp.sum(act_g).astype(jnp.int32)
    idx = jnp.minimum(jnp.arange(ng, dtype=jnp.int32),
                      jnp.maximum(na - 1, 0))
    active_list = order[idx]
    params = jnp.stack([jnp.float32(damping), jnp.float32(tol),
                        jnp.float32(inv_n)])

    def tiles(i, kc, na, al, gnnz):
        # tail steps (i >= na) repeat the last live step's block
        kc = jnp.where(i < na[0], kc, nk - 1)
        return (al[i], 0, jnp.minimum(kc, _last_chunk(gnnz[al[i]], ct)))

    def srcs(*ix):   # the tile block's row group and chunk
        g, _, c = tiles(*ix)
        return g, c

    def rows(i, kc, na, al, gnnz):
        return (al[i], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ng, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),        # params
            pl.BlockSpec((g, b, ct * b), tiles),          # vals
            pl.BlockSpec((g, ct * b), srcs),              # gathered x
            pl.BlockSpec((g, 1), rows),                   # nnz
            pl.BlockSpec((g, b), rows),                   # xg
            pl.BlockSpec((g, b), rows),                   # valid & act
            pl.BlockSpec((g, b), rows),                   # x alias
            pl.BlockSpec((g, 1), rows),                   # ch alias
        ],
        out_specs=[
            pl.BlockSpec((g, b), rows),                   # x_new
            pl.BlockSpec((g, 1), rows),                   # changed
            pl.BlockSpec((1, 1), lambda i, kc, na, al, gnnz: (0, 0)),
        ])
    x_new, changed, conv = pl.pallas_call(
        functools.partial(_fused_kernel, semiring=semiring,
                          apply_kind=apply_kind, b=b, ct=ct, nk=nk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rp, b), jnp.float32),
                   jax.ShapeDtypeStruct((rp, 1), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        # operand indices COUNT the scalar-prefetch operands (na, al,
        # gnnz): 9 = the xg copy aliased onto x_new, 10 = the zero
        # changed flags
        input_output_aliases={9: 0, 10: 1},
        interpret=interpret,
    )(jnp.reshape(na, (1,)), active_list, group_nnz, params,
      block_vals.astype(jnp.float32), xs, nnz[:, None], xg,
      vg.astype(jnp.int32), xg, jnp.zeros((rp, 1), jnp.int32))
    return x_new[:r], changed[:r, 0] != 0, conv[0, 0] != 0
