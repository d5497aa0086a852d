"""Self-timed asynchronous distributed engine — the paper's thesis at
the distributed level.

The bulk-synchronous engine (``placement.distributed_sync_run_batched``)
halo-exchanges every shard on every sweep: each sweep is paced by the
global worst case — exactly the global-clock execution the paper argues
against.  This module is the *self-timed* counterpart, one flavor knob
away (``ExecutionPolicy(mode="distributed", dist_flavor="async",
local_sweeps=k)``):

  * **k local sweeps per halo exchange.**  Each shard runs ``k``
    Gauss-Seidel-style relaxation sweeps between collectives: local
    reads are always fresh (a value produced by sweep ``s`` feeds sweep
    ``s+1`` immediately — the software analogue of values flowing
    through NALE FIFOs as soon as they are produced), remote reads come
    from the halo buffered at the start of the round.  For idempotent,
    monotone update rules (``algebra.UPDATE_RULES``: the semiring
    ``relax`` of SSSP/BFS/CC/reachability, k-core peeling, and
    GraphScale's ``pagerank_delta`` accumulation) a stale remote value
    is just a not-yet-improved bound, so the fixpoint is untouched while
    the collective count drops by up to ``k``.

  * **Self-timed shard pacing.**  A shard whose local sweep improved
    nothing idles for the rest of the round instead of re-relaxing an
    already-settled partition — each shard runs at its *local* rate, not
    the straggler's.  ``DistStats.shard_sweeps`` reports the per-shard
    active sweep counts that result.

  * **Overlapped, double-buffered halo exchange.**  The frontier
    all_gather is tiled along the "graph" axis (two buffers per round);
    the first sweep of a round relaxes *interior* clusters — rows whose
    in-tiles all live on this shard — from a purely local view that
    depends on neither tile, so XLA's latency-hiding scheduler is free
    to keep the boundary tiles in flight underneath the interior
    compute.  Boundary rows then combine the landed halo with the
    already-freshened interior values.

  * **Cheap convergence voting.**  The first sweep of every round is a
    complete relaxation pass against the round-start global state, so
    "no improvement anywhere" (one ``psum``-ed flag per query) is an
    exact global-fixpoint test: if interior relaxation improved nothing
    the local state is unchanged, hence a quiet boundary pass certifies
    the true bulk-synchronous convergence condition.  Per-query freezing
    matches the sync engine, so converged states are **bit-identical**
    to the bulk-synchronous path on every mesh factorization for the
    *exact* rules (min-plus path sums are associated tail-first in both
    engines; the fixpoint is a min over the same float multiset) and
    tolerance-bounded for accumulation rules like ``pagerank_delta``,
    whose float-add grouping legitimately differs across schedules.

PIUMA and GraphScale (PAPERS.md) center on the same compute /
communication overlap; here it is the difference between charging one
collective per sweep and one per ``k`` sweeps.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .engine import Prepared
from .. import algebra, resilience
from .placement import (DistStats, ShardedBatch,  # noqa: F401 (re-export)
                        local_spmv, rows_per_step, shard_batched_inputs)


def distributed_async_run_batched(
        p: Prepared, x0: jnp.ndarray, apply_kind: str = "relax",
        damping: float = 0.85, tol: float = 1e-6, max_sweeps: int = 10_000,
        mesh: Optional[Mesh] = None, query_axis: Optional[int] = None,
        local_sweeps: int = 2) -> Tuple[jnp.ndarray, DistStats]:
    """Batched self-timed distributed engine: ONE shard_map dispatch over
    the 2-D ``("graph", "query")`` mesh, ``local_sweeps`` relaxations per
    halo exchange.

    Same input layout and padding as the bulk-synchronous engine (both
    run on :func:`placement.shard_batched_inputs`); only the sweep /
    exchange schedule differs, so the converged state is bit-identical
    (exact rules) or tolerance-bounded (accumulation rules) while
    ``DistStats.halo_exchanges`` shrinks toward ``sweeps /
    local_sweeps``.

    Eligibility comes from the update-rule registry
    (``algebra.UPDATE_RULES``): the k-local-sweep schedule relies on
    the rule being idempotent and monotone (stale remote values are
    conservative bounds).  Classic PageRank's unconditional damped
    affine sweep is neither — use ``algo="pagerank_delta"`` (the
    GraphScale delta-accumulating form) or the bulk-synchronous flavor.
    """
    k = int(local_sweeps)
    if k < 1:
        raise ValueError(f"local_sweeps must be >= 1, got {local_sweeps}")
    if not algebra.rule(apply_kind).monotone:
        eligible = sorted(n for n, r in algebra.UPDATE_RULES.items()
                          if r.monotone)
        raise ValueError(
            "dist_flavor='async' requires an idempotent monotone update "
            f"rule ({', '.join(repr(e) for e in eligible)}); "
            f"apply_kind={apply_kind!r} is order-sensitive and needs the "
            "bulk-synchronous distributed engine (for PageRank, "
            "algo='pagerank_delta' is the flavor-eligible form)")
    sb = shard_batched_inputs(p, x0, mesh=mesh, query_axis=query_axis)
    Q, d_g, d_q = sb.q, sb.d_g, sb.d_q
    # host-level fault sites (after eligibility validation, so real API
    # misuse still surfaces as ValueError, never as an injected fault):
    # a straggling shard (delay) and a failed exchange round (raise)
    resilience.fire("dist.straggler", flavor="async", batched=True,
                    shards=d_g)
    resilience.fire("dist.dispatch", flavor="async", batched=True,
                    shards=d_g)
    rl = sb.r_pad // d_g            # local rows per "graph" shard
    step = rows_per_step(rl, p.gb)
    ring = algebra.get(p.semiring)
    inv_n = jnp.float32(1.0 / max(p.n, 1))
    damping = jnp.float32(damping)
    tol = jnp.float32(tol)
    max_rounds = -(-int(max_sweeps) // k)

    @functools.partial(
        jax.shard_map, mesh=sb.mesh,
        in_specs=(P("graph"), P("graph"), P("graph"), P("graph"),
                  P("query", "graph"), P("query")),
        out_specs=(P("query", "graph"), P("query"), P("query"), P(),
                   P("graph")),
        check_vma=False)
    def run(vals_l, cols_l, nnz_l, valid_l, x_l, qlive_l):
        row0 = jax.lax.axis_index("graph") * rl
        valid_b = valid_l[None]
        lane = jnp.arange(cols_l.shape[1])[None, :]
        live_tile = lane < nnz_l[:, None]
        local_col = (cols_l >= row0) & (cols_l < row0 + rl)
        # interior rows: every live in-tile reads this shard's rows —
        # relaxable before any halo byte lands
        interior = ~jnp.any(live_tile & ~local_col, axis=1)
        # local-coordinate column map for the interior (halo-free) view;
        # boundary rows read garbage through the clip and are masked out
        cols_rel = jnp.clip(cols_l - row0, 0, max(rl - 1, 0))

        def spmv(cols, xg):
            # the wave kernel takes the queries inside each row-block
            return jnp.swapaxes(local_spmv(
                vals_l, cols, nnz_l, jnp.swapaxes(xg, 0, 1), p.semiring,
                step), 0, 1)

        def gather_halo(x):
            # tiled all_gather along "graph": two buffers per round so
            # boundary tiles stream while interior clusters relax
            tiles = [x] if rl < 2 else [x[:, : rl // 2], x[:, rl // 2:]]
            got = [jax.lax.all_gather(t, "graph", axis=0, tiled=False)
                   for t in tiles]
            h = got[0] if len(got) == 1 else jnp.concatenate(got, axis=2)
            return jnp.transpose(h, (1, 0, 2, 3)).reshape(
                x.shape[0], d_g * rl, x.shape[2])

        def overlay(halo, x):
            # buffered remote values + freshest local values
            return jax.lax.dynamic_update_slice(halo, x, (0, row0, 0))

        def relax(cols, xg, x):
            y = spmv(cols, xg)
            return algebra.apply_rule(apply_kind, ring, y, x, valid_b,
                                      damping, inv_n, tol)

        def cond(st):
            i, x, done_q, lsw, sls, all_done = st
            return (~all_done) & (i < max_rounds)

        def body(st):
            i, x, done_q, lsw, sls, _ = st
            live = ~done_q
            # issue the round's halo exchange (boundary tiles in flight)
            halo = gather_halo(x)
            # sweep 0a — interior clusters, purely local view: no data
            # dependency on the gather above, so compute overlaps it
            x_i, imp_i = relax(cols_rel, x, x)
            upd_i = live[:, None, None] & interior[None, :, None]
            x = jnp.where(upd_i, x_i, x)
            # sweep 0b — boundary clusters: landed halo overlaid with
            # the freshly relaxed interior values (Gauss-Seidel order)
            x_b, imp_b = relax(cols_l, overlay(halo, x), x)
            upd_b = live[:, None, None] & ~interior[None, :, None]
            x = jnp.where(upd_b, x_b, x)
            imp0 = (imp_i & upd_i) | (imp_b & upd_b)
            imp0_q = jnp.any(imp0, axis=(1, 2))
            # sweep 0 is exact w.r.t. the round-start global state, so
            # this psum is the same convergence vote the BSP engine takes
            imp0_g = jax.lax.psum(
                imp0_q.astype(jnp.int32), "graph") > 0
            lsw = lsw + live.astype(jnp.int32)
            sls = sls + jnp.sum(live.astype(jnp.int32))
            # sweeps 1..k-1 — self-timed: each shard re-relaxes against
            # the buffered halo only while ITS local work keeps landing;
            # a settled shard idles until the next exchange
            active = live & imp0_g
            still = imp0_q
            for _ in range(k - 1):
                go = active & still
                x_n, imp = relax(cols_l, overlay(halo, x), x)
                x = jnp.where(go[:, None, None], x_n, x)
                still = jnp.any(imp, axis=(1, 2)) & go
                lsw = lsw + go.astype(jnp.int32)
                sls = sls + jnp.sum(go.astype(jnp.int32))
            done_q = done_q | ~imp0_g
            open_n = jax.lax.psum(jnp.sum(~done_q), "query")
            return i + 1, x, done_q, lsw, sls, open_n == 0

        done0 = ~qlive_l
        st = (jnp.int32(0), x_l, done0,
              jnp.zeros(x_l.shape[0], jnp.int32), jnp.int32(0),
              jnp.array(False))
        i, x, done_q, lsw, sls, _ = jax.lax.while_loop(cond, body, st)
        # per-query sweeps are the straggler shard's; per-shard totals
        # sum the query axis (both replicated along the reduced axis)
        return (x, jax.lax.pmax(lsw, "graph"), done_q, i[None],
                jax.lax.psum(sls, "query")[None])

    x, sweeps_q, done_q, exch, shard_sweeps = run(
        sb.vals, sb.cols, sb.nnz, sb.valid, jnp.asarray(sb.x0),
        jnp.asarray(sb.qlive))
    sweeps_q = np.asarray(sweeps_q)[:Q]
    stats = DistStats(
        sweeps=int(sweeps_q.max(initial=0)),
        converged=bool(np.all(np.asarray(done_q)[:Q])),
        halo_bytes_per_sweep=sb.halo_bytes_per_exchange(p.b),
        cut_fraction=p.clustering.cut_fraction,
        mesh_shape=(d_g, d_q), query_sweeps=sweeps_q,
        halo_exchanges=int(exch[0]), local_sweeps=k,
        shard_sweeps=np.asarray(shard_sweeps), **sb.placement())
    return x[:Q, : p.r_pad], stats


def distributed_async_run(
        p: Prepared, x0: jnp.ndarray, apply_kind: str = "relax",
        damping: float = 0.85, tol: float = 1e-6, max_sweeps: int = 10_000,
        mesh: Optional[Mesh] = None,
        local_sweeps: int = 2) -> Tuple[jnp.ndarray, DistStats]:
    """Single-source self-timed distributed run: the batched engine with
    a query axis of one (``query_axis=1`` keeps the whole device grid on
    "graph", matching ``distributed_sync_run``'s 1-D layout)."""
    x, stats = distributed_async_run_batched(
        p, jnp.asarray(x0)[None], apply_kind=apply_kind, damping=damping,
        tol=tol, max_sweeps=max_sweeps, mesh=mesh, query_axis=1,
        local_sweeps=local_sweeps)
    return x[0], stats
