"""Cluster → device placement and the distributed graph engine.

Paper mapping: inter-NALE FIFOs become inter-device halo exchange.  Row
groups (clusters) are placed contiguously on the "graph" axis of a 2-D
``("graph", "query")`` mesh by ``cluster.place_clusters``; each sweep a
device gathers the frontier values it needs (here: tiled all_gather —
the collective the roofline charges; the edge-cut from clustering bounds
the useful fraction) and computes its local rows.

The second mesh axis carries concurrent queries: the paper's
task-to-element mapping composes at both levels (PIUMA / GraphScale make
the same point), so multi-source frontiers shard over "query" while the
partitioned graph shards over "graph" — halo exchange stays confined to
"graph" because queries are independent.  ``query=1`` degenerates to the
historical 1-D behavior.

Works on 1 real device (tests), on N fake host devices (subprocess tests,
the CI multi-device lane, dry-run) and unchanged on a real pod slice.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .engine import Prepared
from .. import algebra, obs, resilience
from ..kernels import ops
from ..kernels.spec import KernelSpec

# the distributed engines shard_map the ref wave kernel (Pallas calls
# cannot be SPMD-partitioned); resolved once through the same registry
# the local engines use
_spmv_wave = ops.select_kernel("bsr_spmv_wave", KernelSpec(impl="ref"))


def make_graph_mesh(num_devices: Optional[int] = None,
                    query_axis: int = 1) -> Mesh:
    """2-D ``("graph", "query")`` device mesh.

    ``num_devices`` (default: all) are factored as
    ``graph = num_devices // query_axis``; ``query_axis=1`` is the
    degenerate 1-D layout every pre-existing caller gets.
    """
    n = num_devices or len(jax.devices())
    q = int(query_axis)
    if q < 1:
        raise ValueError(f"query_axis must be >= 1, got {q}")
    if n % q:
        raise ValueError(
            f"query_axis={q} does not divide {n} devices; pick a "
            f"divisor of the device count (see factor_query_axis)")
    # Auto axes: the engines place data through shard_map specs, and
    # their results are indexed on the host like any replicated array
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh((n // q, q), ("graph", "query"),
                         axis_types=(auto, auto))


def factor_query_axis(num_devices: int, num_queries: int) -> int:
    """Auto-factor the device count for a Q-source batch: the largest
    divisor of ``num_devices`` not exceeding ``num_queries``, so both
    mesh axes stay as full as the batch allows (q queries can't feed
    more than q query-shards; leftover devices go to "graph")."""
    q = max(int(num_queries), 1)
    for cand in range(min(q, num_devices), 0, -1):
        if num_devices % cand == 0:
            return cand
    return 1


# -- plan placement: chosen once, when the plan is built ---------------------

#: share of one device's ``bytes_limit`` that a plan's rows on it plus
#: the working set of its widest wave may take.  The quarter left over
#: is for what :func:`device_need` does not count: the compiled sweep's
#: other buffers, each wave's inputs and results, other plans in the
#: store and the allocator's fragmentation.
HEADROOM = 0.75


def device_bytes_limit() -> Optional[int]:
    """The first device's ``bytes_limit``, or None where the backend
    reports none (the CPU)."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def device_need(rows: int, row_bytes: float, group_rows: int,
                query_row_bytes: float, query_bytes: float,
                num_devices: int, max_wave: int, d_g: int) -> float:
    """Bytes one device holds with the plan on a mesh of "graph" extent
    ``d_g``: its share of the plan's ``rows`` row-blocks (``row_bytes``
    each), and for each of the wave's queries it serves, the batched
    sweep's working set.  The sweep steps over the device's rows
    :func:`rows_per_step` (at most ``group_rows``) at a time, so a query
    holds the gathered source blocks of one step's rows
    (``query_row_bytes`` a row) and its frontier (``query_bytes``)."""
    local = -(-rows // d_g)
    queries = -(-max(int(max_wave), 1) // (num_devices // d_g))
    step = rows_per_step(local, group_rows)
    return local * row_bytes + \
        queries * (step * query_row_bytes + query_bytes)


def graph_extent(rows: int, row_bytes: float, group_rows: int,
                 query_row_bytes: float, query_bytes: float,
                 num_devices: int, max_wave: int,
                 bytes_limit: Optional[int]) -> int:
    """The "graph" extent of the mesh a plan of ``rows`` row-blocks is
    placed on over ``num_devices`` devices.

    The smallest divisor of ``num_devices``, and no smaller than
    ``factor_query_axis`` leaves for a wave of ``max_wave`` queries, at
    which :func:`device_need` fits ``HEADROOM`` of ``bytes_limit``
    (None: no limit).  Where none fits, every device takes a share.
    """
    n = int(num_devices)
    budget = HEADROOM * bytes_limit if bytes_limit else float("inf")
    low = n // factor_query_axis(n, max(int(max_wave), 1))
    for d_g in range(low, n + 1):
        if n % d_g:
            continue
        if device_need(rows, row_bytes, group_rows, query_row_bytes,
                       query_bytes, n, max_wave, d_g) <= budget:
            break
    return d_g


def plan_mesh(rows: int, row_bytes: float, group_rows: int,
              query_row_bytes: float, query_bytes: float,
              num_devices: int, max_wave: int) -> Mesh:
    """The mesh a plan is placed on, from what can be observed: the
    device count, the plan's bytes and one device's ``bytes_limit``
    (:func:`graph_extent`); the devices left over form the "query"
    extent."""
    n = int(num_devices)
    d_g = graph_extent(rows, row_bytes, group_rows, query_row_bytes,
                       query_bytes, n, max_wave, device_bytes_limit())
    return make_graph_mesh(n, n // d_g)


def mesh_name(mesh: Mesh) -> str:
    """``"<graph>x<query>"``, as spans record a mesh."""
    shape = dict(mesh.shape)
    return f"{shape['graph']}x{shape.get('query', 1)}"


def _rows_of(a: np.ndarray, rows: int, index: tuple) -> np.ndarray:
    """One shard's rows of a host array padded to ``rows`` rows: zero
    rows past its end."""
    lo, hi, _ = index[0].indices(rows)
    return _pad_rows(a[lo:min(hi, a.shape[0])], hi - lo)


def place_rows(arrays: dict, mesh: Mesh) -> dict:
    """Put plan row arrays on ``mesh``, sharded ``P("graph")``, their rows
    padded with zeros to a multiple of the "graph" extent (a padding row
    has no tiles and no valid vertex).  Each device receives only its own
    rows, copied from the host array one shard at a time, so the host
    stages one shard's transfer, not the whole array's; a device array is
    first fetched to the host.  One ``plan.place`` span covers it."""
    d_g = dict(mesh.shape)["graph"]
    rows = NamedSharding(mesh, P("graph"))
    out = {}
    with obs.span("plan.place", devices=mesh.size, mesh=mesh_name(mesh)):
        for name, a in arrays.items():
            a = np.asarray(a)
            shape = (-(-a.shape[0] // d_g) * d_g,) + a.shape[1:]
            shards = []
            for d, idx in rows.addressable_devices_indices_map(
                    shape).items():
                shards.append(jax.device_put(
                    _rows_of(a, shape[0], idx), d).block_until_ready())
            out[name] = jax.make_array_from_single_device_arrays(
                shape, rows, shards)
    return out


def plan_mesh_of(p: Prepared) -> Optional[Mesh]:
    """The mesh a plan's rows were placed on at build, or None for a plan
    on one device."""
    s = p.vals.sharding
    if isinstance(s, NamedSharding) and "graph" in s.mesh.axis_names:
        return s.mesh
    return None


def plan_rows(p: Prepared, mesh: Mesh) -> Tuple[jax.Array, ...]:
    """``(vals, cols, nnz, valid)`` as a dispatch on ``mesh`` takes them:
    sharded ``P("graph")``, rows padded to a multiple of the "graph"
    extent.  A plan placed so at build is used as it is; any other is
    placed now (:func:`place_rows`)."""
    names = ("vals", "cols", "nnz", "valid")
    d_g = dict(mesh.shape)["graph"]
    r_pad = -(-p.r_pad // d_g) * d_g
    want = NamedSharding(mesh, P("graph"))
    arrays = {f: getattr(p, f) for f in names}
    if not all(isinstance(a, jax.Array) and a.shape[0] == r_pad
               and a.sharding.is_equivalent_to(want, a.ndim)
               for a in arrays.values()):
        arrays = place_rows(arrays, mesh)
    return tuple(arrays[f] for f in names)


@dataclasses.dataclass
class DistStats:
    sweeps: int
    converged: bool
    halo_bytes_per_sweep: float   # all_gather payload per exchange (per device)
    cut_fraction: float
    mesh_shape: Tuple[int, int] = (1, 1)       # (graph, query) extent
    query_sweeps: Optional[np.ndarray] = None  # per-query sweep counts
    # self-timed accounting (PR 7): the bulk-synchronous engines exchange
    # once per sweep, so halo_exchanges == sweeps there; the async flavor
    # (core/async_dist.py) runs local_sweeps relaxations per exchange and
    # reports strictly fewer exchanges on multi-sweep fixpoints.
    halo_exchanges: int = 0
    local_sweeps: int = 1                      # k (1 = bulk-synchronous)
    shard_sweeps: Optional[np.ndarray] = None  # per-"graph"-shard active
    #                                            local sweeps (self-timed
    #                                            rate of each shard)
    plan_devices: int = 1       # devices holding a shard of the plan rows
    plan_shard_rows: int = 0    # plan rows resident on each of them

    @property
    def halo_bytes(self) -> float:
        """The run's all_gather payload per device: every exchange's."""
        return self.halo_bytes_per_sweep * self.halo_exchanges

    def span_attrs(self) -> dict:
        """What a ``run.device`` span records of a distributed run."""
        return dict(mesh="x".join(map(str, self.mesh_shape)),
                    halo_exchanges=int(self.halo_exchanges),
                    halo_bytes=float(self.halo_bytes))


def _pad_rows(arr: np.ndarray, rows: int) -> np.ndarray:
    pad = rows - arr.shape[0]
    if pad <= 0:
        return arr
    widths = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, widths, constant_values=0)


@dataclasses.dataclass
class ShardedBatch:
    """Host-side scaffolding shared by every batched distributed flavor:
    the mesh, the row/query padding, and the padded input arrays a
    ``("graph", "query")`` shard_map dispatch consumes.

    Built by :func:`shard_batched_inputs`; both the bulk-synchronous
    engine (:func:`distributed_sync_run_batched`) and the self-timed
    asynchronous one (``core.async_dist``) run on exactly this layout,
    which is what makes their converged states comparable bit-for-bit.
    """

    mesh: Mesh
    d_g: int                # "graph" extent
    d_q: int                # "query" extent
    r_pad: int              # rows padded to a multiple of d_g
    q_pad: int              # queries padded to a multiple of d_q
    q: int                  # real (un-padded) query count
    vals: jax.Array         # plan rows, placed P("graph") on the mesh
    cols: jax.Array
    nnz: jax.Array
    valid: jax.Array
    x0: np.ndarray          # (q_pad, r_pad, B)
    qlive: np.ndarray       # (q_pad,) — padding queries start converged

    def halo_bytes_per_exchange(self, b: int) -> float:
        """Remote bytes a device gathers in ONE tiled all_gather of the
        frontier (summed over its resident query rows)."""
        return (self.r_pad // self.d_g) * b * 4.0 * (self.d_g - 1) * \
            (self.q_pad // self.d_q)

    def placement(self) -> dict:
        """Where the plan rows live: ``DistStats`` placement fields."""
        return dict(plan_devices=len(self.vals.sharding.device_set),
                    plan_shard_rows=int(
                        self.vals.addressable_shards[0].data.shape[0]))


def shard_batched_inputs(p: Prepared, x0: jnp.ndarray,
                         mesh: Optional[Mesh] = None,
                         query_axis: Optional[int] = None) -> ShardedBatch:
    """Pad a ``Prepared`` image and a stacked ``(Q, r_pad, B)`` frontier
    for a 2-D ``("graph", "query")`` mesh dispatch.

    Rows are padded to a multiple of the "graph" extent (min-semiring
    padding rows hold +inf so they never win a reduction), queries to a
    multiple of the "query" extent (padding queries are marked dead in
    ``qlive`` — converged from sweep 0, zero work).  A plan placed on a
    mesh at build runs on that mesh and its rows as they lie
    (:func:`plan_rows`); otherwise ``query_axis=None`` auto-factors the
    device count against the batch size.  0 is rejected here for every
    flavor (the per-source escape hatch lives in the session API, not
    the engines).
    """
    Q = int(x0.shape[0])
    if query_axis is not None and query_axis < 1:
        # the query_axis=0 per-source escape hatch lives one layer up
        # (GraphProcessor._run_batched) — the engine itself must never
        # silently reinterpret 0 as "auto-factor"
        raise ValueError(
            "batched distributed engines need query_axis=None (auto) "
            f"or >= 1, got {query_axis}; the query_axis=0 per-source "
            "loop is dispatched by the session API, not the engine")
    if mesh is None and query_axis is None:
        mesh = plan_mesh_of(p)
    if mesh is None:
        ndev = len(jax.devices())
        mesh = make_graph_mesh(
            ndev, query_axis or factor_query_axis(ndev, Q))
    shape = dict(mesh.shape)
    d_g = shape["graph"]
    d_q = shape.get("query", 1)

    r_pad = ((p.r_pad + d_g - 1) // d_g) * d_g
    vals, cols, nnz, valid = plan_rows(p, mesh)
    q_pad = ((Q + d_q - 1) // d_q) * d_q
    x0 = np.asarray(x0)
    x0 = np.concatenate(
        [x0, np.zeros((q_pad - Q,) + x0.shape[1:], x0.dtype)])
    x0 = np.stack([_pad_rows(x0[qi], r_pad) for qi in range(q_pad)])
    # padding rows hold the ⊕-identity so they never win a reduction
    # (inf for the min semirings, 0 for plus_times/max_min — the value
    # np.pad already wrote, so this is a no-op there)
    x0[:, p.r_pad:] = algebra.get(p.semiring).zero
    # padding queries start converged: frozen from sweep 0, zero work
    qlive = np.arange(q_pad) < Q
    return ShardedBatch(mesh=mesh, d_g=d_g, d_q=d_q, r_pad=r_pad,
                        q_pad=q_pad, q=Q, vals=vals, cols=cols, nnz=nnz,
                        valid=valid, x0=x0, qlive=qlive)


def distributed_sync_run(
        p: Prepared, x0: jnp.ndarray, apply_kind: str = "relax",
        damping: float = 0.85, tol: float = 1e-6, max_sweeps: int = 10_000,
        mesh: Optional[Mesh] = None) -> Tuple[jnp.ndarray, DistStats]:
    """Bulk-synchronous distributed engine (shard_map over 'graph'), on
    the mesh the plan was placed on at build unless ``mesh`` is given."""
    mesh = mesh or plan_mesh_of(p) or make_graph_mesh()
    d = mesh.shape["graph"]
    # host-level fault sites: an exchange-round failure (raise) and a
    # straggling shard (delay) — shard_map bodies are compiled, so the
    # engine's dispatch boundary is where injection can model them
    resilience.fire("dist.straggler", flavor="sync", batched=False,
                    shards=d)
    resilience.fire("dist.dispatch", flavor="sync", batched=False,
                    shards=d)
    ring = algebra.get(p.semiring)

    r_pad = ((p.r_pad + d - 1) // d) * d
    step = rows_per_step(r_pad // d, p.gb)
    vals, cols, nnz, valid = plan_rows(p, mesh)
    x0 = _pad_rows(np.asarray(x0), r_pad).copy()
    # padding rows hold the ⊕-identity so they never win a reduction
    x0[p.r_pad:] = ring.zero
    inv_n = jnp.float32(1.0 / max(p.n, 1))
    damping = jnp.float32(damping)
    tol = jnp.float32(tol)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("graph"), P("graph"), P("graph"), P("graph"),
                  P("graph")),
        out_specs=(P("graph"), P(), P()), check_vma=False)
    def run(vals_l, cols_l, nnz_l, valid_l, x_l):
        def cond(st):
            i, x_loc, done = st
            return (~done) & (i < max_sweeps)

        def body(st):
            i, x_loc, _ = st
            # one query: (r, B) is the wave's row-major layout at Q=1
            x_new, imp = wave_sweep(vals_l, cols_l, nnz_l, valid_l, x_loc,
                                    p.semiring, apply_kind, step, damping,
                                    inv_n, tol)
            done = ~(jax.lax.psum(jnp.any(imp).astype(jnp.int32),
                                  "graph") > 0)
            return i + 1, x_new, done

        i, x_loc, done = jax.lax.while_loop(
            cond, body, (jnp.int32(0), x_l, False))
        return x_loc, i[None], done[None]

    x, i, done = run(vals, cols, nnz, valid, jnp.asarray(x0))
    halo = (r_pad // d) * p.b * 4.0 * (d - 1)  # gathered remote bytes/device
    stats = DistStats(sweeps=int(i[0]), converged=bool(done[0]),
                      halo_bytes_per_sweep=float(halo),
                      cut_fraction=p.clustering.cut_fraction,
                      mesh_shape=(d, dict(mesh.shape).get("query", 1)),
                      halo_exchanges=int(i[0]))  # BSP: one per sweep
    return x[: p.r_pad], stats


def rows_per_step(local_rows: int, gb: int) -> int:
    """Rows of the tiles a distributed sweep relaxes at a time: the
    largest divisor of a device's ``local_rows`` not above the plan's
    group size ``gb``, as the single-device engines step over groups."""
    for step in range(min(max(int(gb), 1), local_rows), 0, -1):
        if local_rows % step == 0:
            return step
    return 1


def local_spmv(vals, cols, nnz, x, semiring: str, step: int):
    """The wave kernel over a device's rows for a wave ``x`` (C, Q, B),
    queries contiguous inside each row-block → (R, Q, B), ``step`` rows
    of the tiles at a time: only one step's gathered source rows are
    live, not those of every row (at a scale-17 Kronecker plan's shard,
    26 GB for a wave of 8).  Each row is computed as in one call over
    all rows, so the result is the same."""
    r = vals.shape[0]

    def rows(v, c, n):
        return _spmv_wave(v, c, n, x, semiring=semiring)

    if step >= r:
        return rows(vals, cols, nnz)
    g = r // step
    y = jax.lax.map(lambda a: rows(*a), (
        vals.reshape((g, step) + vals.shape[1:]),
        cols.reshape(g, step, cols.shape[1]), nnz.reshape(g, step)))
    return y.reshape((r,) + y.shape[2:])


def wave_sweep(vals, cols, nnz, valid, x, semiring: str, apply_kind: str,
               step: int, damping, inv_n, tol):
    """One bulk-synchronous sweep of a device's rows inside a shard_map
    over "graph", for a wave carried row-major: ``x`` (r, Q*B), query q
    of row-block i in ``x[i, q*B:(q+1)*B]``.  The halo is a tiled
    all_gather of ``x`` along "graph"; each tile's source block is then
    one contiguous row of Q*B values for the whole wave
    (:func:`local_spmv`).  Returns ``(x_new, improved)``, both (r, Q*B)."""
    r, qb = x.shape
    b = valid.shape[1]
    xg = jax.lax.all_gather(x, "graph", tiled=True)
    y = local_spmv(vals, cols, nnz, xg.reshape(-1, qb // b, b), semiring,
                   step)
    return algebra.apply_rule(apply_kind, algebra.get(semiring),
                              y.reshape(r, qb), x,
                              jnp.tile(valid, (1, qb // b)), damping,
                              inv_n, tol)


@functools.lru_cache(maxsize=64)
def batched_sync_program(mesh: Mesh, semiring: str, apply_kind: str,
                         damping: float, inv_n: float, tol: float,
                         max_sweeps: int, step: int):
    """The bulk-synchronous batched engine as one jitted shard_map
    program over ``mesh``: ``(vals, cols, nnz, valid, x0, qlive)`` →
    ``(x, per-query sweeps, per-query done)``, plan rows sharded
    ``P("graph")`` and the frontier ``P("query", "graph")``.  Cached, so
    every wave of a plan dispatches the program compiled for the first."""
    inv_n = jnp.float32(inv_n)
    damping = jnp.float32(damping)
    tol = jnp.float32(tol)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("graph"), P("graph"), P("graph"), P("graph"),
                  P("query", "graph"), P("query")),
        out_specs=(P("query", "graph"), P("query"), P("query")),
        check_vma=False)
    def run(vals_l, cols_l, nnz_l, valid_l, x_l, qlive_l):
        nq, r_l, b = x_l.shape

        def cond(st):
            i, x, done_q, sweeps_q, all_done = st
            return (~all_done) & (i < max_sweeps)

        def body(st):
            i, x, done_q, sweeps_q, _ = st
            # halo exchange: ONLY along "graph" — queries are independent
            x_new, imp = wave_sweep(vals_l, cols_l, nnz_l, valid_l, x,
                                    semiring, apply_kind, step, damping,
                                    inv_n, tol)
            live = ~done_q
            # a live query's final (no-improvement) sweep still writes
            # x_new and counts — exactly like the sequential while_loop
            x = jnp.where(jnp.repeat(live, b)[None], x_new, x)
            sweeps_q = sweeps_q + live.astype(jnp.int32)
            imp_q = jax.lax.psum(jnp.any(
                imp.reshape(r_l, nq, b), axis=(0, 2)).astype(jnp.int32),
                "graph") > 0
            done_q = done_q | ~imp_q
            # scalar convergence vote — the only cross-"query" traffic
            open_n = jax.lax.psum(jnp.sum(~done_q), "query")
            return i + 1, x, done_q, sweeps_q, open_n == 0

        # the wave is carried row-major, (r_l, Q*B), relaid once on
        # either side of the loop: a relayout inside it could fuse into
        # the source gather and read Q strided rows of B again
        x = jnp.transpose(x_l, (1, 0, 2)).reshape(r_l, nq * b)
        st = (jnp.int32(0), x, ~qlive_l, jnp.zeros(nq, jnp.int32),
              jnp.array(False))
        _, x, done_q, sweeps_q, _ = jax.lax.while_loop(cond, body, st)
        return (jnp.transpose(x.reshape(r_l, nq, b), (1, 0, 2)), sweeps_q,
                done_q)

    return jax.jit(run)


def distributed_sync_run_batched(
        p: Prepared, x0: jnp.ndarray, apply_kind: str = "relax",
        damping: float = 0.85, tol: float = 1e-6, max_sweeps: int = 10_000,
        mesh: Optional[Mesh] = None, query_axis: Optional[int] = None
        ) -> Tuple[jnp.ndarray, DistStats]:
    """Batched distributed engine: ONE shard_map dispatch over the 2-D
    ``("graph", "query")`` mesh for a stacked ``(Q, r_pad, B)`` frontier.

    Rows shard over "graph" exactly as in :func:`distributed_sync_run`;
    the query axis shards over "query".  Halo exchange (the tiled
    all_gather of frontier values) runs only along "graph" — queries are
    independent, so no bytes cross the "query" axis except the scalar
    convergence vote.  Each query freezes (bit-exactly, including its
    final no-improvement sweep — the same last write the sequential loop
    does) once it individually converges, so results are bit-identical
    to running the sources one at a time through the sequential
    distributed engine, for any mesh factorization.

    ``query_axis``: explicit "query" extent (must divide the device
    count); None runs on the mesh the plan was placed on at build, or
    auto-factors via :func:`factor_query_axis` for a plan on one device.
    Ignored when ``mesh`` is given.  A device steps over its rows
    :func:`rows_per_step` at a time (:func:`local_spmv`).
    """
    sb = shard_batched_inputs(p, x0, mesh=mesh, query_axis=query_axis)
    Q, d_g, d_q = sb.q, sb.d_g, sb.d_q
    resilience.fire("dist.straggler", flavor="sync", batched=True,
                    shards=d_g)
    resilience.fire("dist.dispatch", flavor="sync", batched=True,
                    shards=d_g)
    run = batched_sync_program(
        sb.mesh, p.semiring, apply_kind, damping=damping,
        inv_n=1.0 / max(p.n, 1), tol=tol, max_sweeps=max_sweeps,
        step=rows_per_step(sb.r_pad // d_g, p.gb))
    x, sweeps_q, done_q = run(sb.vals, sb.cols, sb.nnz, sb.valid,
                              jnp.asarray(sb.x0), jnp.asarray(sb.qlive))
    sweeps_q = np.asarray(sweeps_q)[:Q]
    straggler = int(sweeps_q.max(initial=0))
    stats = DistStats(
        sweeps=straggler,
        converged=bool(np.all(np.asarray(done_q)[:Q])),
        halo_bytes_per_sweep=sb.halo_bytes_per_exchange(p.b),
        cut_fraction=p.clustering.cut_fraction,
        mesh_shape=(d_g, d_q), query_sweeps=sweeps_q,
        halo_exchanges=straggler,  # bulk-synchronous: one per sweep
        **sb.placement())
    return x[:Q, : p.r_pad], stats


def lower_distributed(p: Prepared, mesh: Mesh, apply_kind: str = "relax",
                      batch: Optional[int] = None):
    """Lower (no execution) the distributed sweep for dry-run inspection:
    the engines' :func:`wave_sweep`, stepping over a device's rows as
    they do.

    ``batch=Q`` lowers the 2-D batched sweep instead: a ``(Q, r_pad, B)``
    frontier sharded ``P("query", "graph")``, carried row-major as the
    batched engine carries it — the collective layout CI and dry-run
    tooling inspect to confirm the halo exchange stays on "graph"."""
    shape = dict(mesh.shape)
    d = shape["graph"]
    d_q = shape.get("query", 1)
    r_pad = ((p.r_pad + d - 1) // d) * d
    step = rows_per_step(r_pad // d, p.gb)
    shard = NamedSharding(mesh, P("graph"))

    def one_sweep(vals, cols, nnz, valid, x):
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P("graph"),) * 4 + (
                P("query", "graph") if batch else P("graph"),),
            out_specs=P("query", "graph") if batch else P("graph"),
            check_vma=False)
        def sweep(vals_l, cols_l, nnz_l, valid_l, x_l):
            xr = x_l
            if batch:
                nq, r_l, b = x_l.shape
                xr = jnp.transpose(x_l, (1, 0, 2)).reshape(r_l, nq * b)
            x_new, _ = wave_sweep(vals_l, cols_l, nnz_l, valid_l, xr,
                                  p.semiring, apply_kind, step,
                                  jnp.float32(0.85), jnp.float32(1.0 / p.n),
                                  jnp.float32(1e-6))
            if batch:
                x_new = jnp.transpose(x_new.reshape(r_l, nq, b), (1, 0, 2))
            return x_new
        return sweep(vals, cols, nnz, valid, x)

    specs = [
        jax.ShapeDtypeStruct((r_pad, p.b, p.k_max * p.b), jnp.float32,
                             sharding=shard),
        jax.ShapeDtypeStruct((r_pad, p.k_max), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((r_pad,), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((r_pad, p.b), jnp.bool_, sharding=shard),
    ]
    if batch:
        q_pad = ((int(batch) + d_q - 1) // d_q) * d_q
        specs.append(jax.ShapeDtypeStruct(
            (q_pad, r_pad, p.b), jnp.float32,
            sharding=NamedSharding(mesh, P("query", "graph"))))
    else:
        specs.append(jax.ShapeDtypeStruct(
            (r_pad, p.b), jnp.float32, sharding=shard))
    return jax.jit(one_sweep).lower(*specs)
