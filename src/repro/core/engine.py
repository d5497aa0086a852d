"""Graph execution engines — the paper's model of computation, in JAX.

Two engines over the same clustered BSR substrate:

  * ``run_sync``  — bulk-synchronous (Jacobi): every sweep processes every
    tile against last sweep's values.  This is the conventional
    global-clock execution the paper argues against; it is the CPU/GPU
    baseline semantics.

  * ``run_async`` — the paper's asynchronous model, adapted to TPU (see
    DESIGN.md §2): clusters are processed along the dependency schedule;
    each cluster (a) *skips* entirely when none of its inputs changed —
    self-timed, work ∝ data readiness — and (b) reads the *freshest*
    values, including ones produced earlier in the same sweep
    (Gauss-Seidel), the software analogue of values flowing through NALE
    FIFOs as soon as they are produced rather than at a global barrier.

Both engines emit work counters (tiles, edges, per-sweep critical path,
halo traffic) that feed the cycle/energy models in ``power.py`` and the
ISA-level accounting in ``compile.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import io
import json
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .cluster import Clustering, cluster_graph, identity_clustering
from .graph import Graph, to_bsr
from ..kernels import ops
from ..kernels.bsr_spmv import lane_tiles
from ..kernels.spec import KernelSpec, as_kernel_spec
from .. import algebra, obs, resilience

#: the widest wave of sources a served plan runs at once: the default of
#: ``GraphService.max_wave`` and ``WavePolicy.max_wave``, and the wave a
#: plan placed over several devices is sized for when no server says
MAX_WAVE = 64


@dataclasses.dataclass
class Prepared:
    """Clustered, permuted, device-resident graph + engine metadata."""

    # device arrays
    vals: jnp.ndarray       # (r_pad, B, K*B) f32 — destination-major tiles
    cols: jnp.ndarray       # (r_pad, K) i32
    nnz: jnp.ndarray        # (r_pad,) i32
    valid: jnp.ndarray      # (r_pad, B) bool — real (non-padding) vertices
    dangling: jnp.ndarray   # (r_pad, B) bool — zero-outdegree vertices
    group_tiles: jnp.ndarray  # (S,) f32
    group_edges: jnp.ndarray  # (S,) f32
    group_ext_tiles: jnp.ndarray  # (S,) f32 — tiles reading outside group
    row_edges: jnp.ndarray  # (r_pad,) f32 — true edges per row-block
    row_ext: jnp.ndarray    # (r_pad,) f32 — tiles reading outside the
    #                         row's group (fused-path halo accounting)
    # host metadata
    n: int
    b: int
    r_pad: int
    k_max: int
    gb: int                 # row-blocks per group ("cluster" at engine level)
    s: int                  # number of groups
    semiring: str
    perm: np.ndarray        # old id -> new id
    inv_perm: np.ndarray    # new id -> old id
    clustering: Clustering
    tiles_total: float = 0.0
    edges_total: float = 0.0

    def to_blocks(self, x_flat: np.ndarray, pad: float) -> jnp.ndarray:
        """(n,) values in OLD ids → (r_pad, B) block layout in new ids."""
        out = np.full(self.r_pad * self.b, pad, dtype=np.float32)
        out[self.perm] = x_flat
        return jnp.asarray(out.reshape(self.r_pad, self.b))

    def from_blocks(self, xb: jnp.ndarray) -> np.ndarray:
        """(r_pad, B) block layout → (n,) values in OLD ids."""
        flat = np.asarray(xb).reshape(-1)
        return flat[self.perm]

    @property
    def nbytes(self) -> int:
        """Footprint of the plan (device tile image + host metadata) —
        the unit of the plan store's byte budget.  Metadata-only: jax
        arrays report nbytes without a device-to-host transfer."""
        dev = sum(int(getattr(self, f).nbytes)
                  for f in _PREPARED_DEVICE_FIELDS)
        return dev + self._host_nbytes()

    @property
    def shard_nbytes(self) -> int:
        """``nbytes`` as one device holds it: the device arrays' bytes on
        the fullest device (a plan whose rows are sharded over a mesh
        holds a share on each), plus the host metadata.  Equal to
        ``nbytes`` for a plan on one device."""
        per: dict = {}
        for f in _PREPARED_DEVICE_FIELDS:
            a = getattr(self, f)
            s = getattr(a, "sharding", None)
            if s is None:
                per[None] = per.get(None, 0) + int(a.nbytes)
                continue
            n = int(np.prod(s.shard_shape(a.shape))) * a.dtype.itemsize
            for d in s.device_set:
                per[d] = per.get(d, 0) + n
        return max(per.values()) + self._host_nbytes()

    def _host_nbytes(self) -> int:
        return int(self.perm.nbytes) + int(self.inv_perm.nbytes) + \
            int(self.clustering.assign.nbytes) + \
            int(self.clustering.perm.nbytes)


# ``Prepared`` as a pytree: device arrays are leaves, host metadata is the
# (hashable, content-compared) treedef aux.  This is what makes a plan a
# first-class JAX value — it can ride through jax.tree_util (serialization
# walks the same split) and be passed whole into transformed functions.

_PREPARED_DEVICE_FIELDS = (
    "vals", "cols", "nnz", "valid", "dangling",
    "group_tiles", "group_edges", "group_ext_tiles",
    "row_edges", "row_ext")
_PREPARED_HOST_FIELDS = (
    "n", "b", "r_pad", "k_max", "gb", "s", "semiring",
    "perm", "inv_perm", "clustering", "tiles_total", "edges_total")


class _HostMeta:
    """Hashable wrapper for Prepared's host half (numpy arrays compare by
    content; the hash folds in the permutation bytes)."""

    __slots__ = ("fields", "_hash")

    def __init__(self, fields: tuple):
        self.fields = fields
        d = dict(zip(_PREPARED_HOST_FIELDS, fields))
        self._hash = hash((d["n"], d["b"], d["r_pad"], d["k_max"],
                           d["gb"], d["s"], d["semiring"],
                           d["perm"].tobytes()))

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        if not isinstance(other, _HostMeta):
            return NotImplemented
        for a, b in zip(self.fields, other.fields):
            if isinstance(a, np.ndarray):
                if not np.array_equal(a, b):
                    return False
            elif isinstance(a, Clustering):
                if not (a.num_clusters == b.num_clusters
                        and np.array_equal(a.perm, b.perm)
                        and np.array_equal(a.schedule, b.schedule)):
                    return False
            elif a != b:
                return False
        return True


def _prepared_flatten(p: Prepared):
    children = tuple(getattr(p, f) for f in _PREPARED_DEVICE_FIELDS)
    aux = _HostMeta(tuple(getattr(p, f) for f in _PREPARED_HOST_FIELDS))
    return children, aux


def _prepared_unflatten(aux: _HostMeta, children) -> Prepared:
    kw = dict(zip(_PREPARED_DEVICE_FIELDS, children))
    kw.update(zip(_PREPARED_HOST_FIELDS, aux.fields))
    return Prepared(**kw)


jax.tree_util.register_pytree_node(
    Prepared, _prepared_flatten, _prepared_unflatten)


# ---------------------------------------------------------------------------
# Prepared (de)serialization — the persistent half of the plan store
# ---------------------------------------------------------------------------
#
# A serialized plan is one .npz payload: the device tile image pulled back
# to host, the clustering/permutation, and a JSON metadata record.  A warm
# restart deserializes this instead of re-running the whole compile
# pipeline (profile → cluster → analyze → place → BSR build).

PREPARED_FORMAT_VERSION = 3  # v3: destination-major (r, B, K*B) tiles

# Payload framing: serialized plans carry a content digest so the store
# can tell a corrupt/truncated disk entry from a healthy one and
# quarantine-and-rebuild instead of crashing (or worse, loading silently
# mangled tiles).  Frame = MAGIC + blake2b-128(payload) + payload;
# pre-framing payloads (no magic) still load, with integrity unknown.
_PLAN_MAGIC = b"RPLN\x01\x00"
_PLAN_DIGEST_SIZE = 16


class PlanIntegrityError(ValueError):
    """A framed plan payload failed its checksum — the bytes on disk are
    not the bytes that were written (bit rot, truncation, torn write)."""


def _frame_payload(payload: bytes) -> bytes:
    digest = hashlib.blake2b(payload,
                             digest_size=_PLAN_DIGEST_SIZE).digest()
    return _PLAN_MAGIC + digest + payload


def _unframe_payload(data: bytes) -> bytes:
    if not data.startswith(_PLAN_MAGIC):
        return data  # legacy unframed payload
    head = len(_PLAN_MAGIC)
    digest = data[head:head + _PLAN_DIGEST_SIZE]
    payload = data[head + _PLAN_DIGEST_SIZE:]
    want = hashlib.blake2b(payload,
                           digest_size=_PLAN_DIGEST_SIZE).digest()
    if digest != want:
        raise PlanIntegrityError(
            f"plan payload checksum mismatch ({len(payload)} bytes); "
            "the disk entry is corrupt — rebuild the plan")
    return payload


def serialize_prepared(p: Prepared) -> bytes:
    """Pack a ``Prepared`` into a self-describing bytes payload."""
    c = p.clustering
    meta = dict(
        version=PREPARED_FORMAT_VERSION, n=p.n, b=p.b, r_pad=p.r_pad,
        k_max=p.k_max, gb=p.gb, s=p.s, semiring=p.semiring,
        tiles_total=p.tiles_total, edges_total=p.edges_total,
        c_num_clusters=c.num_clusters, c_internal=c.internal_edges,
        c_cut=c.cut_edges)
    arrays = {f: np.asarray(getattr(p, f)) for f in _PREPARED_DEVICE_FIELDS}
    arrays.update(perm=p.perm, inv_perm=p.inv_perm, c_assign=c.assign,
                  c_perm=c.perm, c_sizes=c.sizes, c_schedule=c.schedule)
    buf = io.BytesIO()
    np.savez(buf, __meta__=np.frombuffer(
        json.dumps(meta).encode(), dtype=np.uint8), **arrays)
    return _frame_payload(buf.getvalue())


def deserialize_prepared(data: bytes) -> Prepared:
    """Rebuild a ``Prepared`` (device arrays re-uploaded) from a payload
    produced by :func:`serialize_prepared`.  Raises
    ``PlanIntegrityError`` when a framed payload fails its checksum."""
    data = _unframe_payload(data)
    with np.load(io.BytesIO(data), allow_pickle=False) as z:
        meta = json.loads(z["__meta__"].tobytes().decode())
        if meta["version"] != PREPARED_FORMAT_VERSION:
            raise ValueError(
                f"plan payload version {meta['version']} != "
                f"{PREPARED_FORMAT_VERSION}; rebuild the plan")
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    clustering = Clustering(
        num_clusters=int(meta["c_num_clusters"]),
        assign=arrays["c_assign"], perm=arrays["c_perm"],
        sizes=arrays["c_sizes"], schedule=arrays["c_schedule"],
        internal_edges=int(meta["c_internal"]),
        cut_edges=int(meta["c_cut"]))
    return Prepared(
        **{f: jnp.asarray(arrays[f]) for f in _PREPARED_DEVICE_FIELDS},
        n=int(meta["n"]), b=int(meta["b"]), r_pad=int(meta["r_pad"]),
        k_max=int(meta["k_max"]), gb=int(meta["gb"]), s=int(meta["s"]),
        semiring=meta["semiring"], perm=arrays["perm"],
        inv_perm=arrays["inv_perm"], clustering=clustering,
        tiles_total=float(meta["tiles_total"]),
        edges_total=float(meta["edges_total"]))


def prepare(g: Graph, semiring_name: str, b: int = 32,
            num_clusters: Optional[int] = None, pull: bool = True,
            clustered: bool = True, normalize: Optional[str] = None,
            seed: int = 0, devices: int = 1,
            max_wave: int = MAX_WAVE) -> Prepared:
    """Paper Fig. 4 steps 1–5: profile/extract → cluster → analyze →
    place → build the device BSR image.

    pull=True computes over in-edges (y_i = ⊕_j A[j→i] ⊗ x_j), the natural
    direction for relaxation/propagation algorithms.
    normalize="out_stochastic": edge j→i gets weight 1/outdeg(j) (PageRank).
    devices > 1: the plan serves the distributed engines on that many
    devices; its rows go straight from the host to their shards of a
    ``("graph", "query")`` mesh that ``placement.plan_mesh`` chooses
    from the plan's bytes, one device's memory and ``max_wave``, the
    widest wave it serves.  No device receives the whole plan unless the
    mesh's "graph" extent is 1.

    The phases are timed as spans (``repro.obs``): ``plan.cluster``,
    ``plan.tile`` (permute, transpose, BSR build, lane-tile padding,
    group and halo geometry) and ``plan.upload``, which ends when the
    plan's arrays are on the device; ``plan.upload`` records ``devices``,
    ``mesh`` and ``shard_bytes`` (tile bytes on the fullest device), and
    on a mesh holds the ``plan.place`` span of the rows' placement.
    """
    ring = algebra.get(semiring_name)
    n = g.n
    if normalize == "out_stochastic":
        outdeg = np.maximum(np.diff(g.indptr), 1)
        w = (1.0 / outdeg)[np.repeat(np.arange(n), np.diff(g.indptr))]
        g = Graph(n=n, indptr=g.indptr, indices=g.indices,
                  weights=w.astype(np.float32))
    num_clusters = num_clusters or max(1, min(64, n // max(b, 1)))
    with obs.span("plan.cluster"):
        c = (cluster_graph(g, num_clusters, seed=seed) if clustered
             else identity_clustering(g, num_clusters))
    with obs.span("plan.tile"):
        g2 = g.permute(c.perm.astype(np.int32))
        gm = g2.transpose() if pull else g2
        bsr = to_bsr(gm, b, pad_value=float(ring.zero))

        # group (engine-level cluster) geometry: contiguous row-block ranges
        s = min(c.num_clusters, bsr.r)
        gb = (bsr.r + s - 1) // s
        r_pad = s * gb
        # tile slots padded so a row-block's sources fill whole 128-lane
        # columns: the Pallas kernels then take the device image as it is
        m = lane_tiles(b)
        k = -(-bsr.k_max // m) * m
        vals = np.full((r_pad, b, k * b), float(ring.zero),
                       dtype=np.float32)
        cols = np.zeros((r_pad, k), dtype=np.int32)
        nnz = np.zeros(r_pad, dtype=np.int32)
        vals[: bsr.r, :, : bsr.k_max * b] = bsr.block_vals
        cols[: bsr.r, : bsr.k_max] = bsr.block_cols
        nnz[: bsr.r] = bsr.block_nnz

        valid = np.zeros((r_pad, b), dtype=bool)
        valid.reshape(-1)[: n] = True  # permuted ids are 0..n-1
        outdeg0 = np.zeros(r_pad * b, dtype=np.int64)
        outdeg0[: n] = np.diff(g2.indptr)
        dangling = valid & (outdeg0.reshape(r_pad, b) == 0)

        grp = np.arange(r_pad) // gb
        group_tiles = np.zeros(s, dtype=np.float64)
        np.add.at(group_tiles, grp, nnz)
        group_edges = np.zeros(s, dtype=np.float64)
        edge_nnz = np.zeros(r_pad, dtype=np.float64)
        edge_nnz[: bsr.r] = bsr.edge_nnz
        del bsr             # its tiles live on as their padded copy, vals
        np.add.at(group_edges, grp, edge_nnz)
        # halo: tiles whose source col-block lives outside the group row range
        ext = ((cols // gb) != grp[:, None]) & \
              (np.arange(k)[None, :] < nnz[:, None])
        group_ext_tiles = np.zeros(s, dtype=np.float64)
        np.add.at(group_ext_tiles, grp, ext.sum(axis=1))
        row_ext = ext.sum(axis=1).astype(np.float64)
        perm = np.asarray(c.perm)
        inv_perm = np.argsort(perm)

    if devices > 1:
        dev = _upload_sharded(
            dict(vals=vals, cols=cols, nnz=nnz, valid=valid,
                 dangling=dangling,
                 row_edges=edge_nnz.astype(np.float32),
                 row_ext=row_ext.astype(np.float32)),
            dict(group_tiles=group_tiles.astype(np.float32),
                 group_edges=group_edges.astype(np.float32),
                 group_ext_tiles=group_ext_tiles.astype(np.float32)),
            gb, devices, max_wave)
    else:
        with obs.span("plan.upload", devices=1, mesh="1x1",
                      shard_bytes=int(vals.nbytes)):
            dev = dict(
                vals=jnp.asarray(vals), cols=jnp.asarray(cols),
                nnz=jnp.asarray(nnz), valid=jnp.asarray(valid),
                dangling=jnp.asarray(dangling),
                group_tiles=jnp.asarray(group_tiles, jnp.float32),
                group_edges=jnp.asarray(group_edges, jnp.float32),
                group_ext_tiles=jnp.asarray(group_ext_tiles, jnp.float32),
                row_edges=jnp.asarray(edge_nnz, jnp.float32),
                row_ext=jnp.asarray(row_ext, jnp.float32))
            jax.block_until_ready(dev)
    return Prepared(
        **dev, n=n, b=b, r_pad=r_pad, k_max=k, gb=gb, s=s,
        semiring=semiring_name, perm=perm, inv_perm=inv_perm,
        clustering=c, tiles_total=float(nnz.sum()),
        edges_total=float(edge_nnz.sum()))


def _upload_sharded(rows: dict, groups: dict, gb: int, devices: int,
                    max_wave: int) -> dict:
    """A plan's device arrays on the mesh ``placement.plan_mesh`` chooses
    for it: the row arrays sharded ``P("graph")``, the per-group ones
    replicated."""
    from jax.sharding import NamedSharding, PartitionSpec
    from . import placement
    vals = rows["vals"]
    r_pad, b = vals.shape[0], vals.shape[1]
    k = rows["cols"].shape[1]
    mesh = placement.plan_mesh(
        r_pad, row_bytes=sum(a.nbytes for a in rows.values()) / r_pad,
        group_rows=gb,
        # per query, lane-dense (a wave carries its queries inside each
        # row-block): the gathered source blocks x[cols] of a row of the
        # sweep's step and their relayout, and the gathered frontier and
        # the new state
        query_row_bytes=2 * k * b * 4, query_bytes=2 * r_pad * b * 4,
        num_devices=devices, max_wave=max_wave)
    d_g = dict(mesh.shape)["graph"]
    with obs.span("plan.upload", devices=devices,
                  mesh=placement.mesh_name(mesh),
                  shard_bytes=-(-r_pad // d_g) * int(vals[0].nbytes)):
        dev = placement.place_rows(rows, mesh)
        whole = NamedSharding(mesh, PartitionSpec())
        dev.update({f: jax.device_put(a, whole)
                    for f, a in groups.items()})
        jax.block_until_ready(dev)
    return dev


@dataclasses.dataclass
class RunStats:
    sweeps: int
    converged: bool
    tile_work: float          # tiles actually combined
    edge_work: float          # true edges behind those tiles
    crit_tiles: float         # Σ_sweeps max_cluster(active tiles) — NALE critical path
    active_group_sweeps: float
    halo_tiles: float         # inter-cluster tile reads (FIFO/ICI traffic)
    total_groups: int
    mode: str


def bsp_stats(p: Prepared, sweeps: int, converged: bool, mode: str,
              work_sweeps: Optional[int] = None) -> RunStats:
    """Work counters for bulk-synchronous execution: every sweep touches
    every tile.  ``work_sweeps`` (default ``sweeps``) lets batched runs
    charge total work across the query axis while ``sweeps`` (and the
    critical path) reflect the straggler query."""
    w = sweeps if work_sweeps is None else work_sweeps
    return RunStats(
        sweeps=sweeps, converged=converged,
        tile_work=p.tiles_total * w,
        edge_work=p.edges_total * w,
        crit_tiles=float(np.max(np.asarray(p.group_tiles))) * sweeps,
        active_group_sweeps=float(p.s * w),
        halo_tiles=float(np.asarray(p.group_ext_tiles).sum()) * w,
        total_groups=p.s, mode=mode)


def dist_run_stats(p: Prepared, dist, mode: str = "distributed"
                   ) -> RunStats:
    """Work counters for a distributed run described by a
    ``placement.DistStats``.  Compute work follows the sweep counts as in
    :func:`bsp_stats`, but halo traffic is charged per *exchange*: the
    self-timed flavor's entire point is ``halo_exchanges < sweeps`` when
    ``local_sweeps > 1``, and the modeled boundary traffic must show it.
    """
    qs = dist.query_sweeps
    w = int(qs.sum()) if qs is not None else int(dist.sweeps)
    return RunStats(
        sweeps=dist.sweeps, converged=dist.converged,
        tile_work=p.tiles_total * w,
        edge_work=p.edges_total * w,
        crit_tiles=float(np.max(np.asarray(p.group_tiles))) * dist.sweeps,
        active_group_sweeps=float(p.s * w),
        halo_tiles=float(np.asarray(p.group_ext_tiles).sum())
        * dist.halo_exchanges,
        total_groups=p.s, mode=mode)


# ---------------------------------------------------------------------------
# synchronous (BSP / Jacobi) engine
# ---------------------------------------------------------------------------


def _resolve_kernel(kernel, impl: str) -> KernelSpec:
    """Resolve the runner-level ``kernel=``/legacy ``impl=`` pair into
    one KernelSpec (``kernel`` wins when given)."""
    if kernel is not None:
        return as_kernel_spec(kernel)
    return KernelSpec(impl=impl)


@functools.partial(jax.jit, static_argnames=(
    "semiring_name", "apply_kind", "max_sweeps", "kernel"))
@jax.named_scope("engine.sync_loop")
def _sync_loop(vals, cols, nnz, valid, dangling, x0, damping, tol, inv_n,
               semiring_name, apply_kind, max_sweeps, kernel):
    ring = algebra.get(semiring_name)
    spmv = ops.select_kernel("bsr_spmv", kernel)

    def cond(st):
        i, x, done = st
        return (~done) & (i < max_sweeps)

    def body(st):
        i, x, _ = st
        with jax.named_scope("sweep.spmv"):
            y = spmv(vals, cols, nnz, x, semiring=semiring_name)
        with jax.named_scope("sweep.apply"):
            x_new, imp = algebra.apply_rule(apply_kind, ring, y, x, valid,
                                            damping, inv_n, tol)
        return i + 1, x_new, ~jnp.any(imp)

    i, x, done = jax.lax.while_loop(cond, body, (jnp.int32(0), x0, False))
    return i, x, done


@functools.partial(jax.jit, static_argnames=(
    "semiring_name", "apply_kind", "max_sweeps", "gb", "s", "kernel"))
@jax.named_scope("engine.sync_loop")
def _sync_loop_fused(vals, cols, nnz, valid, row_edges, row_ext, x0,
                     changed0, damping, tol, inv_n, semiring_name,
                     apply_kind, max_sweeps, gb, s, kernel):
    """Jacobi sweep via the fused kernel: each sweep builds the active
    row-block set from the change flags (a row is live iff one of its
    live input tiles changed last sweep), hands the compact list to the
    fused relax+select+reduce kernel, and consumes the kernel's own
    convergence flag — no separate XLA apply/reduce.

    Exactness: with ``act`` built this way, skipped rows provably cannot
    improve (their inputs are bitwise-unchanged), so the trajectory —
    values AND sweep count — matches the unfused path.  Bias apply kinds
    (pagerank/identity) must touch every valid row once, on sweep 0.
    """
    spmv = ops.select_kernel("bsr_spmv", kernel)
    k = cols.shape[1]
    lane = jnp.arange(k)[None, :]
    live = lane < nnz[:, None]
    nnz_f = nnz.astype(jnp.float32)
    bias = algebra.rule(apply_kind).bias
    valid_rows = jnp.any(valid, axis=1)

    def cond(st):
        i, x, ch, done, c = st
        return (~done) & (i < max_sweeps)

    def body(st):
        i, x, ch, _, c = st
        with jax.named_scope("sweep.frontier"):
            act = jnp.any(ch[cols] & live, axis=1)
            if bias:
                act = act | ((i == 0) & valid_rows)
        with jax.named_scope("sweep.spmv"):
            x, ch, imp_any = spmv(vals, cols, nnz, x, x, valid, act,
                                  damping, tol, inv_n,
                                  semiring=semiring_name,
                                  apply_kind=apply_kind)
        af = act.astype(jnp.float32)
        g_tiles = (af * nnz_f).reshape(s, gb).sum(axis=1)
        c = dict(
            c,
            tile_work=c["tile_work"] + jnp.sum(af * nnz_f),
            edge_work=c["edge_work"] + jnp.sum(af * row_edges),
            halo=c["halo"] + jnp.sum(af * row_ext),
            active=c["active"] + jnp.sum(
                jnp.any(act.reshape(s, gb), axis=1).astype(jnp.float32)),
            crit=c["crit"] + jnp.max(g_tiles))
        return i + 1, x, ch, ~imp_any, c

    counters0 = dict(tile_work=jnp.float32(0), edge_work=jnp.float32(0),
                     halo=jnp.float32(0), active=jnp.float32(0),
                     crit=jnp.float32(0))
    i, x, ch, done, c = jax.lax.while_loop(
        cond, body, (jnp.int32(0), x0, changed0, False, counters0))
    return i, x, done, c


def _counter_stats(p: Prepared, sweeps: int, converged: bool, c: dict,
                   mode: str) -> RunStats:
    """RunStats from measured per-sweep counters (fused paths); batched
    callers pass summed arrays, so reduce with numpy."""
    return RunStats(
        sweeps=sweeps, converged=converged,
        tile_work=float(np.asarray(c["tile_work"]).sum()),
        edge_work=float(np.asarray(c["edge_work"]).sum()),
        crit_tiles=float(np.asarray(c["crit"]).max(initial=0.0)),
        active_group_sweeps=float(np.asarray(c["active"]).sum()),
        halo_tiles=float(np.asarray(c["halo"]).sum()),
        total_groups=p.s, mode=mode)


def run_sync(p: Prepared, x0: jnp.ndarray, apply_kind: str = "relax",
             damping: float = 0.85, tol: float = 1e-6,
             max_sweeps: int = 10_000, impl: str = "ref", kernel=None,
             changed0: Optional[jnp.ndarray] = None
             ) -> Tuple[jnp.ndarray, RunStats]:
    spec = _resolve_kernel(kernel, impl)
    resilience.fire("engine.run", mode="sync", impl=spec.impl,
                    fused=spec.fuse_frontier, batched=False)
    inv_n = jnp.float32(1.0 / max(p.n, 1))
    if spec.fuse_frontier:
        if changed0 is None:
            changed0 = jnp.ones(p.r_pad, dtype=bool)
        i, x, done, c = _sync_loop_fused(
            p.vals, p.cols, p.nnz, p.valid, p.row_edges, p.row_ext, x0,
            changed0, jnp.float32(damping), jnp.float32(tol), inv_n,
            p.semiring, apply_kind, max_sweeps, p.gb, p.s, spec)
        return x, _counter_stats(p, int(i), bool(done), c, "sync")
    i, x, done = _sync_loop(p.vals, p.cols, p.nnz, p.valid, p.dangling, x0,
                            jnp.float32(damping), jnp.float32(tol), inv_n,
                            p.semiring, apply_kind, max_sweeps, spec)
    return x, bsp_stats(p, int(i), bool(done), "sync")


# ---------------------------------------------------------------------------
# asynchronous (cluster-dataflow, Gauss-Seidel) engine
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=(
    "semiring_name", "apply_kind", "max_sweeps", "gb", "s", "kernel"))
@jax.named_scope("engine.async_loop")
def _async_loop(vals, cols, nnz, valid, dangling, group_tiles, group_edges,
                group_ext, row_edges, row_ext, x0, changed0, damping, tol,
                inv_n, semiring_name, apply_kind, max_sweeps, gb, s,
                kernel):
    ring = algebra.get(semiring_name)
    spmv = ops.select_kernel("bsr_spmv", kernel)
    fused = kernel.fuse_frontier
    k = cols.shape[1]
    lane = jnp.arange(k)[None, :]

    # apply kinds with a bias term (PageRank's (1-d)/n, k-core's
    # threshold test) must touch every cluster at least once even if it
    # has no in-edges (registry: algebra.UPDATE_RULES).
    first_touch = algebra.rule(apply_kind).bias

    def sweep_step(carry, sidx):
        x, ch_prev, ch_next, ran, counters = carry
        row0 = sidx * gb
        vals_g = jax.lax.dynamic_slice_in_dim(vals, row0, gb, 0)
        cols_g = jax.lax.dynamic_slice_in_dim(cols, row0, gb, 0)
        nnz_g = jax.lax.dynamic_slice_in_dim(nnz, row0, gb, 0)
        # data readiness: any live input tile whose source block changed —
        # either last sweep (ch_prev) or earlier THIS sweep (ch_next, the
        # Gauss-Seidel freshness path).
        with jax.named_scope("sweep.frontier"):
            ch = ch_prev | ch_next
            live = lane < nnz_g[:, None]
            active = jnp.any(ch[cols_g] & live)
            if first_touch:
                active = active | ~ran[sidx]
            if fused:
                # row-granular frontier inside the group: the kernel's active
                # list skips the group's untouched row-blocks entirely.
                vg = jax.lax.dynamic_slice_in_dim(valid, row0, gb, 0)
                act_rows = jnp.any(ch[cols_g] & live, axis=1)
                if first_touch:
                    act_rows = act_rows | (~ran[sidx]
                                           & jnp.any(vg, axis=1))

        def do(args):
            x, ch_next = args
            xg = jax.lax.dynamic_slice_in_dim(x, row0, gb, 0)
            vg = jax.lax.dynamic_slice_in_dim(valid, row0, gb, 0)
            if fused:
                with jax.named_scope("sweep.spmv"):
                    x_new, imp_rows, _ = spmv(
                        vals_g, cols_g, nnz_g, x, xg, vg, act_rows,
                        damping, tol, inv_n, semiring=semiring_name,
                        apply_kind=apply_kind)
            else:
                with jax.named_scope("sweep.spmv"):
                    y = spmv(vals_g, cols_g, nnz_g, x,
                             semiring=semiring_name)
                with jax.named_scope("sweep.apply"):
                    x_new, imp = algebra.apply_rule(
                        apply_kind, ring, y, xg, vg, damping, inv_n, tol)
                    imp_rows = jnp.any(imp, axis=1)
            x = jax.lax.dynamic_update_slice_in_dim(x, x_new, row0, 0)
            ch_next = jax.lax.dynamic_update_slice_in_dim(
                ch_next, imp_rows, row0, 0)
            return x, ch_next

        x, ch_next = jax.lax.cond(active, do, lambda a: a, (x, ch_next))
        ran = ran.at[sidx].set(ran[sidx] | active)
        af = active.astype(jnp.float32)
        if fused:
            # charge only the rows the kernel actually walked
            arf = act_rows.astype(jnp.float32)
            g_tiles = jnp.sum(arf * nnz_g.astype(jnp.float32))
            g_edges = jnp.sum(
                arf * jax.lax.dynamic_slice_in_dim(row_edges, row0, gb, 0))
            g_halo = jnp.sum(
                arf * jax.lax.dynamic_slice_in_dim(row_ext, row0, gb, 0))
        else:
            g_tiles = af * group_tiles[sidx]
            g_edges = af * group_edges[sidx]
            g_halo = af * group_ext[sidx]
        counters = dict(
            counters,
            tile_work=counters["tile_work"] + g_tiles,
            edge_work=counters["edge_work"] + g_edges,
            halo=counters["halo"] + g_halo,
            active=counters["active"] + af,
            sweep_max=jnp.maximum(counters["sweep_max"], g_tiles))
        return (x, ch_prev, ch_next, ran, counters), None

    def cond(st):
        i, x, ch, ran, done, _ = st
        return (~done) & (i < max_sweeps)

    def body(st):
        i, x, ch_prev, ran, _, counters = st
        counters = dict(counters, sweep_max=jnp.float32(0.0))
        ch_next = jnp.zeros_like(ch_prev)
        (x, _, ch_next, ran, counters), _ = jax.lax.scan(
            sweep_step, (x, ch_prev, ch_next, ran, counters),
            jnp.arange(s, dtype=jnp.int32))
        counters = dict(counters,
                        crit=counters["crit"] + counters["sweep_max"])
        done = ~jnp.any(ch_next)
        return i + 1, x, ch_next, ran, done, counters

    counters0 = dict(tile_work=jnp.float32(0), edge_work=jnp.float32(0),
                     halo=jnp.float32(0), active=jnp.float32(0),
                     crit=jnp.float32(0), sweep_max=jnp.float32(0))
    ran0 = jnp.zeros(s, dtype=bool)
    i, x, ch, ran, done, counters = jax.lax.while_loop(
        cond, body, (jnp.int32(0), x0, changed0, ran0, False, counters0))
    return i, x, done, counters


def run_async(p: Prepared, x0: jnp.ndarray, apply_kind: str = "relax",
              damping: float = 0.85, tol: float = 1e-6,
              max_sweeps: int = 10_000,
              changed0: Optional[jnp.ndarray] = None, impl: str = "ref",
              kernel=None) -> Tuple[jnp.ndarray, RunStats]:
    spec = _resolve_kernel(kernel, impl)
    resilience.fire("engine.run", mode="async", impl=spec.impl,
                    fused=spec.fuse_frontier, batched=False)
    inv_n = jnp.float32(1.0 / max(p.n, 1))
    if changed0 is None:
        changed0 = jnp.ones(p.r_pad, dtype=bool)
    i, x, done, c = _async_loop(
        p.vals, p.cols, p.nnz, p.valid, p.dangling, p.group_tiles,
        p.group_edges, p.group_ext_tiles, p.row_edges, p.row_ext, x0,
        changed0, jnp.float32(damping), jnp.float32(tol), inv_n,
        p.semiring, apply_kind, max_sweeps, p.gb, p.s, spec)
    return x, _counter_stats(p, int(i), bool(done), c, "async")


# ---------------------------------------------------------------------------
# batched multi-source runners — vmap over the frontier-init axis
# ---------------------------------------------------------------------------
#
# One Prepared, one compile: the query axis (e.g. SSSP sources) is a vmap
# axis over x0, so Q queries share the device-resident BSR image and the
# traced program.  JAX's while_loop batching rule masks updates per query,
# so each query stops relaxing once it converges; reported sweeps is the
# straggler's (the batch retires together, like a wavefront of independent
# frontiers through the same NALE array).  The async runner on the unfused
# ref kernel carries the query axis itself instead (``_async_wave_loop``),
# with the same per-query trajectory: under vmap the state of Q queries
# lies query-major, and each tile's gather would read Q strided blocks.


def run_sync_batched(p: Prepared, x0: jnp.ndarray,
                     apply_kind: str = "relax", damping: float = 0.85,
                     tol: float = 1e-6, max_sweeps: int = 10_000,
                     impl: str = "ref", kernel=None,
                     changed0: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, RunStats]:
    """x0: (Q, r_pad, B) — returns ((Q, r_pad, B), aggregate RunStats)."""
    spec = _resolve_kernel(kernel, impl)
    resilience.fire("engine.run", mode="sync", impl=spec.impl,
                    fused=spec.fuse_frontier, batched=True)
    inv_n = jnp.float32(1.0 / max(p.n, 1))

    if spec.fuse_frontier:
        if changed0 is None:
            changed0 = jnp.ones((x0.shape[0], p.r_pad), dtype=bool)

        def one_fused(x0q, ch0q):
            return _sync_loop_fused(
                p.vals, p.cols, p.nnz, p.valid, p.row_edges, p.row_ext,
                x0q, ch0q, jnp.float32(damping), jnp.float32(tol), inv_n,
                p.semiring, apply_kind, max_sweeps, p.gb, p.s, spec)

        i, x, done, c = jax.vmap(one_fused)(x0, changed0)
        sweeps = np.asarray(i)
        return x, _counter_stats(p, int(sweeps.max(initial=0)),
                                 bool(np.all(done)), c, "sync")

    def one(x0q):
        return _sync_loop(p.vals, p.cols, p.nnz, p.valid, p.dangling, x0q,
                          jnp.float32(damping), jnp.float32(tol), inv_n,
                          p.semiring, apply_kind, max_sweeps, spec)

    i, x, done = jax.vmap(one)(x0)
    sweeps = np.asarray(i)
    return x, bsp_stats(p, int(sweeps.max(initial=0)), bool(np.all(done)),
                        "sync", work_sweeps=int(sweeps.sum()))


@functools.partial(jax.jit, static_argnames=(
    "semiring_name", "apply_kind", "max_sweeps", "gb", "s", "kernel"))
@jax.named_scope("engine.async_wave_loop")
def _async_wave_loop(vals, cols, nnz, valid, group_tiles, group_edges,
                     group_ext, x0, changed0, damping, tol, inv_n,
                     semiring_name, apply_kind, max_sweeps, gb, s, kernel):
    """``_async_loop`` for a wave of Q queries on the unfused kernel, with
    the query axis carried inside each row-block: x as (r_pad, Q*B) and
    the change flags as (r_pad, Q).  A tile's source block is then one
    contiguous row of Q*B values for the whole wave's gather, and its
    flags one row of Q.

    Each query follows the trajectory ``jax.vmap(_async_loop)`` gives it:
    every group is computed for the wave, and a query keeps the update
    only where its own frontier (or first touch) made the group active;
    a converged query's state and counters stay frozen while the
    straggler sweeps.  x0: (Q, r_pad, B), changed0: (Q, r_pad); returns
    per-query sweeps, x as (Q, r_pad, B), done and counters (each (Q,)).
    """
    ring = algebra.get(semiring_name)
    spmv = ops.select_kernel("bsr_spmv_wave", kernel)
    nq, r_pad, b = x0.shape
    lane = jnp.arange(cols.shape[1])[None, :, None]
    first_touch = algebra.rule(apply_kind).bias

    def sweep_step(carry, sidx):
        # ch: the flags the frontier test reads, last sweep's | this
        # sweep's so far; ch_next: this sweep's alone.  A group's rows of
        # ch_next are written once a sweep, by its own step.
        x, ch, ch_next, ran, counters, run = carry
        row0 = sidx * gb
        # the group's rows of x and ch are read by row gathers, not
        # slices: a slice would make the compiler lay out all of x or ch
        # as the update wants it, once per group
        rows = row0 + jnp.arange(gb)
        vals_g = jax.lax.dynamic_slice_in_dim(vals, row0, gb, 0)
        cols_g = jax.lax.dynamic_slice_in_dim(cols, row0, gb, 0)
        nnz_g = jax.lax.dynamic_slice_in_dim(nnz, row0, gb, 0)
        with jax.named_scope("sweep.frontier"):
            active = jnp.any(ch[cols_g] & (lane < nnz_g[:, None, None]),
                             axis=(0, 1))
            if first_touch:
                active = active | ~ran[sidx]
            active = active & run
        xg = x[rows]
        vg = jnp.tile(jax.lax.dynamic_slice_in_dim(valid, row0, gb, 0),
                      (1, nq))
        with jax.named_scope("sweep.spmv"):
            y = spmv(vals_g, cols_g, nnz_g, x.reshape(r_pad, nq, b),
                     semiring=semiring_name).reshape(gb, nq * b)
        with jax.named_scope("sweep.apply"):
            x_new, imp = algebra.apply_rule(apply_kind, ring, y, xg, vg,
                                            damping, inv_n, tol)
            x_new = jnp.where(jnp.repeat(active, b), x_new, xg)
            imp_rows = jnp.any(imp.reshape(gb, nq, b), axis=2) & active
        x = jax.lax.dynamic_update_slice_in_dim(x, x_new, row0, 0)
        ch = jax.lax.dynamic_update_slice_in_dim(
            ch, ch[rows] | imp_rows, row0, 0)
        ch_next = jax.lax.dynamic_update_slice_in_dim(
            ch_next, imp_rows, row0, 0)
        ran = ran.at[sidx].set(ran[sidx] | active)
        af = active.astype(jnp.float32)
        g_tiles = af * group_tiles[sidx]
        counters = dict(
            counters,
            tile_work=counters["tile_work"] + g_tiles,
            edge_work=counters["edge_work"] + af * group_edges[sidx],
            halo=counters["halo"] + af * group_ext[sidx],
            active=counters["active"] + af,
            sweep_max=jnp.maximum(counters["sweep_max"], g_tiles))
        return (x, ch, ch_next, ran, counters, run), None

    def cond(st):
        i, x, ch, ran, done, _ = st
        return jnp.any(~done & (i < max_sweeps))

    def body(st):
        i, x, ch_prev, ran, done, counters = st
        run = ~done & (i < max_sweeps)
        c = dict(counters, sweep_max=jnp.zeros(nq, jnp.float32))
        (x, _, ch_next, ran, c, _), _ = jax.lax.scan(
            sweep_step, (x, ch_prev, jnp.zeros_like(ch_prev), ran, c, run),
            jnp.arange(s, dtype=jnp.int32))
        c = dict(c, crit=c["crit"] + c["sweep_max"])
        c = {k: jnp.where(run, v, counters[k]) for k, v in c.items()}
        return (jnp.where(run, i + 1, i), x,
                jnp.where(run, ch_next, ch_prev), ran,
                jnp.where(run, ~jnp.any(ch_next, axis=0), done), c)

    zeros = jnp.zeros(nq, jnp.float32)
    counters0 = dict(tile_work=zeros, edge_work=zeros, halo=zeros,
                     active=zeros, crit=zeros, sweep_max=zeros)
    x = jnp.transpose(x0, (1, 0, 2)).reshape(r_pad, nq * b)
    i, x, ch, ran, done, counters = jax.lax.while_loop(
        cond, body, (jnp.zeros(nq, jnp.int32), x, changed0.T,
                     jnp.zeros((s, nq), dtype=bool),
                     jnp.zeros(nq, dtype=bool), counters0))
    x = jnp.transpose(x.reshape(r_pad, nq, b), (1, 0, 2))
    return i, x, done, counters


def wave_path(kernel) -> bool:
    """Whether ``run_async_batched`` runs a wave on ``_async_wave_loop``:
    the resolved kernel has a wave form (the unfused ref kernel).  The
    fused and Pallas kernels take one query's x and run under vmap."""
    return ops.has_kernel("bsr_spmv_wave", kernel)


def run_async_batched(p: Prepared, x0: jnp.ndarray,
                      apply_kind: str = "relax", damping: float = 0.85,
                      tol: float = 1e-6, max_sweeps: int = 10_000,
                      changed0: Optional[jnp.ndarray] = None,
                      impl: str = "ref", kernel=None
                      ) -> Tuple[jnp.ndarray, RunStats]:
    """x0: (Q, r_pad, B); changed0: optional (Q, r_pad) per-query frontier."""
    spec = _resolve_kernel(kernel, impl)
    resilience.fire("engine.run", mode="async", impl=spec.impl,
                    fused=spec.fuse_frontier, batched=True)
    inv_n = jnp.float32(1.0 / max(p.n, 1))
    if changed0 is None:
        changed0 = jnp.ones((x0.shape[0], p.r_pad), dtype=bool)
    if wave_path(spec):
        i, x, done, c = _async_wave_loop(
            p.vals, p.cols, p.nnz, p.valid, p.group_tiles, p.group_edges,
            p.group_ext_tiles, x0, changed0, jnp.float32(damping),
            jnp.float32(tol), inv_n, p.semiring, apply_kind, max_sweeps,
            p.gb, p.s, spec)
        sweeps = np.asarray(i)
        return x, _counter_stats(p, int(sweeps.max(initial=0)),
                                 bool(np.all(done)), c, "async")

    def one(x0q, ch0q):
        return _async_loop(
            p.vals, p.cols, p.nnz, p.valid, p.dangling, p.group_tiles,
            p.group_edges, p.group_ext_tiles, p.row_edges, p.row_ext,
            x0q, ch0q, jnp.float32(damping), jnp.float32(tol), inv_n,
            p.semiring, apply_kind, max_sweeps, p.gb, p.s, spec)

    i, x, done, c = jax.vmap(one)(x0, changed0)
    sweeps = np.asarray(i)
    return x, _counter_stats(p, int(sweeps.max(initial=0)),
                             bool(np.all(done)), c, "async")
