"""Session API — prepare once, query many (paper Fig. 4 split).

The paper separates *compile-time* work (profile → cluster → analyze →
place) from *run-time* execution on the self-timed NALE array.
``GraphProcessor`` is that split as an API: constructing one builds the
session; each query then runs against cached ``Prepared`` images — the
clustering/permutation and the device-resident BSR tiles are shared by
every algorithm that can use the same plan (keyed by semiring, graph
variant, direction, normalization and tiling), so serving many queries on
one graph pays the compile-time pipeline once.  PIUMA and GraphScale
expose the same load-once / query-many shape.

    proc = GraphProcessor(g, b=16, num_clusters=64)
    pr   = proc.pagerank()                       # prepares plus_times plan
    d    = proc.sssp(0)                          # prepares min_plus plan
    d2   = proc.sssp(5)                          # plan-cache hit: no rework
    dist = proc.sssp(sources=[0, 5, 9])          # batched: one vmap'd run

Execution is controlled by one ``ExecutionPolicy`` (engine mode, kernel
impl, convergence knobs) instead of per-function keyword scatter; every
query returns a uniform ``Result`` bundling per-vertex values, the
engine's measured ``RunStats``, and (via ``platform_models``) the
analytical NALE/CPU/GPU cycle & power models.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import engine as eng
from .algorithms import (AlgorithmSpec, get_algorithm,  # noqa: F401
                         register_algorithm, registered_algorithms)
from .engine import Prepared, RunStats
from .graph import Graph, to_ell_fast
from ..kernels.spec import KernelSpec, as_kernel_spec

MODES = ("sync", "async", "distributed")
IMPLS = ("ref", "pallas")
DIST_FLAVORS = ("sync", "async")


@dataclasses.dataclass(frozen=True)
class ExecutionPolicy:
    """How a query executes — one object for the knobs that used to be
    scattered across the ``algorithms.*`` free functions.

    mode:  "sync" (BSP/Jacobi baseline) | "async" (the paper's self-timed
           cluster-dataflow engine) | "distributed" (shard_map halo-
           exchange engine over the 2-D ("graph", "query") mesh).
    kernel:  a ``kernels.spec.KernelSpec`` — which kernel runs the
           sweeps and how (impl, block_size, rows_per_step,
           fuse_frontier, autotune).  None derives one from ``impl``.
           The distributed engine requires the "ref" kernel (Pallas
           calls cannot be SPMD-partitioned across host meshes).
    impl:  DEPRECATED alias for ``kernel=KernelSpec(impl=...)`` — "ref"
           (XLA-fused jnp) | "pallas" (Mosaic kernel; interpret mode
           off-TPU).  After construction ``impl`` always equals
           ``kernel.impl`` (both spellings compare/hash consistently).
    query_axis:  batched-distributed mesh factorization.  None (default)
           auto-factors the device count against the batch size
           (``placement.factor_query_axis``); an int >= 1 pins the
           "query" mesh extent (must divide the device count); 0 is the
           escape hatch back to the retired per-source sequential loop.
    dist_flavor:  exchange schedule of the distributed engine.  "sync"
           (default) is the bulk-synchronous path — one halo exchange
           per sweep; "async" is the self-timed engine
           (``core.async_dist``) — ``local_sweeps`` Gauss-Seidel
           relaxations per exchange with an overlapped, double-buffered
           halo, bit-identical at convergence for the idempotent
           "relax" algorithms (SSSP/BFS/CC/reachability).
    local_sweeps:  k, local sweeps per halo exchange; only meaningful
           (and only legal ≠ 1) with ``dist_flavor="async"``.
    degrade:  graceful-degradation ladder (True by default).  When an
           engine dispatch fails at run time, ``GraphProcessor.run``
           retries the query one rung down — a pallas/fused kernel
           failure re-runs on ``kernel=ref`` (bit-identical values), a
           distributed dispatch failure falls back to single-device
           ``mode="sync"`` — recording each step in
           ``Result.extra["degraded"]``.  API-misuse errors
           (ValueError/TypeError/KeyError/IndexError) never degrade: a
           request
           that can never execute must say so, not silently run
           something else.  ``degrade=False`` restores fail-fast.
    """

    mode: str = "async"
    impl: Optional[str] = None
    damping: float = 0.85
    tol: float = 1e-6
    max_sweeps: int = 10_000
    query_axis: Optional[int] = None
    dist_flavor: str = "sync"
    local_sweeps: int = 1
    kernel: Optional[KernelSpec] = None
    degrade: bool = True

    def __post_init__(self):
        if self.mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}: {self.mode!r}")
        # normalize the (impl, kernel) pair: afterwards kernel is always a
        # KernelSpec and impl mirrors kernel.impl, so the deprecated and
        # structured spellings compare/hash equal.
        if self.kernel is not None and not isinstance(self.kernel,
                                                      KernelSpec):
            object.__setattr__(self, "kernel", as_kernel_spec(self.kernel))
        if self.kernel is None:
            impl = self.impl if self.impl is not None else "ref"
            if impl == "pallas":
                warnings.warn(
                    "ExecutionPolicy(impl='pallas') is deprecated; pass "
                    "kernel=KernelSpec(impl='pallas', ...) to reach the "
                    "tiling/fusion/autotune surface",
                    DeprecationWarning, stacklevel=3)
            object.__setattr__(self, "kernel", KernelSpec(impl=impl))
            object.__setattr__(self, "impl", impl)
        else:
            if self.impl is not None and self.impl != self.kernel.impl:
                raise ValueError(
                    f"impl={self.impl!r} conflicts with kernel.impl="
                    f"{self.kernel.impl!r}; set only kernel= (impl= is "
                    "the deprecated alias)")
            object.__setattr__(self, "impl", self.kernel.impl)
        if self.mode == "distributed" and self.kernel.impl != "ref":
            raise ValueError(
                "the distributed engine shard_maps the ref kernel; "
                "Pallas calls cannot be SPMD-partitioned — use "
                "mode='sync'/'async' for kernel.impl='pallas'")
        if self.query_axis is not None and self.query_axis < 0:
            raise ValueError(
                "query_axis must be None (auto), 0 (per-source "
                f"fallback) or a positive extent: {self.query_axis!r}")
        if self.dist_flavor not in DIST_FLAVORS:
            raise ValueError(
                f"dist_flavor must be one of {DIST_FLAVORS}: "
                f"{self.dist_flavor!r}")
        if self.local_sweeps < 1:
            raise ValueError(
                f"local_sweeps must be >= 1, got {self.local_sweeps!r}")
        if self.dist_flavor == "async" and self.mode != "distributed":
            raise ValueError(
                "dist_flavor='async' selects the self-timed distributed "
                f"engine and requires mode='distributed', not "
                f"{self.mode!r}")
        if self.local_sweeps != 1 and self.dist_flavor != "async":
            raise ValueError(
                f"local_sweeps={self.local_sweeps} needs "
                "dist_flavor='async'; the bulk-synchronous engine "
                "exchanges every sweep by construction")
        if self.dist_flavor == "async" and self.query_axis == 0:
            raise ValueError(
                "query_axis=0 (per-source sequential fallback) has no "
                "async flavor; use query_axis=None or a mesh extent")

    def but(self, **kw) -> "ExecutionPolicy":
        """Copy with overrides (policy objects are frozen).

        Overriding ``impl=`` or ``kernel=`` alone re-derives the other
        half of the normalized pair, so single-field overrides never
        trip the impl/kernel conflict check."""
        if "impl" in kw and "kernel" not in kw:
            kw["kernel"] = None
        elif "kernel" in kw and "impl" not in kw:
            kw["impl"] = None
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """Everything that determines a ``Prepared`` image for one graph.

    Paired with the graph's content :meth:`~repro.core.graph.Graph.
    fingerprint`, a PlanKey is globally unique — that pair is the key of
    the cross-process plan store (``serve.graph.PlanStore``).
    """

    semiring: str
    variant: str          # base | unit | undirected — graph transform
    pull: bool
    normalize: Optional[str]
    b: int
    num_clusters: Optional[int]
    clustered: bool
    seed: int = 0         # clustering seed (part of plan identity)
    # Prepared images are kernel-agnostic and keyed with kernel=None (the
    # base key — existing stores stay valid); autotune records are keyed
    # by replace(base_key, kernel=requesting_spec), so tunings ride the
    # same (fingerprint, PlanKey) scheme without duplicating plans.
    kernel: Optional[KernelSpec] = None
    # devices the plan is placed over: 1, the image on one device every
    # engine takes; n > 1, rows sharded over a mesh of n devices for the
    # distributed engines (placement.plan_mesh, chosen at build)
    devices: int = 1


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One query against a session: algorithm + sources + policy.

    ``params`` are policy-field overrides applied over ``policy``; they
    may be given as a plain dict (``{"max_sweeps": 1}``) or as the
    historical tuple-of-tuples — dicts are normalized on construction so
    the spec stays hashable either way.
    """

    algo: str                                   # an AlgorithmSpec name
                                                # (core/algorithms.py)
    sources: Tuple[int, ...] = ()
    batched: bool = False                       # sources is a query axis
    policy: Optional[ExecutionPolicy] = None    # None → session default
    params: Union[Mapping[str, float],
                  Tuple[Tuple[str, float], ...]] = ()

    def __post_init__(self):
        # fail at construction, not deep in engine dispatch: the error
        # lists every registered algorithm
        get_algorithm(self.algo)
        items = self.params.items() if isinstance(self.params, Mapping) \
            else ((str(k), v) for k, v in self.params)
        # sorted in both forms: equivalent specs must compare/hash equal
        object.__setattr__(self, "params", tuple(sorted(items)))


@dataclasses.dataclass
class Result:
    """Uniform query result.

    ``values`` is per-vertex output in ORIGINAL vertex ids — shape (n,)
    for single queries, (Q, n) for batched multi-source queries.  The
    leading four fields match the old ``algorithms.AlgoResult`` layout,
    which is kept as an alias.
    """

    values: np.ndarray
    stats: RunStats
    prepared: Optional[Prepared]
    extra: dict
    policy: Optional[ExecutionPolicy] = None
    graph: Optional[Graph] = None

    def platform_models(self, sync_stats: Optional[RunStats] = None
                        ) -> dict:
        """Analytical NALE/CPU/GPU models (core/power.py) for this run.

        The GPU model needs bulk-synchronous sweep counts; it is included
        when this result is already sync or when ``sync_stats`` is given.
        """
        from . import power as PW
        if self.prepared is None:
            raise ValueError(
                f"{self.extra.get('algo', 'this')} result has no BSR "
                "image; platform models need a prepared plan")
        rep = {"nale": PW.model_nale(self.prepared, self.stats),
               "cpu": PW.model_cpu(self.prepared, self.stats)}
        ss = sync_stats or (self.stats if self.stats.mode == "sync"
                            else None)
        if ss is not None and self.graph is not None:
            k_pad = max(float(np.diff(self.graph.indptr).max()), 1.0)
            rep["gpu"] = PW.model_gpu(self.prepared, ss, k_max_pad=k_pad,
                                      avg_degree=self.graph.avg_degree)
        return rep


# back-compat aliases (snapshotted at import; the registry in
# core/algorithms.py is the source of truth and grows at runtime)
ALGOS = registered_algorithms()
SOURCE_REQUIRED = tuple(n for n in ALGOS
                        if get_algorithm(n).source_required)


def validate_spec(spec: QuerySpec) -> None:
    """Raise on specs that can never execute.  Shared by
    ``GraphProcessor.run`` and the serving layer's ``submit`` (which
    must reject bad requests before they can ride in a batch)."""
    a = get_algorithm(spec.algo)
    if a.source_required and not spec.sources:
        raise ValueError(
            f"{spec.algo} requires at least one source vertex")
    given = dict(spec.params)
    missing = [k for k in a.required_params if k not in given]
    if missing:
        raise ValueError(
            f"{spec.algo} requires params={{{', '.join(repr(m) for m in missing)}: ...}}"
            f" (e.g. QuerySpec(algo={spec.algo!r}, "
            f"params={{{missing[0]!r}: 2}}))")
    if len(spec.sources) > 1 and not spec.batched:
        raise ValueError(
            f"{len(spec.sources)} sources with batched=False would "
            "silently run only the first; set batched=True (or submit "
            "one spec per source)")


def _policy_desc(pol: ExecutionPolicy) -> str:
    """Short human tag for a degradation step record."""
    tag = f"{pol.mode}/{pol.kernel.impl}"
    if pol.kernel.fuse_frontier:
        tag += "+fused"
    if pol.mode == "distributed":
        tag += f"/{pol.dist_flavor}"
    return tag


def degrade_policy(pol: ExecutionPolicy) -> Optional[ExecutionPolicy]:
    """One rung down the graceful-degradation ladder, or None at the
    bottom.  Each rung trades the paper's performance machinery for a
    simpler engine that computes the *same values*:

      1. pallas / fused kernel  →  the ``ref`` kernel (same mode).  The
         kernel parity suite pins ref and pallas/fused bit-identical, so
         a degraded result is the healthy result.
      2. ``mode="distributed"``  →  single-device ``mode="sync"``.  The
         distributed engines are bit-identical to sync at convergence,
         so again only the cost changes.  ``GraphProcessor.run`` does not
         take this rung for a plan placed over several devices: the sync
         engine would need a second, whole plan on one device.

    The ladder only changes *how* a query runs, never what it computes —
    which is what lets ``GraphProcessor.run`` retry down it behind the
    caller's back and still honor the bit-identical serving contract.
    """
    if pol.kernel is not None and pol.kernel.impl != "ref":
        return pol.but(kernel=KernelSpec(impl="ref"))
    if pol.mode == "distributed":
        return pol.but(mode="sync", dist_flavor="sync", local_sweeps=1,
                       query_axis=None)
    return None


class GraphProcessor:
    """Prepare-once / query-many session over one graph.

    Holds a plan cache of ``Prepared`` images keyed by ``PlanKey`` so
    repeated and cross-algorithm queries share the compile-time pipeline
    (clustering, permutation, BSR build, device upload), plus derived
    graph variants (unit-weight, undirected) built at most once.

    When ``store`` (a ``serve.graph.PlanStore``) is injected, plans are
    *borrowed* from it instead of owned: every ``prepare`` consults the
    shared store under ``(graph_fingerprint, PlanKey)``, so plans are
    shared across processors, across graphs registered in one
    ``GraphService``, and — through the store's on-disk cache — across
    process restarts.  Eviction then lives in exactly one place (the
    store); the processor keeps no private copy.
    """

    def __init__(self, g: Graph, b: int = 32,
                 num_clusters: Optional[int] = None, clustered: bool = True,
                 seed: int = 0, policy: Optional[ExecutionPolicy] = None,
                 store=None, max_wave: int = eng.MAX_WAVE):
        self.g = g
        self.b = b
        self.num_clusters = num_clusters
        self.clustered = clustered
        self.seed = seed
        self.policy = policy or ExecutionPolicy()
        self.store = store
        self.max_wave = int(max_wave)   # widest batch a plan is sized for
        self._plans: Dict[PlanKey, Prepared] = {}
        self._tunings: Dict[PlanKey, dict] = {}  # session-local fallback
        self._variants: Dict[str, Graph] = {"base": g}
        self._prepare_calls = 0
        self._autotune_calls = 0

    # -- compile-time pipeline (cached) ---------------------------------

    def _variant(self, name: str) -> Graph:
        if name not in self._variants:
            g = self.g
            if name == "unit":
                self._variants[name] = Graph(
                    n=g.n, indptr=g.indptr, indices=g.indices,
                    weights=np.ones(g.nnz, dtype=np.float32))
            elif name == "undirected":
                self._variants[name] = g.to_undirected()
            elif name == "unit_undirected":
                und = self._variant("undirected")
                self._variants[name] = Graph(
                    n=und.n, indptr=und.indptr, indices=und.indices,
                    weights=np.ones(und.nnz, dtype=np.float32))
            else:
                raise ValueError(f"unknown graph variant {name!r}")
        return self._variants[name]

    def plan_key(self, semiring: str, variant: str = "base",
                 pull: bool = True, normalize: Optional[str] = None,
                 devices: int = 1) -> PlanKey:
        return PlanKey(semiring, variant, pull, normalize, self.b,
                       self.num_clusters, self.clustered, self.seed,
                       devices=devices)

    def plan_devices(self, pol: Optional[ExecutionPolicy] = None) -> int:
        """Devices a plan that serves ``pol`` (default: the session's
        policy) is placed over: every device for the distributed
        engines, else one."""
        pol = pol or self.policy
        return len(jax.devices()) if pol.mode == "distributed" else 1

    def prepare(self, semiring: str, variant: str = "base",
                pull: bool = True, normalize: Optional[str] = None,
                kernel: Optional[KernelSpec] = None,
                devices: Optional[int] = None) -> Prepared:
        """Fetch (or build and cache) the Prepared image for a plan.

        With an injected store the lookup (and LRU/byte accounting) is
        delegated; without one, plans live in a session-local dict.
        Passing a ``kernel`` with ``autotune=True`` also runs (or
        fetches) the measured tuning sweep now, so the first query pays
        no calibration latency.  ``devices`` (default:
        ``plan_devices()`` of the session's policy) is part of the
        plan's key: a plan placed on one device and one sharded over a
        mesh are different plans.
        """
        if devices is None:
            devices = self.plan_devices()
        key = self.plan_key(semiring, variant, pull, normalize, devices)
        if self.store is not None:
            p = self.store.get(self.g.fingerprint(), key)
            if p is None:
                self._prepare_calls += 1
                p = self._build(semiring, variant, pull, normalize,
                                devices)
                self.store.put(self.g.fingerprint(), key, p)
        else:
            p = self._plans.get(key)
            if p is None:
                self._prepare_calls += 1
                p = self._build(semiring, variant, pull, normalize,
                                devices)
                self._plans[key] = p
        if kernel is not None and kernel.autotune:
            self._ensure_tuning(p, key, kernel)
        return p

    def _build(self, semiring: str, variant: str, pull: bool,
               normalize: Optional[str], devices: int = 1) -> Prepared:
        return eng.prepare(self._variant(variant), semiring, b=self.b,
                           num_clusters=self.num_clusters, pull=pull,
                           clustered=self.clustered, normalize=normalize,
                           seed=self.seed, devices=devices,
                           max_wave=self.max_wave)

    def cache_info(self) -> dict:
        info = {"plans": len(self._plans),
                "prepare_calls": self._prepare_calls,
                "autotune_calls": self._autotune_calls,
                "tunings": len(self._tunings),
                "keys": list(self._plans)}
        if self.store is not None:
            info["store"] = self.store.stats()
        return info

    # -- measured kernel tunings (cached beside the plan) ----------------

    def _ensure_tuning(self, p: Prepared, key: PlanKey,
                       spec: KernelSpec) -> dict:
        """Fetch-or-measure the tuning record for (plan, spec).  Records
        ride the plan store's ``(fingerprint, PlanKey)`` scheme under
        ``replace(base_key, kernel=spec)`` so warm restarts reuse them;
        without a store they live for the session."""
        from ..kernels import autotune as at
        tkey = dataclasses.replace(key, kernel=spec)
        if self.store is not None and hasattr(self.store, "get_tuning"):
            fp = self.g.fingerprint()
            rec = self.store.get_tuning(fp, tkey)
            if rec is None:
                self._autotune_calls += 1
                rec = at.autotune_spmv(p, spec, seed=self.seed)
                self.store.put_tuning(fp, tkey, rec)
            return rec
        rec = self._tunings.get(tkey)
        if rec is None:
            self._autotune_calls += 1
            rec = at.autotune_spmv(p, spec, seed=self.seed)
            self._tunings[tkey] = rec
        return rec

    def _kernel_for_run(self, p: Prepared, key: PlanKey,
                        spec: KernelSpec) -> KernelSpec:
        """The concrete spec a query executes: autotuned knobs filled in
        from the cached (or freshly measured) tuning record."""
        if spec.impl != "pallas" or not spec.autotune:
            return spec
        return spec.concrete(self._ensure_tuning(p, key, spec))

    # -- unified run entry point ----------------------------------------

    def resolve_policy(self, spec: QuerySpec) -> ExecutionPolicy:
        """The effective policy for a spec: explicit policy (or session
        default merged with the algorithm's registered defaults), then
        ``params`` overrides (translated through the algorithm's
        ``param_map``, so e.g. k-core's ``k`` rides the damping scalar
        slot).  Exposed so the serving layer can group same-policy
        requests for coalescing exactly as ``run`` would execute them."""
        a = get_algorithm(spec.algo)
        pol = spec.policy or self.policy.but(**dict(a.default_policy))
        if spec.params:
            pm = dict(a.param_map)
            pol = pol.but(**{pm.get(k, k): v
                             for k, v in dict(spec.params).items()})
        return pol

    def run(self, spec: QuerySpec) -> Result:
        """Execute one QuerySpec.  All algorithm methods route here.

        Run-time engine failures walk the graceful-degradation ladder
        (see :func:`degrade_policy`) while ``policy.degrade`` is set:
        the query re-executes one rung down, each step recorded in
        ``Result.extra["degraded"]`` as ``{"from", "to", "error"}``.  A
        rung whose plan would be placed on other devices is not taken
        (a distributed plan sharded over a mesh has no single-device
        copy to fall back on): the failure propagates.
        Errors that mean the request itself is wrong (ValueError /
        TypeError / KeyError — bad spec, ineligible flavor, missing
        kernel registration) always propagate: degradation absorbs
        *infrastructure* failures, not caller mistakes.
        """
        validate_spec(spec)
        a = get_algorithm(spec.algo)
        pol = self.resolve_policy(spec)
        if a.runner is not None:
            return getattr(self, a.runner)(spec, pol)
        steps: list = []
        while True:
            try:
                res = self._execute(spec, pol)
            except (ValueError, TypeError, KeyError, IndexError):
                raise
            except Exception as e:
                nxt = degrade_policy(pol) if pol.degrade else None
                if nxt is None or \
                        self.plan_devices(nxt) != self.plan_devices(pol):
                    raise
                steps.append({"from": _policy_desc(pol),
                              "to": _policy_desc(nxt),
                              "error": f"{type(e).__name__}: {e}"})
                pol = nxt
                continue
            if steps:
                res.extra["degraded"] = steps
            return res

    def _execute(self, spec: QuerySpec, pol: ExecutionPolicy) -> Result:
        """One engine attempt at (spec, pol) — the pre-ladder ``run``.

        Timed as three spans (``repro.obs``): ``run.prep`` (plan lookup,
        initial state built and uploaded), ``run.device`` (dispatch until
        the sweep count is on the host) and ``run.fetch`` (device → host
        copies, un-permute, ``post``).  ``run.device`` says how the
        engine gathered source blocks (``gather``: ``"wave"``, once for
        the whole batch, or ``"per_query"``) and for how many queries
        (``q``); a distributed run adds its ``mesh`` (``"4x1"``),
        ``halo_exchanges`` and ``halo_bytes``, the all_gather payload a
        device received over the run (``DistStats.halo_bytes``)."""
        if spec.batched:
            return self._run_batched(spec, pol)
        with obs.span("run.prep"):
            p, kern, x0f, pad, apply_kind, post = self._relaxation_setup(
                spec, pol)
            src = spec.sources[0] if spec.sources else None
            x0 = p.to_blocks(x0f(src), pad)
        with obs.span("run.device", gather="per_query", q=1) as sp:
            x, stats, extra = self._dispatch(pol, p, x0, apply_kind, src,
                                             kern)
            if "dist" in extra:
                sp.attrs.update(extra["dist"].span_attrs())
        with obs.span("run.fetch"):
            values = post(p.from_blocks(x))
        extra = dict(extra, algo=spec.algo,
                     **({"src": src} if src is not None else {}))
        return Result(values, stats, p, extra, policy=pol, graph=self.g)

    # -- registry-driven plan + frontier-init descriptors ----------------

    def _relaxation_setup(self, spec: QuerySpec, pol: ExecutionPolicy):
        """Returns (Prepared, KernelSpec to run, x0_builder(src), pad,
        apply_kind, post) — all read off the algorithm's registered
        ``AlgorithmSpec``; no per-algorithm branching here."""
        a = get_algorithm(spec.algo)
        devices = self.plan_devices(pol)
        key = self.plan_key(a.semiring, variant=a.variant, pull=a.pull,
                            normalize=a.normalize, devices=devices)
        p = self.prepare(a.semiring, variant=a.variant, pull=a.pull,
                         normalize=a.normalize, devices=devices)
        kern = self._kernel_for_run(p, key, pol.kernel)
        pad = float(a.ring.zero) if a.pad is None else a.pad
        post = a.post if a.post is not None else (lambda v: v)
        return p, kern, (lambda src: a.init(p, src, pol)), pad, \
            a.update, post

    def _frontier(self, p: Prepared, src: Optional[int]) -> jnp.ndarray:
        """Initial changed-set for the async engine: just the source's
        row-block when there is a point source, else everything."""
        if src is None:
            return jnp.ones(p.r_pad, dtype=bool)
        ch = np.zeros(p.r_pad, dtype=bool)
        ch[int(p.perm[src]) // p.b] = True
        return jnp.asarray(ch)

    # -- engine dispatch -------------------------------------------------

    def _dispatch(self, pol: ExecutionPolicy, p: Prepared, x0,
                  apply_kind: str, src: Optional[int],
                  kern: Optional[KernelSpec] = None):
        kern = kern if kern is not None else pol.kernel
        kw = dict(apply_kind=apply_kind, damping=pol.damping, tol=pol.tol,
                  max_sweeps=pol.max_sweeps)
        if pol.mode == "sync":
            ch0 = self._frontier(p, src) if kern.fuse_frontier else None
            x, stats = eng.run_sync(p, x0, kernel=kern, changed0=ch0,
                                    **kw)
            return x, stats, {}
        if pol.mode == "async":
            x, stats = eng.run_async(p, x0, kernel=kern,
                                     changed0=self._frontier(p, src), **kw)
            return x, stats, {}
        # distributed: shard_map engine over the device mesh (ref
        # kernels).  dist_flavor picks the exchange schedule: "sync" =
        # bulk-synchronous (one exchange per sweep), "async" = self-timed
        # k-local-sweep engine with overlapped halo (core.async_dist).
        from . import placement
        if pol.dist_flavor == "async":
            from . import async_dist
            x, dist = async_dist.distributed_async_run(
                p, x0, local_sweeps=pol.local_sweeps, **kw)
            stats = eng.dist_run_stats(p, dist)
            return x, stats, {"dist": dist}
        x, dist = placement.distributed_sync_run(p, x0, **kw)
        stats = eng.bsp_stats(p, dist.sweeps, dist.converged,
                              "distributed")
        return x, stats, {"dist": dist}

    def _run_batched(self, spec: QuerySpec,
                     pol: ExecutionPolicy) -> Result:
        sources = list(spec.sources)
        if not sources:
            raise ValueError("batched query needs at least one source")
        dist = pol.mode == "distributed"
        if dist and pol.query_axis == 0:
            return self._run_batched_dist_fallback(spec, pol, sources)
        with obs.span("run.prep"):
            p, kern, x0f, pad, apply_kind, post = self._relaxation_setup(
                spec, pol)
            if dist:
                # Stack on host: the engine pads/shards the frontier
                # itself, so a device-resident stack would round-trip
                # pointlessly.
                x0 = np.stack([np.asarray(p.to_blocks(x0f(s), pad))
                               for s in sources])
            else:
                x0 = jnp.stack([p.to_blocks(x0f(s), pad)
                                for s in sources])
                ch0 = (jnp.stack([self._frontier(p, s) for s in sources])
                       if pol.mode == "async" or kern.fuse_frontier
                       else None)
        extra = {"algo": spec.algo, "sources": sources}
        kw = dict(apply_kind=apply_kind, damping=pol.damping, tol=pol.tol,
                  max_sweeps=pol.max_sweeps)
        # the distributed engines always run the wave kernel
        # (placement.local_spmv); one device only the async ref loop
        wave = dist or (pol.mode == "async" and eng.wave_path(kern))
        with obs.span("run.device", gather="wave" if wave else "per_query",
                      q=len(sources)) as sp:
            if not dist:
                run = (eng.run_async_batched if pol.mode == "async"
                       else eng.run_sync_batched)
                x, stats = run(p, x0, changed0=ch0, kernel=kern, **kw)
            # One 2-D shard_map dispatch: rows over "graph", the query
            # axis over "query" (placement.distributed_sync_run_batched).
            # Bit-identical to the per-source sequential path; `sweeps`
            # is the straggler's, work counters total the query axis.
            elif pol.dist_flavor == "async":
                from . import async_dist
                x, extra["dist"] = \
                    async_dist.distributed_async_run_batched(
                        p, x0, local_sweeps=pol.local_sweeps,
                        query_axis=pol.query_axis, **kw)
                stats = eng.dist_run_stats(p, extra["dist"])
            else:
                from . import placement
                x, d = placement.distributed_sync_run_batched(
                    p, x0, query_axis=pol.query_axis, **kw)
                stats = eng.bsp_stats(
                    p, d.sweeps, d.converged, "distributed",
                    work_sweeps=int(d.query_sweeps.sum()))
                extra["dist"] = d
            if dist:
                sp.attrs.update(extra["dist"].span_attrs())
        with obs.span("run.fetch"):
            values = np.stack([post(p.from_blocks(x[q]))
                               for q in range(len(sources))])
        return Result(values, stats, p, extra, policy=pol, graph=self.g)

    def _run_batched_dist_fallback(self, spec, pol, sources) -> Result:
        """``query_axis=0`` escape hatch: the retired per-source loop
        through the sequential distributed engine.  Kept for debugging
        mesh factorizations against a known-serial reference — the
        default batched path is one 2-D shard_map dispatch."""
        p, _, x0f, pad, apply_kind, post = self._relaxation_setup(spec, pol)
        xs, sweeps, conv = [], [], []
        for s in sources:
            x0q = p.to_blocks(x0f(s), pad)
            xq, st, _ = self._dispatch(pol, p, x0q, apply_kind, s)
            xs.append(xq)
            sweeps.append(st.sweeps)
            conv.append(st.converged)
        stats = eng.bsp_stats(p, max(sweeps), all(conv),
                              "distributed", work_sweeps=sum(sweeps))
        values = np.stack([post(p.from_blocks(xq)) for xq in xs])
        extra = {"algo": spec.algo, "sources": sources,
                 "batched_fallback": "per-source sequential"}
        return Result(values, stats, p, extra, policy=pol,
                      graph=self.g)

    # -- the algorithm catalog (registry-backed convenience methods) -----

    def _spec(self, algo: str, sources, policy, **params) -> QuerySpec:
        batched = sources is not None and not np.isscalar(sources)
        srcs = (tuple(int(s) for s in sources) if batched
                else ((int(sources),) if sources is not None else ()))
        params = {k: v for k, v in params.items() if v is not None}
        if params:
            base = policy or self.policy.but(
                **dict(get_algorithm(algo).default_policy))
            policy = base.but(**params)
        return QuerySpec(algo=algo, sources=srcs, batched=batched,
                         policy=policy)

    def pagerank(self, damping: Optional[float] = None,
                 tol: Optional[float] = None,
                 max_sweeps: Optional[int] = None,
                 policy: Optional[ExecutionPolicy] = None) -> Result:
        """Convergence kwargs override the (given or session) policy;
        defaults are damping=0.85, tol=1e-8, max_sweeps=500."""
        return self.run(self._spec("pagerank", None, policy,
                                   damping=damping, tol=tol,
                                   max_sweeps=max_sweeps))

    def pagerank_delta(self, damping: Optional[float] = None,
                       tol: Optional[float] = None,
                       max_sweeps: Optional[int] = None,
                       policy: Optional[ExecutionPolicy] = None) -> Result:
        """Delta-accumulating PageRank (GraphScale): ranks only rise from
        the (1-damping)/n floor, making the update idempotent/monotone —
        eligible for the async engine and ``dist_flavor="async"``.
        Tolerance-bounded vs the classic sweep (see algorithm catalog)."""
        return self.run(self._spec("pagerank_delta", None, policy,
                                   damping=damping, tol=tol,
                                   max_sweeps=max_sweeps))

    def sssp(self, sources: Union[int, Sequence[int]],
             policy: Optional[ExecutionPolicy] = None) -> Result:
        """Single-source (int) or batched multi-source (sequence)."""
        return self.run(self._spec("sssp", sources, policy))

    def bfs(self, sources: Union[int, Sequence[int]],
            policy: Optional[ExecutionPolicy] = None) -> Result:
        res = self.run(self._spec("bfs", sources, policy))
        res.extra["levels"] = res.values
        return res

    def connected_components(
            self, policy: Optional[ExecutionPolicy] = None) -> Result:
        return self.run(self._spec("cc", None, policy))

    def kcore(self, k: float,
              policy: Optional[ExecutionPolicy] = None) -> Result:
        """k-core membership: values[v] is 1.0 iff v survives k-core
        peeling.  ``k`` is a required query param (rides the policy's
        damping scalar slot via the registry's param_map)."""
        return self.run(QuerySpec(algo="kcore", policy=policy,
                                  params={"k": float(k)}))

    def reachability(self, src: int,
                     policy: Optional[ExecutionPolicy] = None) -> Result:
        return self.run(self._spec("reachability", src, policy))

    def minitri(self, policy: Optional[ExecutionPolicy] = None,
                chunk: int = 65536) -> Result:
        del policy  # one-shot data-parallel: engine policy does not apply
        return self._minitri(chunk)

    def tricount(self, policy: Optional[ExecutionPolicy] = None,
                 chunk: int = 65536) -> Result:
        """Per-vertex triangle counts (each triangle credits its three
        corners once)."""
        del policy  # one-shot data-parallel: engine policy does not apply
        return self._tricount(chunk)

    def dfs(self, src: int,
            policy: Optional[ExecutionPolicy] = None) -> Result:
        return self.run(QuerySpec(algo="dfs", sources=(int(src),),
                                  policy=policy))

    # -- runner hooks: registry dispatch for non-relaxation workloads ----

    def _minitri_runner(self, spec: QuerySpec,
                        pol: ExecutionPolicy) -> Result:
        return self._minitri()

    def _tricount_runner(self, spec: QuerySpec,
                         pol: ExecutionPolicy) -> Result:
        return self._tricount()

    def _dfs_runner(self, spec: QuerySpec,
                    pol: ExecutionPolicy) -> Result:
        return self._dfs(spec.sources[0])

    # -- triangle workloads: one-shot data-parallel intersections --------

    def _oriented_edges(self):
        """Shared compile-time step for the triangle workloads: orient
        the undirected graph low→high by (degree, id) — a DAG with small
        max out-degree — and return (und, k_max, rows, eu, ev) where
        ``rows`` is the (n+1, k_max) sorted ELL neighbour table padded
        with the sentinel row ``n`` and (eu, ev) are the oriented edges.
        Each triangle appears exactly once: as its lowest edge (u, v)
        with the third corner in N+(u) ∩ N+(v)."""
        und = self._variant("undirected")
        deg = und.out_degrees()
        src = np.repeat(np.arange(und.n, dtype=np.int64),
                        np.diff(und.indptr))
        dst = und.indices.astype(np.int64)
        key_s = deg[src] * (und.n + 1) + src
        key_d = deg[dst] * (und.n + 1) + dst
        keep = key_s < key_d
        s2, d2 = src[keep], dst[keep]
        g_plus = Graph.from_edges(und.n, s2.astype(np.int32),
                                  d2.astype(np.int32),
                                  np.ones(len(s2), dtype=np.float32))
        ell = to_ell_fast(g_plus)
        rows = np.vstack([ell.cols, np.full((1, ell.k_max), und.n,
                                            dtype=np.int32)])
        eu = np.repeat(np.arange(und.n, dtype=np.int32),
                       np.diff(g_plus.indptr))
        ev = g_plus.indices.astype(np.int32)
        return und, ell.k_max, rows, eu, ev

    def _minitri(self, chunk: int = 65536) -> Result:
        und, k_max, rows, eu, ev = self._oriented_edges()
        rows_j = jnp.asarray(rows)
        total = 0
        for i in range(0, len(eu), chunk):
            total += int(_tri_count(rows_j, jnp.asarray(eu[i:i + chunk]),
                                    jnp.asarray(ev[i:i + chunk]),
                                    jnp.int32(und.n)))
        e_plus = len(eu)
        # one-shot data-parallel workload: intersections distribute evenly
        # over the NALE array (no dependency chain), so the critical path
        # is total work / array width, not the serial stream
        nales = 256.0
        stats = RunStats(
            sweeps=1, converged=True,
            tile_work=float(e_plus * k_max),
            edge_work=float(e_plus * max(k_max, 1)),
            crit_tiles=float(e_plus * k_max) / nales,
            active_group_sweeps=nales, halo_tiles=0.0, total_groups=1,
            mode="oneshot")
        return Result(np.array([total]), stats, None,
                      {"algo": "minitri", "triangles": total,
                       "oriented_edges": e_plus, "k_max": k_max},
                      policy=None, graph=self.g)

    def _tricount(self, chunk: int = 65536) -> Result:
        """Per-vertex triangle counts over the same oriented-edge table
        as MiniTri: for each oriented edge (u, v), every common
        out-neighbour w closes one triangle — credit u, v, and w."""
        und, k_max, rows, eu, ev = self._oriented_edges()
        counts = np.zeros(und.n, dtype=np.int64)
        # numpy all-pairs matching per edge chunk; K*K comparisons per
        # edge, chunk sized to bound the (chunk, K, K) mask at ~4M cells
        kk = max(k_max * k_max, 1)
        step = max(1, min(chunk, (1 << 22) // kk))
        for i in range(0, len(eu), step):
            u, v = eu[i:i + step], ev[i:i + step]
            a, b = rows[u], rows[v]               # (E, K) neighbour ids
            m = (a[:, :, None] == b[:, None, :]) & \
                (a[:, :, None] != und.n)
            per_edge = m.sum(axis=(1, 2))
            np.add.at(counts, u, per_edge)
            np.add.at(counts, v, per_edge)
            e_idx, i_idx, _ = np.nonzero(m)
            np.add.at(counts, a[e_idx, i_idx], 1)
        total = int(counts.sum() // 3)
        e_plus = len(eu)
        nales = 256.0
        stats = RunStats(
            sweeps=1, converged=True,
            tile_work=float(e_plus * k_max),
            edge_work=float(e_plus * max(k_max, 1)),
            crit_tiles=float(e_plus * k_max) / nales,
            active_group_sweeps=nales, halo_tiles=0.0, total_groups=1,
            mode="oneshot")
        return Result(counts.astype(np.float32), stats, None,
                      {"algo": "tricount", "triangles": total,
                       "oriented_edges": e_plus, "k_max": k_max},
                      policy=None, graph=self.g)

    # -- DFS: sequential stack machine (worst-case-serial) ---------------

    def _dfs(self, src: int) -> Result:
        g = self.g
        ell = to_ell_fast(g)
        n, k = g.n, ell.k_max
        cols = jnp.asarray(ell.cols)  # pad = n

        cap = g.nnz + n + 2

        @jax.jit
        def run():
            stack = jnp.zeros(cap, dtype=jnp.int32).at[0].set(src)
            pstack = jnp.full(cap, -1, dtype=jnp.int32)
            visited = jnp.zeros(n + 1, dtype=bool).at[n].set(True)
            order = jnp.full(n, -1, dtype=jnp.int32)
            parent = jnp.full(n, -1, dtype=jnp.int32)

            def cond(st):
                sp, *_ = st
                return sp > 0

            def body(st):
                sp, stack, pstack, visited, order, parent, cnt = st
                u = stack[sp - 1]
                pu = pstack[sp - 1]
                sp = sp - 1
                fresh = ~visited[u]

                def visit(args):
                    sp, stack, pstack, visited, order, parent, cnt = args
                    visited = visited.at[u].set(True)
                    order = order.at[cnt].set(u)
                    parent = parent.at[u].set(pu)

                    # push neighbours in reverse so lowest pops first
                    def push(i, a):
                        sp, stack, pstack = a
                        v = cols[u, k - 1 - i]
                        ok = ~visited[v]
                        stack = stack.at[sp].set(
                            jnp.where(ok, v, stack[sp]))
                        pstack = pstack.at[sp].set(
                            jnp.where(ok, u, pstack[sp]))
                        return sp + ok.astype(jnp.int32), stack, pstack

                    sp, stack, pstack = jax.lax.fori_loop(
                        0, k, push, (sp, stack, pstack))
                    return (sp, stack, pstack, visited, order, parent,
                            cnt + 1)

                return jax.lax.cond(
                    fresh, visit, lambda a: a,
                    (sp, stack, pstack, visited, order, parent, cnt))

            st = (jnp.int32(1), stack, pstack, visited, order, parent,
                  jnp.int32(0))
            sp, stack, pstack, visited, order, parent, cnt = \
                jax.lax.while_loop(cond, body, st)
            return order, parent, cnt

        order, parent, cnt = run()
        stats = RunStats(
            sweeps=int(cnt), converged=True,
            tile_work=float(int(cnt) * k), edge_work=float(g.nnz),
            crit_tiles=float(int(cnt) * k),
            active_group_sweeps=float(int(cnt)),
            halo_tiles=0.0, total_groups=1, mode="sequential")
        return Result(np.asarray(order), stats, None,
                      {"algo": "dfs", "src": src,
                       "parent": np.asarray(parent),
                       "visited_count": int(cnt)},
                      policy=None, graph=self.g)


@jax.jit
def _tri_count(rows: jnp.ndarray, eu: jnp.ndarray, ev: jnp.ndarray,
               sentinel: jnp.int32) -> jnp.ndarray:
    """rows: (n+1, k) sorted neighbour ids padded with `sentinel`; (eu, ev)
    oriented edges.  Batched sorted-intersection via searchsorted."""

    def one(u, v):
        a, bb = rows[u], rows[v]
        pos = jnp.searchsorted(bb, a)
        pos = jnp.clip(pos, 0, bb.shape[0] - 1)
        hit = (bb[pos] == a) & (a != sentinel)
        return jnp.sum(hit)

    return jnp.sum(jax.vmap(one)(eu, ev))
