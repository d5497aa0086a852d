"""Chip smoke test: the served graph path on a TPU at full road-graph size.

Drives the system once through the entry points a user calls, on the
paper's CA road graph at scale 1.0 (about 1.96M vertices and 5.2M edges,
generated from ``--seed``):

  python chip_smoke.py            # one chip: GraphServer wave of 8
                                  # SSSP sources, BFS, PageRank, and one
                                  # SSSP on the fused Pallas kernel
  python chip_smoke.py --chips 4  # only the distributed path: batched
                                  # 8-source SSSP, sync and async
                                  # flavors, meshes (4, 1) and (2, 2)

Every query runs with ``degrade=False`` and is checked against the numpy
oracles of ``repro.core.oracles`` (computed in CPU-only worker
processes while the chip works) at the tier-1 tests' tolerances.
Timings and plan sizes go to earlier lines, each labelled with the
device.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero, with no such line, when JAX finds no TPU or
any check fails.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import multiprocessing
import os
import sys
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))

GRAPH, SCALE, B, CLUSTERS, WAVE = "ca", 1.0, 16, 64, 8
ORACLE_SOURCES = 3   # wave sources checked against Dijkstra
PAGERANK_L1 = 0.05   # a uniform vector is ~0.2 away on this graph


class SmokeFailure(RuntimeError):
    """A smoke check failed."""


def check(ok, what):
    # a raise, not ``assert``: the checks must survive ``python -O``
    if not ok:
        raise SmokeFailure(what)


def _oracle(job, n, indptr, indices, weights, src):
    """One numpy oracle, run in a CPU-only worker process."""
    os.environ["JAX_PLATFORMS"] = "cpu"   # before repro imports jax
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.core import oracles
    from repro.core.graph import Graph
    g = Graph(n=n, indptr=indptr, indices=indices, weights=weights)
    if job == "sssp":
        return oracles.sssp_oracle(g, src)
    if job == "bfs":
        return oracles.bfs_oracle(g, src)
    return oracles.pagerank_oracle(g, tol=1e-12)


class Compiles:
    """Seconds JAX spends tracing, lowering and compiling, and the
    persistent compile-cache hits, per phase."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.seconds, self.hits = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    @contextlib.contextmanager
    def phase(self, out: dict):
        c0, h0, t0 = self.seconds, self.hits, time.perf_counter()
        yield
        out["latency_s"] = time.perf_counter() - t0
        out["compile_s"] = self.seconds - c0
        out["run_s"] = out["latency_s"] - out["compile_s"]
        out["cache_hits"] = self.hits - h0


class Smoke:
    def __init__(self, args, tag: str, budget: int):
        import numpy as np
        from repro import api
        from repro.core import graph as G
        self.np, self.api, self.tag = np, api, tag
        self.compiles = Compiles()
        t0 = time.perf_counter()
        self.g = G.make_paper_graph(GRAPH, scale=SCALE, seed=args.seed)
        self.report("graph", name=GRAPH, scale=SCALE, n=self.g.n,
                    edges=self.g.nnz, seconds=time.perf_counter() - t0)
        rng = np.random.default_rng(args.seed)
        self.sources = [int(s) for s in
                        rng.choice(self.g.n, WAVE, replace=False)]
        self.policy = api.ExecutionPolicy(degrade=False)
        self.svc = api.GraphService(max_plan_bytes=budget,
                                    policy=self.policy)
        self.proc = self.svc.register(GRAPH, self.g, b=B,
                                      num_clusters=CLUSTERS)
        self.pool = ProcessPoolExecutor(
            max_workers=ORACLE_SOURCES + 2,
            mp_context=multiprocessing.get_context("spawn"))
        self.oracles = {}

    def close(self):
        self.pool.shutdown(cancel_futures=True)

    def report(self, what: str, **kv):
        body = " ".join(f"{k}={v}" for k, v in kv.items())
        print(f"[{self.tag}] {what} {body}", flush=True)

    def oracle(self, job: str, src=None):
        key = (job, src)
        if key not in self.oracles:
            g = self.g
            self.oracles[key] = self.pool.submit(
                _oracle, job, g.n, g.indptr, g.indices, g.weights, src)
        return self.oracles[key]

    def build_plan(self, algo: str):
        from repro.core.algorithms import get_algorithm
        a = get_algorithm(algo)
        out = {}
        with self.compiles.phase(out):
            p = self.proc.prepare(a.semiring, variant=a.variant,
                                  pull=a.pull, normalize=a.normalize)
            p.vals.block_until_ready()
        self.report(f"plan/{algo}", build_s=out["latency_s"],
                    plan_bytes=p.nbytes, r_pad=p.r_pad, k=p.k_max,
                    tiles=int(p.tiles_total))
        return p

    def wave(self, what: str, policy=None):
        """``WAVE`` concurrent single-source SSSP submits to a paused
        server, released together so they coalesce into one wave."""
        api = self.api
        spec = [api.QuerySpec(algo="sssp", sources=(s,), policy=policy)
                for s in self.sources]
        out = {}
        with api.GraphServer(service=self.svc, autostart=False,
                             warm_limit=0) as server:
            with ThreadPoolExecutor(WAVE) as ex:
                futs = list(ex.map(lambda q: server.submit(GRAPH, q),
                                   spec))
            with self.compiles.phase(out):
                server.start()
                res = [f.result() for f in futs]
        for r in res:
            check(r.extra.get("coalesced") == WAVE,
                  f"{what}: not one wave of {WAVE}: {r.extra}")
            check("degraded" not in r.extra,
                  f"{what}: degraded {r.extra.get('degraded')}")
        self.report(what, queries=WAVE, **out,
                    sweeps=res[0].stats.sweeps,
                    converged=res[0].stats.converged)
        check(res[0].stats.converged, f"{what}: did not converge")
        values = self.np.stack([r.values for r in res])
        for i, s in enumerate(self.sources[:ORACLE_SOURCES]):
            self.np.testing.assert_allclose(
                values[i], self.oracle("sssp", s).result(), rtol=1e-5,
                atol=1e-4)
        return values, res[0]

    def query(self, what: str, spec):
        out = {}
        with self.api.GraphServer(service=self.svc, warm_limit=0) as server:
            with self.compiles.phase(out):
                r = server.run(GRAPH, spec)
        check("degraded" not in r.extra,
              f"{what}: degraded {r.extra.get('degraded')}")
        self.report(what, **out, sweeps=r.stats.sweeps,
                    converged=r.stats.converged)
        check(r.stats.converged, f"{what}: did not converge")
        return r

    def one_chip(self):
        import jax
        import jax.numpy as jnp
        api, np = self.api, self.np
        s0 = self.sources[0]
        for s in self.sources[:ORACLE_SOURCES]:
            self.oracle("sssp", s)
        self.oracle("bfs", s0)
        self.oracle("pagerank")
        p_sssp = self.build_plan("sssp")
        self.build_plan("bfs")
        self.build_plan("pagerank")

        wave, _ = self.wave("sssp/wave")

        r = self.query("bfs", api.QuerySpec(algo="bfs", sources=(s0,)))
        np.testing.assert_array_equal(r.values,
                                      self.oracle("bfs", s0).result())

        r = self.query("pagerank", api.QuerySpec(algo="pagerank"))
        pr = self.oracle("pagerank").result()
        err = np.abs(r.values - pr)
        self.report("pagerank/check", max_abs_err=float(err.max()),
                    l1_err=float(err.sum()))
        check(err.max() < 1e-5 and abs(r.values.sum() - 1.0) < 1e-5,
              "pagerank: off the oracle")
        check(err.sum() < PAGERANK_L1, "pagerank: L1 error too large")

        fused = api.KernelSpec(impl="pallas", fuse_frontier=True)
        polf = self.policy.but(kernel=fused)
        r = self.query("sssp/fused", api.QuerySpec(
            algo="sssp", sources=(s0,), policy=polf))
        np.testing.assert_array_equal(r.values, wave[0])
        # the program the engine runs holds the compiled Mosaic kernel
        from repro.kernels import ops
        spmv = ops.select_kernel("bsr_spmv", fused)
        x = jnp.zeros((p_sssp.r_pad, p_sssp.b), jnp.float32)
        act = jnp.ones(p_sssp.r_pad, bool)
        hlo = jax.jit(lambda v, c, n, x, ok, a: spmv(
            v, c, n, x, x, ok, a, 0.85, 1e-6, 1.0, semiring="min_plus",
            apply_kind="relax")).lower(
                p_sssp.vals, p_sssp.cols, p_sssp.nnz, x, p_sssp.valid,
                act).as_text()
        check("tpu_custom_call" in hlo,
              "fused kernel did not lower to a Mosaic custom call")
        self.report("sssp/fused/check", tpu_custom_call=True,
                    equal_to_ref=True)

    def four_chips(self):
        import jax
        np = self.np
        for s in self.sources[:ORACLE_SOURCES]:
            self.oracle("sssp", s)
        self.build_plan("sssp")
        first = None
        for flavor, query_axis, local_sweeps in (
                ("sync", 1, 1), ("sync", 2, 1),
                ("async", 1, 4), ("async", 2, 4)):
            pol = self.policy.but(mode="distributed", dist_flavor=flavor,
                                  query_axis=query_axis,
                                  local_sweeps=local_sweeps)
            mesh = (4 // query_axis, query_axis)
            values, r = self.wave(
                f"sssp/wave/distributed/{flavor}/mesh{mesh[0]}x{mesh[1]}",
                policy=pol)
            dist = r.extra["dist"]
            self.report("dist", mesh=dist.mesh_shape,
                        halo_bytes_per_sweep=dist.halo_bytes_per_sweep,
                        halo_exchanges=dist.halo_exchanges,
                        plan_devices=dist.plan_devices,
                        plan_shard_rows=dist.plan_shard_rows)
            check(tuple(dist.mesh_shape) == mesh, f"mesh {dist.mesh_shape}")
            # plan rows split over "graph", each device holding its own
            check(dist.plan_devices == len(jax.devices()) == 4,
                  f"plan on {dist.plan_devices} devices")
            check(dist.plan_shard_rows == -(-r.prepared.r_pad // mesh[0]),
                  f"{dist.plan_shard_rows} plan rows per device")
            check(dist.halo_bytes_per_sweep > 0, "no halo exchanged")
            if first is None:
                first = values
            np.testing.assert_array_equal(values, first)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served single-chip path; 4: only the "
                         "distributed path, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{dev.platform!r}); this smoke runs on a TPU only",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    tag = f"{dev.platform}/{dev.device_kind}/x{len(devs)}"
    # plans may fill the chip: the store's budget is its memory
    budget = dev.memory_stats()["bytes_limit"]
    smoke = Smoke(args, tag, budget)
    try:
        if args.chips == 4:
            smoke.four_chips()
        else:
            smoke.one_chip()
        stats = smoke.svc.stats()["plan_store"]
        check(stats["evictions"] == 0, f"plans evicted: {stats}")
        smoke.report("memory", plan_store_bytes=stats["bytes"],
                     peak_bytes_in_use=dev.memory_stats().get(
                         "peak_bytes_in_use"))
    finally:
        smoke.close()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
