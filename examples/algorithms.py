"""Algorithm-catalog tour — the ``AlgorithmSpec`` registry end to end.

Part 1 walks the registry: every registered algorithm with its semiring,
update rule, and async-eligibility, then runs the four PR-9 families
(pagerank_delta / cc / kcore / tricount) on one graph through several
engine flavors — including delta-form PageRank on the self-timed
distributed engine (``dist_flavor="async"``), which the classic
accumulation form cannot use.

Part 2 registers a NEW algorithm from scratch — best-reliability paths
over a custom max-times semiring — and runs it through the same
``GraphProcessor.run(QuerySpec)`` front door with zero engine edits.

  PYTHONPATH=src python examples/algorithms.py
"""

import sys

sys.path.insert(0, ".")  # allow running from repo root

import numpy as np  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from repro import algebra, api  # noqa: E402
from repro.core import graph as G  # noqa: E402


def tour_catalog(proc):
    print("== registered algorithms ==")
    hdr = (f"{'algorithm':14s} {'semiring':14s} {'update':15s} "
           f"{'async-eligible':>14s} {'dist-async':>10s}")
    print(hdr)
    print("-" * len(hdr))
    for name in api.registered_algorithms():
        a = api.get_algorithm(name)
        if a.runner is not None:
            print(f"{name:14s} {'—':14s} {'(one-shot/host)':15s} "
                  f"{'—':>14s} {'—':>10s}")
            continue
        rule = algebra.rule(a.update)
        print(f"{name:14s} {a.semiring:14s} {a.update:15s} "
              f"{'yes':>14s} {'yes' if rule.monotone else 'no':>10s}")

    print("\n== the PR-9 families across engine flavors ==")
    flavors = {
        "sync": api.ExecutionPolicy(mode="sync"),
        "async": api.ExecutionPolicy(mode="async"),
        "dist-async(k=2)": api.ExecutionPolicy(
            mode="distributed", dist_flavor="async", local_sweeps=2),
    }
    for fname, pol in flavors.items():
        r = proc.pagerank_delta(policy=pol.but(tol=1e-9, max_sweeps=2000))
        top = int(np.argmax(np.asarray(r.values)))
        print(f"pagerank_delta [{fname:15s}] top vertex {top:4d} "
              f"mass {float(np.asarray(r.values)[top]):.5f} "
              f"sweeps {r.stats.sweeps}")
    r = proc.run(api.QuerySpec(algo="cc"))
    ncomp = len(np.unique(np.asarray(r.values)))
    print(f"cc             components: {ncomp}")
    for k in (2, 3):
        r = proc.kcore(k)
        print(f"kcore k={k}      members: "
              f"{int(np.asarray(r.values).sum())}/{proc.g.n}")
    r = proc.tricount()
    print(f"tricount       triangles: {r.extra['triangles']} "
          f"(max per-vertex {int(np.asarray(r.values).max())})")

    print("\nclassic pagerank on the self-timed distributed engine "
          "(order-sensitive — rejected):")
    try:
        proc.run(api.QuerySpec(algo="pagerank", policy=flavors[
            "dist-async(k=2)"]))
    except ValueError as e:
        print(f"  ValueError: {e}")


def register_reliability():
    """A new algorithm = a semiring + an AlgorithmSpec. Nothing else."""
    if "max_times" not in algebra.SEMIRINGS:
        algebra.register(algebra.Semiring(
            name="max_times",          # ⊕ = max, ⊗ = × over [0, 1]
            add=jnp.maximum,
            mul=jnp.multiply,
            zero=0.0,                  # absorbs under ⊗ — the contract
            one=1.0,
            improves=lambda new, old: new > old,
            reduce_fn=lambda x, axis=None: jnp.max(x, axis=axis),
        ))
    if "reliability" not in api.registered_algorithms():
        api.register_algorithm(api.AlgorithmSpec(
            name="reliability",
            semiring="max_times",
            update="relax",            # idempotent ⇒ every flavor eligible
            source_required=True,
            coalescible=True,
            init=lambda p, src, pol: np.where(
                np.arange(p.n) == src, 1.0, 0.0).astype(np.float32),
            default_policy=(("max_sweeps", 10_000),),
        ))


def main():
    g = G.rmat(400, 2400, seed=3)
    proc = api.GraphProcessor(g, b=16, num_clusters=16)
    tour_catalog(proc)

    print("\n== registering a custom algorithm: best-reliability paths ==")
    register_reliability()
    # reuse the same session: weights squashed into (0, 1] probabilities
    gp = G.Graph(n=g.n, indptr=g.indptr, indices=g.indices,
                 weights=(1.0 / (1.0 + g.weights)).astype(np.float32))
    proc2 = api.GraphProcessor(gp, b=16, num_clusters=16)
    # the registered ring runs on the ref kernels and, as well, on the
    # Pallas kernel with the fused apply rule
    fused = api.KernelSpec(impl="pallas", fuse_frontier=True)
    for mode, kernel in (("sync", None), ("async", None), ("async", fused)):
        r = proc2.run(api.QuerySpec(
            algo="reliability", sources=(0,),
            policy=api.ExecutionPolicy(mode=mode, kernel=kernel)))
        v = np.asarray(r.values)
        reach = int((v > 0).sum())
        name = mode if kernel is None else "fused"
        print(f"reliability [{name:5s}] reachable {reach}/{gp.n}, "
              f"best non-source path p={float(np.sort(v)[-2]):.4f}, "
              f"sweeps {r.stats.sweeps}")


if __name__ == "__main__":
    main()
