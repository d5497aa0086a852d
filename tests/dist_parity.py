"""Bit-identity of the batched distributed engines with the per-source
sequential distributed engine, on four virtual CPU devices.

Every built-in semiring runs on meshes (1,1), (4,1), (2,2) and (1,4)
with a wave of 5 queries, which is not a multiple of the "query" extent
2 or 4, so padding queries run beside the real ones.  The reference is
``placement.distributed_sync_run``, one source at a time on a (4,1)
mesh.  Run by ``tests/test_distribution.py`` (``sync``) and
``tests/test_async_dist.py`` (``async``):

    PYTHONPATH=src python tests/dist_parity.py sync|async

prints one line, ``RESULT`` and a JSON object keyed
``"<semiring>/<devices>x<query>"`` (async: ``".../k<local_sweeps>"``).
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np  # noqa: E402

from repro.core import async_dist as AD  # noqa: E402
from repro.core import engine as E  # noqa: E402
from repro.core import graph as G  # noqa: E402
from repro.core import placement as PL  # noqa: E402

SEMIRINGS = ("min_plus", "min_select", "max_min", "plus_times")
MESHES = ((1, 1), (4, 1), (4, 2), (4, 4))   # (devices, "query" extent)
KS = (1, 2, 4)
SOURCES = (0, 5, 9, 13, 17)


def fixture(semiring):
    """(Prepared, stacked x0 (Q, r_pad, B), update rule, damping)."""
    g = G.rmat(200, 900, seed=6)
    if semiring == "plus_times":
        # k-core peeling (k=2 rides damping): sums of 0/1, exact in any
        # order; each query starts from its own live set
        g = G.Graph(n=g.n, indptr=g.indptr, indices=g.indices,
                    weights=np.ones_like(g.weights))
        p = E.prepare(g, semiring, b=8, num_clusters=8)
        starts = [(np.random.default_rng(s).random(g.n) < 0.8)
                  .astype(np.float32) for s in SOURCES]
        return p, np.stack([np.asarray(p.to_blocks(x, 0.0))
                            for x in starts]), "kcore", 2.0
    p = E.prepare(g, semiring, b=8, num_clusters=8)
    zero, src = (0.0, 1.0) if semiring == "max_min" else (np.inf, 0.0)
    starts = []
    for s in SOURCES:
        x = np.full(g.n, zero, np.float32)
        x[s] = src
        starts.append(np.asarray(p.to_blocks(x, zero)))
    return p, np.stack(starts), "relax", 0.85


def main(flavor):
    out = {}
    for semiring in SEMIRINGS:
        p, x0, rule, damping = fixture(semiring)
        kw = dict(damping=damping, max_sweeps=100_000)
        seq = [PL.distributed_sync_run(p, xq, rule,
                                       mesh=PL.make_graph_mesh(4), **kw)
               for xq in x0]
        ref = np.stack([np.asarray(x) for x, _ in seq])
        ref_sweeps = [d.sweeps for _, d in seq]
        for nd, qa in MESHES:
            mesh = PL.make_graph_mesh(nd, qa)
            name = f"{semiring}/{nd // qa}x{qa}"
            runs = ([(name, PL.distributed_sync_run_batched(
                p, x0, rule, mesh=mesh, **kw))] if flavor == "sync" else
                [(f"{name}/k{k}", AD.distributed_async_run_batched(
                    p, x0, rule, mesh=mesh, local_sweeps=k, **kw))
                 for k in KS])
            for key, (x, ds) in runs:
                out[key] = dict(
                    equal=bool(np.array_equal(np.asarray(x), ref)),
                    converged=bool(ds.converged),
                    mesh=list(ds.mesh_shape),
                    query_sweeps=[int(s) for s in ds.query_sweeps],
                    ref_sweeps=ref_sweeps, sweeps=int(ds.sweeps),
                    local_sweeps=int(ds.local_sweeps),
                    shard_sweeps=(None if ds.shard_sweeps is None else
                                  [int(s) for s in ds.shard_sweeps]))
    print("RESULT" + json.dumps(out))


if __name__ == "__main__":
    main(sys.argv[1])
