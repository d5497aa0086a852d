"""Graph500 Kronecker graph: initiator A, B, C (D = 1 - A - B - C).

A copy of the system's ``core/graph.rmat`` with the Graph500 initiator
(A=0.57, B=0.19, C=0.19), 2**scale vertices and ``edgefactor`` * 2**scale
sampled edges, symmetrised; self-loops and duplicates are dropped and
vertex labels are not permuted.  The topology is drawn from the
configuration's ``topology_seed`` with the original's random stream, so
scale 16 with seed 0 gives 65,536 vertices and 1,819,834 directed edges.
Edge weights, U[``weight_low``, ``weight_high``) and equal in both
directions of an edge, are drawn from the run's ``--seed``.
"""

from __future__ import annotations

import numpy as np

from .. import harness
from .csr import Csr, from_edges, to_undirected


def generate(params: dict, seed: int) -> Csr:
    scale = int(params["scale"])
    n = 1 << scale
    nnz = int(params["edgefactor"]) * n
    a, b, c = (float(params[k]) for k in ("a", "b", "c"))
    rng = np.random.default_rng(int(params["topology_seed"]))
    m = int(nnz * 1.15) + 16   # oversample; dedup trims
    src = np.zeros(m, dtype=np.int64)
    dst = np.zeros(m, dtype=np.int64)
    for _ in range(scale):
        r = rng.random(m)
        quad = np.select([r < a, r < a + b, r < a + b + c], [0, 1, 2],
                         default=3)
        src = src * 2 + (quad >> 1)
        dst = dst * 2 + (quad & 1)
    keep = (src < n) & (dst < n) & (src != dst)
    src, dst = src[keep][:nnz], dst[keep][:nnz]
    directed = from_edges(n, src.astype(np.int32), dst.astype(np.int32),
                          np.ones(len(src), dtype=np.float32))
    g = to_undirected(directed)
    # one weight per undirected pair, drawn in pair order
    s = g.sources()
    d = g.indices.astype(np.int64)
    pair = np.minimum(s, d) * n + np.maximum(s, d)
    uniq, inv = np.unique(pair, return_inverse=True)
    lo, hi = float(params["weight_low"]), float(params["weight_high"])
    w = harness.rng(seed, 1).random(len(uniq)) * (hi - lo) + lo
    g.weights = w[inv].astype(np.float32)
    return g
