"""Road-network stand-in: a lattice of two-way streets with shortcuts.

Built from the system's ``core/graph.road_network`` (the lattice and the
shortcuts are drawn with the same random stream), but every street runs
both ways, as in SNAP roadNet-CA, which is undirected: ``keep_frac`` of
the lattice edges are kept, ``extra_frac * n`` random shortcuts added,
and each kept edge is stored in both directions with one weight.  With
``side=1401, keep_frac=0.68, extra_frac=0.05, topology_seed=0`` that is
1,962,801 vertices and 5,531,706 directed (2,765,853 undirected) edges,
against roadNet-CA's 1,965,206 vertices and 5,533,214 (2,766,607).

The edge weights, U[``weight_low``, ``weight_high``), are drawn from the
run's ``--seed``: the graph is one fixed deployment, and every run
weighs its streets anew.
"""

from __future__ import annotations

import numpy as np

from .. import harness
from .csr import Csr, from_edges


def generate(params: dict, seed: int) -> Csr:
    side = int(params["side"])
    rng = np.random.default_rng(int(params["topology_seed"]))
    n = side * side
    vid = np.arange(n).reshape(side, side)
    right = vid[:, :-1].ravel()
    down = vid[:-1, :].ravel()
    src = np.concatenate([right, down])
    dst = np.concatenate([right + 1, down + side])
    keep = rng.random(len(src)) < float(params["keep_frac"])
    n_extra = int(float(params["extra_frac"]) * n)
    s = np.concatenate([src[keep], rng.integers(0, n, n_extra)])
    d = np.concatenate([dst[keep], rng.integers(0, n, n_extra)])
    loop = s == d
    s, d = s[~loop], d[~loop]
    lo, hi = float(params["weight_low"]), float(params["weight_high"])
    w = (harness.rng(seed, 1).random(len(s)) * (hi - lo) + lo)
    w = w.astype(np.float32)
    return from_edges(n, np.concatenate([s, d]).astype(np.int32),
                      np.concatenate([d, s]).astype(np.int32),
                      np.concatenate([w, w]))
