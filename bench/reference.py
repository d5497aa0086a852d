"""The plain reference that decides ``correct``, and its controls.

It imports nothing of the system under test.  ``sssp_oracle``,
``bfs_oracle`` and ``pagerank_oracle`` are copies of the system's
``core/oracles.py`` (heap Dijkstra, level-synchronous BFS, power
iteration with dangling-drop semantics and a final L1 renormalisation).
They are too slow in pure Python at full size, so a run compares
against ``sssp``, ``bfs`` and ``pagerank`` below: the same semantics
through SciPy's compiled Dijkstra and sparse products, in float64.  The
tests pin the fast forms to the copied oracles on small graphs.

The ``*_control`` functions are the reference put in the system's place
one step down in precision (or, for BFS, with a guarantee broken); they
must come out as not correct.
"""

from __future__ import annotations

import heapq

import numpy as np

from .data.csr import Csr

#: PageRank reference tolerance: far below the system's own 1e-8, so the
#: reference's own error is negligible next to what is compared
PAGERANK_TOL = 1e-14
PAGERANK_MAX_ITER = 2000
#: bfloat16 ranks never settle to 1e-14; the control stops where the
#: system's own PageRank does at the latest (max_sweeps 500)
CONTROL_MAX_ITER = 500


# -- copied oracles (small sizes: tests) ------------------------------------


def pagerank_oracle(g: Csr, damping: float = 0.85, tol: float = 1e-8,
                    max_iter: int = 500) -> np.ndarray:
    n = g.n
    outdeg = np.diff(g.indptr)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1), 0.0)
    x = np.full(n, 1.0 / n)
    src = np.repeat(np.arange(n), outdeg)
    for _ in range(max_iter):
        contrib = x[src] * inv[src]
        y = np.zeros(n)
        np.add.at(y, g.indices, contrib)
        x_new = (1 - damping) / n + damping * y
        if np.max(np.abs(x_new - x)) <= tol:
            x = x_new
            break
        x = x_new
    return x / x.sum()


def sssp_oracle(g: Csr, src: int) -> np.ndarray:
    dist = np.full(g.n, np.inf)
    dist[src] = 0.0
    pq = [(0.0, src)]
    while pq:
        d, u = heapq.heappop(pq)
        if d > dist[u]:
            continue
        for e in range(g.indptr[u], g.indptr[u + 1]):
            v, w = g.indices[e], g.weights[e]
            nd = d + w
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(pq, (nd, int(v)))
    return dist


def bfs_oracle(g: Csr, src: int) -> np.ndarray:
    level = np.full(g.n, np.inf)
    level[src] = 0
    frontier = [src]
    d = 0
    while frontier:
        nxt = []
        for u in frontier:
            for e in range(g.indptr[u], g.indptr[u + 1]):
                v = g.indices[e]
                if level[v] == np.inf:
                    level[v] = d + 1
                    nxt.append(int(v))
        frontier = nxt
        d += 1
    return level


# -- the same semantics, compiled (full size) --------------------------------


def _matrix(g: Csr, weights=None):
    import scipy.sparse as sp
    w = g.weights if weights is None else weights
    return sp.csr_matrix((np.asarray(w, np.float64), g.indices, g.indptr),
                         shape=(g.n, g.n))


def sssp(g: Csr, src: int, weights=None) -> np.ndarray:
    """float64 shortest-path distances from ``src`` (inf: unreachable)."""
    from scipy.sparse.csgraph import dijkstra
    return dijkstra(_matrix(g, weights), directed=True, indices=int(src))


def bfs(g: Csr, src: int) -> np.ndarray:
    """Hop levels from ``src`` (inf: unreachable)."""
    from scipy.sparse.csgraph import dijkstra
    return dijkstra(_matrix(g), directed=True, unweighted=True,
                    indices=int(src))


def _pull_matrix(g: Csr):
    """y = M @ x sums x[j] / outdeg(j) over in-edges j -> i."""
    import scipy.sparse as sp
    outdeg = np.diff(g.indptr)
    inv = np.where(outdeg > 0, 1.0 / np.maximum(outdeg, 1), 0.0)
    src = np.repeat(np.arange(g.n), outdeg)
    return sp.csr_matrix((inv[src], (g.indices, src)), shape=(g.n, g.n))


def pagerank(g: Csr, damping: float, tol: float = PAGERANK_TOL,
             round_to=None, max_iter: int = PAGERANK_MAX_ITER
             ) -> np.ndarray:
    """Power iteration to ``tol``, dangling mass dropped, L1-normalised.
    ``round_to`` (a dtype) rounds the ranks and the edge weights to it
    before every product, as a lower-precision product would."""
    m = _pull_matrix(g)
    if round_to is not None:
        m.data = m.data.astype(round_to).astype(np.float64)
    n = g.n
    x = np.full(n, 1.0 / n)
    for _ in range(max_iter):
        xin = x if round_to is None else \
            x.astype(round_to).astype(np.float64)
        x_new = (1 - damping) / n + damping * (m @ xin)
        if np.max(np.abs(x_new - x)) <= tol:
            x = x_new
            break
        x = x_new
    return x / x.sum()


# -- controls ----------------------------------------------------------------


def bf16():
    import ml_dtypes
    return ml_dtypes.bfloat16


def sssp_control(g: Csr, src: int) -> np.ndarray:
    """Dijkstra on bfloat16 weights, distances stored in bfloat16."""
    w = g.weights.astype(bf16()).astype(np.float64)
    return sssp(g, src, weights=w).astype(bf16()).astype(np.float64)


def bfs_control(g: Csr, src: int) -> np.ndarray:
    """Levels are small integers, exact in bfloat16, so precision cannot
    be the control here: this one breaks the exact-levels guarantee by
    stopping one level early (the deepest level left unvisited)."""
    lv = bfs(g, src)
    finite = np.isfinite(lv)
    deepest = lv[finite].max()
    if deepest > 0:
        lv[lv == deepest] = np.inf
    return lv


def pagerank_control(g: Csr, damping: float) -> np.ndarray:
    """Power iteration with bfloat16 ranks and weights in every product
    (float64 accumulation): what a default-precision TPU product does."""
    return pagerank(g, damping, round_to=bf16(),
                    max_iter=CONTROL_MAX_ITER)
