"""CSR graphs for the benchmark's generators.

``from_edges`` and ``to_undirected`` are copies of the system's
``core/graph.Graph.from_edges`` / ``Graph.to_undirected``, kept here so
that no change to the system can change the benchmark's data.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Csr:
    """Host CSR graph: ``indptr`` int64 (n+1,), ``indices`` int32,
    ``weights`` float32."""

    n: int
    indptr: np.ndarray
    indices: np.ndarray
    weights: np.ndarray

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def out_degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    def sources(self) -> np.ndarray:
        """Source vertex of every edge, in CSR order."""
        return np.repeat(np.arange(self.n, dtype=np.int64),
                         self.out_degrees())


def from_edges(n: int, src: np.ndarray, dst: np.ndarray,
               weights: np.ndarray, dedup: bool = True) -> Csr:
    """Sorted CSR from an edge list; ``dedup`` keeps the first copy of
    each (src, dst) pair."""
    if dedup and len(src):
        key = src.astype(np.int64) * n + dst.astype(np.int64)
        _, keep = np.unique(key, return_index=True)
        src, dst, weights = src[keep], dst[keep], weights[keep]
    order = np.lexsort((dst, src))
    src, dst, weights = src[order], dst[order], weights[order]
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, src + 1, 1)
    indptr = np.cumsum(indptr)
    return Csr(n=n, indptr=indptr, indices=dst.astype(np.int32),
               weights=weights.astype(np.float32))


def to_undirected(g: Csr) -> Csr:
    """Both directions of every edge, duplicates dropped."""
    src = g.sources().astype(np.int32)
    dst = g.indices.astype(np.int32)
    return from_edges(g.n, np.concatenate([src, dst]),
                      np.concatenate([dst, src]),
                      np.concatenate([g.weights, g.weights]), dedup=True)
